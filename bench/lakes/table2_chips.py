"""The Table II lake made on the cell's chips, with its reference there.

The lake of ``table2`` (``harness/lake.py``), for lakes the host cannot
make or check within a run: 2^23 columns of 4,000 rows hold 33.5e9
rows.  ``chunk`` makes each chunk in ``jax.numpy`` on the chip the
harness gives it, from the same 32-bit integer hashes, Zipf table and
float32 arithmetic.  Its key hashes are
bit-identical to ``table2``'s for the same seed.  A device may fuse
``alpha * latent + (1 - alpha) * noise`` into one multiply-add, or
reassociate the products, which moves a value by an ulp and a
categorical code where the value lies within an ulp of a code
boundary; the generator keeps each product rounded on its own, so its
values and codes come out bit-identical as well.  The reference reads
its values from this same generator either way, so it holds the
program to the data it was given.  ``keep_rows`` keeps nothing: the
reference makes the rows again, on the chips.

The reference runs on the machine's chips after the window.
``LakeSketches`` makes each column's rows again, sorts them by the
candidate sketch's rank and keeps its ``n`` first keys as a bitset over
the key universe (``ceil(universe / 32)`` words: 752 bytes a column at
a universe of 6,000), block ``i`` of ``BLOCK`` columns on chip
``i % chips``.  A query's join sizes over every column
come from those bitsets exactly: the query sketch's rows counted per
key, and each bit plane of those counts popcounted against the
bitsets.  So do the lane counts.  MI is ``reference.mi_lanes`` on the
joined samples of the columns asked for.  The base tables and queries
are ``table2``'s, made on the host.
"""

from __future__ import annotations

import functools
import types

import numpy as np

from harness import lake as host
from harness import reference
from harness.reference import check_options, kind_of, ranked  # noqa: F401
from harness.traffic import requests  # noqa: F401

_WORD = 32
# Columns a reference program makes at once.
BLOCK = 2048
_ZERO = np.uint32(0)
_HEAD = 64
# MurmurHash3's mixed first block for the word 1: (1 * c1) rotl 15 * c2.
_MURMUR_K1 = (((0xCC9E2D51 << 15) | (0xCC9E2D51 >> 17)) & 0xFFFFFFFF) \
    * 0x1B873593 & 0xFFFFFFFF


@functools.cache
def _programs() -> types.SimpleNamespace:
    """The generator's and the reference's device programs."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32

    def fmix(h):
        h = h ^ (h >> 16)
        h = h * u32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * u32(0xC2B2AE35)
        return h ^ (h >> 16)

    def hash2(key, a, b):
        return fmix(fmix((a * host._GOLD) ^ key) ^ (b * host._GOLD2))

    def key_hash(z):
        """As ``harness.lake.key_hash``."""
        return fmix(z + (z >= u32(host._FENCE_CODE)).astype(u32))

    def triangular(h):
        lo = (h & u32(0xFFFF)).astype(jnp.float32)
        hi = (h >> 16).astype(jnp.float32)
        u = (lo + hi + np.float32(1.0)) * np.float32(2.0 ** -16) \
            - np.float32(1.0)
        return u * np.float32(np.sqrt(6.0))

    def zipf(lut, head, i, M, ordered):
        """``lut[i]`` for (B, R) indices ``i``, in the order of ``i``
        where ``ordered``, else in each row's own order.  A gather is
        slow on the chip, so each row is sorted and its values up to
        ``len(head)`` counted from the table's breakpoints ``head``; only
        the last ``M`` of the row, which hold its values past them, are
        gathered (the whole row, in the row where they do not)."""
        B, R = i.shape
        i, t = jax.lax.sort(
            (i, jnp.broadcast_to(jnp.arange(R, dtype=u32), (B, R))),
            dimension=1, num_keys=1)
        v = jnp.ones((B, R), u32)
        for b in range(head.shape[0] - 1):
            v = v + (i >= head[b]).astype(u32)
        last = i[:, R - M:]
        v = v.at[:, R - M:].set(jnp.where(last >= head[-1], lut[last],
                                          v[:, R - M:]))
        if M < R:
            v = jax.lax.cond(jnp.any(i[:, R - M - 1] >= head[-1]),
                             lambda: lut[i], lambda: v)
        if ordered:
            _, v = jax.lax.sort((t, v), dimension=1, num_keys=1)
        return v

    def rows(lut, head, k_rows, cols, n_keys, shift, R, M, ordered=True):
        """(B, R) keys of columns ``cols``: Zipf draws modulo each
        column's key count, plus its shift; each row in its own order
        where ``ordered``."""
        t = jnp.arange(R, dtype=u32)
        h = hash2(k_rows, cols[:, None], t[None, :])
        v = zipf(lut, head, h >> (32 - host._LUT_BITS), M, ordered)
        return v % n_keys[:, None] + shift[:, None]

    def rounded(x, zero):
        """``x`` as stored: an XOR of its bits with a zero that the
        compiler cannot see keeps it from fusing a multiply into the next
        add or reassociating products, so each rounds as on the host."""
        bits = jax.lax.bitcast_convert_type(x, u32) ^ zero
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    def values(zero, k_noise, k_latent, alpha, cols, z):
        """(L, R) float32 values of keys ``z`` in columns ``cols``, bit
        for bit the host's; the latent hashed from the key, as the host
        tabulates it."""
        latent = rounded(triangular(hash2(k_latent, u32(0), z)), zero)
        noise = rounded(triangular(hash2(k_noise, cols[:, None], z)), zero)
        a = alpha[:, None]
        return (rounded(a * latent, zero)
                + rounded((np.float32(1.0) - a) * noise, zero))

    @functools.partial(jax.jit, static_argnames=("R", "M"))
    def chunk(zero, lut, head, k_rows, k_noise, k_latent, cols, n_keys, shift,
              alpha, *, R, M):
        z = rows(lut, head, k_rows, cols, n_keys, shift, R, M)
        x = values(zero, k_noise, k_latent, alpha, cols, z)
        return (key_hash(z), x,
                jnp.floor(np.float32(2.0) * x).astype(jnp.int32))

    def sketch_rank(z):
        """``fib(murmur3(1, seed=key_hash(z)))``: the rank by which a
        candidate sketch keeps its keys, smallest first."""
        h = key_hash(z) ^ u32(_MURMUR_K1)
        h = (h << 13) | (h >> 19)
        h = (h * u32(5) + u32(0xE6546B64)) ^ u32(4)
        return fmix(h) * u32(0x9E3779B9)

    @functools.partial(jax.jit, static_argnames=("R", "M", "n", "W"))
    def sketch_bits(lut, head, k_rows, cols, n_keys, shift, *, R, M, n, W):
        """(B, W) bitsets of the columns' sketches: bit ``z`` set where
        key ``z`` is one of the ``n`` of smallest rank that the column
        holds."""
        z = rows(lut, head, k_rows, cols, n_keys, shift, R, M, False)
        B = z.shape[0]
        _, z = jax.lax.sort((sketch_rank(z), z), dimension=1, num_keys=1)
        first = jnp.concatenate(
            [jnp.ones((B, 1), bool), z[:, 1:] != z[:, :-1]], axis=1)
        nth = jnp.cumsum(first, axis=1, dtype=jnp.int32)
        # The n first keys in rank order, to the front of the row.
        order = jnp.where(first & (nth <= n), nth, R + 1)
        order, z = jax.lax.sort((order, z), dimension=1, num_keys=1)
        kept = order[:, :n] <= n
        return jnp.zeros((B, W), u32).at[
            jnp.arange(B)[:, None], z[:, :n] >> 5].add(
            jnp.where(kept, u32(1) << (z[:, :n] & 31), u32(0)))

    @jax.jit
    def join_sizes(bits, planes):
        """(rows,) join sizes: the sum over planes ``p`` of
        ``2**p * popcount(bits & planes[p])``."""
        js = jnp.zeros(bits.shape[0], jnp.int32)
        for p in range(planes.shape[0]):
            hits = jax.lax.population_count(bits & planes[p][None, :])
            js = js + (jnp.sum(hits.astype(jnp.int32), axis=1) << p)
        return js

    return types.SimpleNamespace(
        chunk=chunk, sketch_bits=sketch_bits, join_sizes=join_sizes,
        values=jax.jit(values), take=jax.jit(lambda a, i: a[i]))


def _padded(a: np.ndarray, least: int = 8) -> np.ndarray:
    """``a`` padded with zeros to a power of two of entries (at least
    ``least``), so that few shapes compile."""
    size = max(least, 1 << max(0, len(a) - 1).bit_length())
    return np.concatenate([a, np.zeros(size - len(a), a.dtype)])


class Lake(host.Lake):
    """The Table II lake of ``cfg`` under ``seed``, made chunk by chunk
    on the chips."""

    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed)
        self._k_latent = host.stream_key(seed, host.S_LATENT)
        # Zipf values up to _HEAD by comparison with the table's
        # breakpoints; the rest, about 10% of draws, gathered from the
        # last ``tail`` draws of each sorted row (12 standard deviations
        # above their mean count).
        self.head = np.searchsorted(self.lut, np.arange(2, _HEAD + 2)) \
            .astype(np.uint32)
        R = self.rows_per_table
        mean = R * (1.0 - self.head[-1] / len(self.lut))
        self.tail = min(R, -(-int(mean + 12 * mean ** 0.5 + 1) // 128) * 128)
        self._tables = {}

    def tables(self, device) -> tuple:
        """The Zipf table and its breakpoints on ``device``."""
        import jax
        if device not in self._tables:
            self._tables[device] = jax.device_put((self.lut, self.head),
                                                  device)
        return self._tables[device]

    def chunk(self, c0: int, c1: int, device=None) -> dict:
        """Columns [c0, c1) as ``table2`` makes them, committed to
        ``device`` (the first chip where None)."""
        import jax
        device = device or jax.local_devices()[0]
        args = jax.device_put(
            (np.arange(c0, c1, dtype=np.uint32),
             self.n_keys[c0:c1].astype(np.uint32),
             self.shift[c0:c1].astype(np.uint32), self.alpha[c0:c1]),
            device)
        keys, vf, vc = _programs().chunk(
            _ZERO, *self.tables(device), self._k_rows, self._k_noise,
            self._k_latent, *args, R=self.rows_per_table, M=self.tail)
        return {"keys": keys, "vals_f": vf, "vals_c": vc}

    def keep_rows(self) -> None:
        """Nothing to keep: the reference makes the rows again."""

    def values_of(self, cols: np.ndarray, z: np.ndarray) -> np.ndarray:
        """(len(cols), len(z)) float32 values of keys ``z`` in columns
        ``cols``, from the chunks' own generator, on the first chip."""
        import jax
        c = _padded(np.asarray(cols, np.uint32))
        zb = np.broadcast_to(np.asarray(z, np.uint32), (len(c), len(z)))
        x = _programs().values(_ZERO, self._k_noise, self._k_latent,
                               *jax.device_put((self.alpha[c], c, zb),
                                               jax.local_devices()[0]))
        return np.asarray(x)[:len(cols)]


class LakeSketches:
    """Every column's candidate sketch as a bitset over the key universe,
    block ``i`` of ``BLOCK`` columns on chip ``i % len(devices)``: chip
    ``d`` holds ``bits[d]``, one row of ``W`` words a column, bit ``z``
    for key ``z``, its rows the columns ``cols[d]`` in order.  No two
    keys of the universe share a rank (checked), so no sketch is
    ``ambiguous``."""

    def __init__(self, lake: Lake, n: int):
        import jax
        import jax.numpy as jnp
        prog = _programs()
        U, C = lake.universe, lake.columns
        self.lake = lake
        self.devices = jax.local_devices()
        self.W = -(-U // _WORD)
        self.n_planes = int(n).bit_length()
        if reference.rank_order(U)[1].any():
            raise ValueError(f"two keys of the universe of {U} share a "
                             "rank: a sketch at the tie is not defined")
        parts = {}
        for c0 in range(0, C, BLOCK):
            c1 = min(c0 + BLOCK, C)
            d = self.chip_of(c0)
            dev = self.devices[d]
            args = jax.device_put(
                (np.arange(c0, c1, dtype=np.uint32),
                 lake.n_keys[c0:c1].astype(np.uint32),
                 lake.shift[c0:c1].astype(np.uint32)), dev)
            bits = prog.sketch_bits(*lake.tables(dev), lake._k_rows, *args,
                                    R=lake.rows_per_table, M=lake.tail,
                                    n=int(n), W=self.W)
            parts.setdefault(d, []).append((np.arange(c0, c1), bits))
        self.cols = {d: np.concatenate([c for c, _ in ps])
                     for d, ps in parts.items()}
        self.bits = {d: jnp.concatenate([b for _, b in ps])
                     for d, ps in parts.items()}
        self.ambiguous = np.zeros(C, bool)
        jax.block_until_ready(list(self.bits.values()))

    def chip_of(self, c):
        """The chip (its place in ``devices``) that holds column ``c``'s
        bitset; elementwise on an array."""
        return (c // BLOCK) % len(self.devices)

    def join_sizes(self, qz: np.ndarray) -> np.ndarray:
        """(C,) join sizes of a query sketch whose rows hold keys
        ``qz``."""
        import jax
        cnt = np.bincount(qz, minlength=self.W * _WORD)
        bit = (cnt[:, None] >> np.arange(self.n_planes)) & 1
        planes = np.sum(bit.reshape(self.W, _WORD, -1).astype(np.uint32)
                        << np.arange(_WORD, dtype=np.uint32)[None, :, None],
                        axis=1, dtype=np.uint32).T
        prog = _programs()
        out = {d: prog.join_sizes(b, jax.device_put(
            planes, self.devices[d])) for d, b in self.bits.items()}
        js = np.zeros(self.lake.columns, np.int64)
        for d, r in out.items():
            js[self.cols[d]] = np.asarray(r)
        return js

    def member(self, cols: np.ndarray, qz: np.ndarray) -> np.ndarray:
        """(len(cols), len(qz)) whether each column's sketch holds each
        key of ``qz``."""
        import jax
        words = np.empty((len(cols), self.W), np.uint32)
        chip = self.chip_of(cols)
        out = []
        for d in np.unique(chip):
            sel = np.flatnonzero(chip == d)
            r = np.searchsorted(self.cols[d], cols[sel])
            out.append((sel, _programs().take(self.bits[d], jax.device_put(
                _padded(r.astype(np.int32)), self.devices[d]))))
        for sel, w in out:
            words[sel] = np.asarray(w)[:len(sel)]
        qz = np.asarray(qz, np.int64)
        shift = (qz % _WORD).astype(np.uint32)
        return ((words[:, qz // _WORD] >> shift) & 1).astype(bool)


def join(ls: LakeSketches, q: host.Query, n: int, joins: dict | None = None):
    """(sketch rows, tie, join sizes (C,)) of the base table of ``q``,
    kept in ``joins`` by table."""
    if joins is not None and q.table in joins:
        return joins[q.table]
    sel, tie = reference.train_rows(q, n)
    out = (sel, tie, ls.join_sizes(q.z[sel]))
    if joins is not None:
        joins[q.table] = out
    return out


def answer(lake: Lake, ls: LakeSketches, q: host.Query, *, n: int, k: int,
           min_join: int, only=None, dtype: str = "float32",
           joins: dict | None = None) -> reference.Answer:
    """The reference's answer to ``q``, as ``reference.answer``: MI of
    the passing columns among ``only`` (all where None)."""
    sel, tie, js = join(ls, q, n, joins)
    tz, ty = q.z[sel], q.y[sel]
    mi = np.full(len(js), np.nan, np.float32)
    for disc, cols in reference.passing_columns(lake, js, min_join, only):
        x = lake.values_of(cols, tz)
        if disc:
            x = np.floor(np.float32(2.0) * x).astype(np.int64)
        mi[cols] = reference.mi_lanes(x, ty, ls.member(cols, tz),
                                      kind=kind_of(disc, q.discrete), k=k,
                                      dtype=dtype)
    return reference.Answer(js, mi, ls.ambiguous, tie)
