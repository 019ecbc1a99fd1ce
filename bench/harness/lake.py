"""Seeded data lake and base tables, made on the host from ``--seed``.

Every value is a pure function of (seed, counters) through 32-bit
integer hashing and exact float32 arithmetic in NumPy, so the ingest
path and the reference regenerate bit-identical tables.

The tables follow the paper's Table II setting (arXiv:2403.15553,
Section V-B: real-data pairs from NYC Open Data and World Bank
Finances) as ``benchmarks/paper_tables.bench_table2_corpus`` synthesizes
its (train, candidate) table pairs; a configuration file
(``bench/configs/<name>.json``) holds the synthesizer's numbers:

* every table has ``rows_per_table`` rows; a table's keys are
  Zipf(``key_zipf``) draws taken modulo the table's own key count, which
  lies between ``n_keys_min`` and ``n_keys_max``;
* a candidate table's keys are shifted by its key count (into keys a
  base table's head never holds) with probability one minus its
  overlap, the overlap uniform between ``overlap_min`` and 1: that is
  ``shifted_share`` of the lake's tables;
* values are ``alpha * latent[key] + (1 - alpha) * noise``, alpha
  uniform in (0, 1); one candidate in ``categorical_every`` is made
  categorical as ``floor(2 * value)``.

The lake holds ``tables`` candidate tables, one (key, value) column
each, over one key universe (the synthesizer's integer keys, hashed
alike for every table).  Assumed beyond the synthesizer, and listed in
the configuration: one latent per key for the whole lake, so that a
base table relates to every candidate through both their alphas, and
a candidate's noise drawn per (table, key), so the rows of one key agree
and ``agg="first"`` is the key's value.

Per-table key counts, shifts and strengths are the same multisets for
every seed, in another order, so two seeds give the same work.
"""

from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF
_GOLD = np.uint32(0x9E3779B9)
_GOLD2 = np.uint32(0x85EBCA77)
_LUT_BITS = 20
_ZETA_15 = 2.612375348685488   # Riemann zeta(1.5)

# Hash streams (what each counter-based draw is for).
S_ROWS, S_LATENT, S_NOISE, S_QROWS, S_QNOISE, S_PERM = range(6)


def fmix(h):
    """MurmurHash3's 32-bit finalizer: a bijection on uint32."""
    h = np.asarray(h, dtype=np.uint32)
    with np.errstate(over="ignore"):
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    return h


def _fmix_int(x: int) -> int:
    return int(fmix(np.uint32(x & MASK32)))


def _unfmix_int(h: int) -> int:
    """Inverse of :func:`fmix` on one Python int."""
    h ^= h >> 16
    h = (h * 0x7ED1B41D) & MASK32
    h ^= (h >> 13) ^ (h >> 26)
    h = (h * 0xA5CB9243) & MASK32
    h ^= h >> 16
    return h


# The one key whose hash is 0xFFFFFFFF, which the index uses as its
# padding fence; keys at or above it shift up by one.
_FENCE_CODE = _unfmix_int(MASK32)


def key_hash(z):
    """32-bit join-key hash of integer key ``z`` (distinct for distinct
    keys, never 0xFFFFFFFF)."""
    code = np.asarray(z, np.uint32)
    code = code + (code >= np.uint32(_FENCE_CODE)).astype(np.uint32)
    return fmix(code)


def stream_key(seed: int, stream: int) -> np.uint32:
    seed = int(seed) % (1 << 64)
    h = _fmix_int((seed & MASK32) ^ 0x243F6A88)
    h = _fmix_int(h ^ (seed >> 32) ^ 0x85A308D3)
    return np.uint32(_fmix_int(h ^ ((stream * 0x9E3779B9) & MASK32)))


def hash2(key: np.uint32, a, b):
    """Counter hash of (a, b) under a stream key; uint32 arrays."""
    a = np.asarray(a, np.uint32)
    b = np.asarray(b, np.uint32)
    with np.errstate(over="ignore"):
        return fmix(fmix((a * _GOLD) ^ key) ^ (b * _GOLD2))


def triangular(h):
    """Zero-mean, unit-variance value from one hash: the sum of two
    16-bit uniforms, shifted and scaled (exact float32 up to the scale)."""
    lo = (h & np.uint32(0xFFFF)).astype(np.float32)
    hi = (h >> np.uint32(16)).astype(np.float32)
    u = (lo + hi + np.float32(1.0)) * np.float32(2.0 ** -16) - np.float32(1.0)
    return u * np.float32(np.sqrt(6.0))


def zipf_lut(s: float) -> np.ndarray:
    """Inverse CDF of Zipf(s) on 1, 2, ... (NumPy's ``rng.zipf``),
    tabulated at 2^20 evenly spaced quantiles: value = lut[h >> 12].
    Exact up to 2^22; beyond, the tail of the Hurwitz zeta function,
    held at 2^32 - 1 (a share of 1.2e-5 lies above it)."""
    if s != 1.5:
        raise ValueError("the tail formula is written for s = 1.5")
    V = 1 << 22
    cdf = np.cumsum(np.arange(1, V + 1, dtype=np.float64) ** -s) / _ZETA_15
    q = (np.arange(1 << _LUT_BITS, dtype=np.float64) + 0.5) / (1 << _LUT_BITS)
    out = np.searchsorted(cdf, q, side="left").astype(np.uint64) + 1
    tail = q > cdf[-1]
    # P(X > x) ~ 2 / (zeta(1.5) sqrt(x)) for large x.
    out[tail] = np.minimum(np.floor((2.0 / (_ZETA_15 * (1.0 - q[tail])))
                                    ** 2), MASK32)
    return out.astype(np.uint32)


def quantiles(n: int, lo: float, hi: float) -> np.ndarray:
    """``n`` evenly spaced quantiles of the uniform law on (lo, hi)."""
    return lo + (np.arange(n) + 0.5) / n * (hi - lo)


def permutation(seed: int, stream: int, n: int) -> np.ndarray:
    return np.argsort(hash2(stream_key(seed, S_PERM), stream,
                            np.arange(n)), kind="stable")


class Lake:
    """The candidate tables of one configuration under one seed."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.seed = int(seed)
        C = self.columns = int(cfg["tables"])
        self.rows_per_table = int(cfg["rows_per_table"])
        self.universe = 2 * int(cfg["n_keys_max"])
        if self.universe >= (1 << 16):
            raise ValueError("n_keys_max too large for the uint16 key store")
        self.lut = zipf_lut(float(cfg["key_zipf"]))
        # Fixed multisets, placed by seeded permutations.
        self.n_keys = np.floor(quantiles(C, cfg["n_keys_min"],
                                         cfg["n_keys_max"])).astype(np.int64)[
            permutation(seed, 0, C)]
        shifted = np.arange(C) < int(round(float(cfg["shifted_share"]) * C))
        self.shift = np.where(shifted[permutation(seed, 1, C)],
                              self.n_keys, 0)
        self.alpha = quantiles(C, 0.0, 1.0).astype(np.float32)[
            permutation(seed, 2, C)]
        self.discrete = (np.arange(C) % int(cfg["categorical_every"])) == 0
        self.z = None  # (C, rows) uint16 keys, filled by chunk()
        self._k_rows = stream_key(seed, S_ROWS)
        self._k_noise = stream_key(seed, S_NOISE)
        self._latent = triangular(hash2(stream_key(seed, S_LATENT), 0,
                                        np.arange(self.universe)))

    # -- rows ----------------------------------------------------------
    def draw_keys(self, key: np.uint32, ids, n_keys, shift) -> np.ndarray:
        """(len(ids), rows) keys: Zipf draws modulo each table's key
        count, plus its shift."""
        ids = np.asarray(ids, np.int64)
        t = np.arange(self.rows_per_table)
        h = hash2(key, ids[:, None], t[None, :])
        v = self.lut[h >> np.uint32(32 - _LUT_BITS)]
        n_keys = np.asarray(n_keys, np.uint32)[:, None]
        return (v % n_keys + np.asarray(shift, np.uint32)[:, None]).astype(
            np.uint16)

    def latent(self, z):
        return self._latent[z]

    def values(self, col, z):
        """Float32 value of key ``z`` in column ``col`` (arrays)."""
        col = np.asarray(col)
        noise = triangular(hash2(self._k_noise, col, z))
        a = self.alpha[col]
        return a * self.latent(z) + (np.float32(1.0) - a) * noise

    def chunk(self, c0: int, c1: int, device=None) -> dict:
        """Columns [c0, c1) as (c1 - c0, rows) arrays: key hashes, float
        and categorical values, made on the host whatever ``device``."""
        cols = np.arange(c0, c1)
        z = self.draw_keys(self._k_rows, cols, self.n_keys[c0:c1],
                           self.shift[c0:c1])
        if self.z is not None:
            self.z[c0:c1] = z
        x = self.values(cols[:, None], z)
        return {
            "keys": key_hash(z),
            "vals_f": x,
            "vals_c": np.floor(np.float32(2.0) * x).astype(np.int64),
        }

    def keep_rows(self) -> None:
        """Keep every row's key (2 bytes a row) for the reference."""
        self.z = np.zeros((self.columns, self.rows_per_table), np.uint16)


class Query:
    """One target column of a base table: rows of (key, target).  The
    targets of one base table share its keys (``table``)."""

    def __init__(self, lake: Lake, table: int, n_keys: int, target: int,
                 alpha: float, discrete: bool):
        self.table, self.target, self.discrete = int(table), int(target), discrete
        self.n_keys = int(n_keys)
        self.z = lake.draw_keys(stream_key(lake.seed, S_QROWS), [table],
                                [n_keys], [0])[0]
        t = np.arange(len(self.z))
        a = np.float32(alpha)
        noise = triangular(hash2(stream_key(lake.seed, S_QNOISE),
                                 table * 64 + target, t))
        y = a * lake.latent(self.z) + (np.float32(1.0) - a) * noise
        self.y = np.floor(np.float32(2.0) * y).astype(np.int64) \
            if discrete else y
        self.keys = key_hash(self.z)
