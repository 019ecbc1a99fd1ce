"""Plain reference for what the discovery service answers.

Written from the paper's definitions (arXiv:2403.15553 Sections III-V)
and the service's documented semantics, and importing nothing of the
program:

* TUPSK sketches: a candidate column keeps its ``n`` distinct keys of
  smallest ``fib(murmur3(1, seed=key))``, with the value of the key's
  rows (agg ``first``; the lake's rows of one key agree); a query table
  keeps its ``n`` rows of smallest ``fib(murmur3(j, seed=key))``, ``j``
  the row's occurrence number of its key.
* The sketch join: query-sketch rows whose key the candidate sketch
  holds; the join size is their count.
* MI estimators on the joined sample (Section V): plug-in MLE for two
  categorical columns, the Gao et al. mixed KSG for two numeric ones,
  Ross's DC-KSG otherwise, with L-infinity balls, k neighbours and the
  sample's own P x P distances, materialized.
* Ranking: MI descending, then column number ascending, top ``top_k``
  of the candidates whose join size reaches ``min_join``.

Sketches and joins run in NumPy on the host; the estimators run in
plain ``jax.numpy`` at float32 on the device, in blocks of lanes.  The
``dtype`` argument is the control: the same estimators with the sample
values and distances in a lower precision.
"""

from __future__ import annotations

import functools

import numpy as np

from harness.lake import Lake, Query, key_hash

_C1, _C2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)
_FIB = np.uint32(0x9E3779B9)


def murmur3_word(word, seed):
    """MurmurHash3 x86_32 of one 4-byte little-endian word."""
    with np.errstate(over="ignore"):
        k = np.asarray(word, np.uint32) * _C1
        k = (k << np.uint32(15)) | (k >> np.uint32(17))
        k = k * _C2
        h = np.asarray(seed, np.uint32) ^ k
        h = (h << np.uint32(13)) | (h >> np.uint32(19))
        h = h * np.uint32(5) + np.uint32(0xE6546B64)
        h = h ^ np.uint32(4)
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    return h


def fib(h):
    with np.errstate(over="ignore"):
        return np.asarray(h, np.uint32) * _FIB


def occurrence(z: np.ndarray) -> np.ndarray:
    """1-based occurrence number of each entry among equal entries, in
    order."""
    order = np.argsort(z, kind="stable")
    sz = z[order]
    start = np.r_[True, sz[1:] != sz[:-1]]
    first = np.maximum.accumulate(np.where(start, np.arange(len(z)), 0))
    j = np.empty(len(z), np.int64)
    j[order] = np.arange(len(z)) - first + 1
    return j


def train_rows(q: Query, n: int):
    """Rows of the query sketch (indices into the base table) and
    whether a rank tie at its edge leaves it undefined."""
    rank = fib(murmur3_word(occurrence(q.z).astype(np.uint32), q.keys))
    order = np.argsort(rank, kind="stable")
    tie = len(order) > n and rank[order[n - 1]] == rank[order[n]]
    return order[:n], bool(tie)


def rank_order(U: int) -> tuple[np.ndarray, np.ndarray]:
    """(pos, tied) of the key universe ``range(U)`` in a candidate
    sketch's rank order: key ``z`` is ``pos[z]``-th; ``tied[p]`` where
    the ``p``-th key's rank equals the next one's."""
    rank = fib(murmur3_word(np.uint32(1), key_hash(np.arange(U))))
    by_rank = np.argsort(rank, kind="stable")
    pos = np.empty(U, np.int64)
    pos[by_rank] = np.arange(U)
    rank_sorted = rank[by_rank]
    return pos, np.r_[rank_sorted[1:] == rank_sorted[:-1], False]


class LakeSketches:
    """Reference candidate sketches of every column of the lake, as a
    (keys x columns) membership matrix ``member`` over the key universe,
    its keys in rank order (row ``pos[z]`` is key ``z``)."""

    def __init__(self, lake: Lake, n: int):
        U = lake.universe
        C = lake.columns
        self.pos, tied = rank_order(U)
        self.member = np.zeros((U, C), bool)
        self.ambiguous = np.zeros(C, bool)
        step = max(1, (1 << 24) // U)
        for a in range(0, C, step):
            z = self.pos[lake.z[a:a + step]]
            present = np.zeros((len(z), U), bool)
            present[np.arange(len(z))[:, None], z] = True
            cs = np.cumsum(present, axis=1, dtype=np.int16)
            if tied.any():
                # The n-th kept key and the next present key share a
                # rank: which one a sketch keeps is not defined.
                edge = present & (cs == n) & tied[None, :]
                nxt = np.roll(present & (cs == n + 1), -1, axis=1)
                self.ambiguous[a:a + step] = (edge & nxt).any(axis=1)
            self.member[:, a:a + step] = (present & (cs <= n)).T

    def joined(self, z) -> np.ndarray:
        """(C, len(z)) whether each column's sketch holds each key."""
        return self.member[self.pos[z]].T


def check_options(gates: dict) -> None:
    """This reference (of the Table II lakes) has no phase-0 containment
    gate, so it takes the service's defaults only."""
    for key, value in gates.items():
        raise ValueError(
            f"lake table2 refuses {key}={value!r}: its reference has no "
            f"phase-0 containment gate, so a mix on it leaves {key} out")


def kind_of(cand_discrete: bool, target_discrete: bool) -> str:
    if cand_discrete and target_discrete:
        return "mle"
    if not cand_discrete and not target_discrete:
        return "mixed"
    return "dc_x" if cand_discrete else "dc_y"


# ---------------------------------------------------------------------------
# Estimators (device, materialized P x P, blocks of lanes)
# ---------------------------------------------------------------------------

def _estimators():
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import digamma

    def pair_masks(mask):
        P = mask.shape[-1]
        eye = jnp.eye(P, dtype=bool)
        both = mask[:, :, None] & mask[:, None, :]
        return both, both & ~eye

    def mle(x, y, mask, k, dt):
        both, _ = pair_masks(mask)
        ex = both & (x[:, :, None] == x[:, None, :])
        ey = both & (y[:, :, None] == y[:, None, :])
        cx = jnp.sum(ex, -1).astype(jnp.float32)
        cy = jnp.sum(ey, -1).astype(jnp.float32)
        cxy = jnp.sum(ex & ey, -1).astype(jnp.float32)
        M = jnp.sum(mask, -1).astype(jnp.float32)
        t = jnp.where(mask, jnp.log(jnp.maximum(cxy, 1.0))
                      - jnp.log(jnp.maximum(cx, 1.0))
                      - jnp.log(jnp.maximum(cy, 1.0)), 0.0)
        mi = jnp.sum(t, -1) / jnp.maximum(M, 1.0) + jnp.log(jnp.maximum(M, 1.0))
        return jnp.where(M > 0, jnp.maximum(mi, 0.0), 0.0)

    def mixed(x, y, mask, k, dt):
        _, valid = pair_masks(mask)
        x = x.astype(dt)
        y = y.astype(dt)
        inf = jnp.array(jnp.inf, dt)
        dx = jnp.where(valid, jnp.abs(x[:, :, None] - x[:, None, :]), inf)
        dy = jnp.where(valid, jnp.abs(y[:, :, None] - y[:, None, :]), inf)
        dj = jnp.maximum(dx, dy)
        rho = -jax.lax.top_k(-dj, k)[0][..., k - 1]
        tie = rho <= 0
        r = rho[..., None]
        cnt = lambda b: jnp.sum(b, -1).astype(jnp.float32)  # noqa: E731
        kp = jnp.where(tie, cnt(dj <= 0) + 1, float(k))
        nx = jnp.where(tie, cnt(dx <= 0), cnt(dx < r)) + 1
        ny = jnp.where(tie, cnt(dy <= 0), cnt(dy < r)) + 1
        M = jnp.sum(mask, -1).astype(jnp.float32)
        per = digamma(kp) + jnp.log(jnp.maximum(M, 1.0))[:, None] \
            - jnp.log(nx) - jnp.log(ny)
        est = jnp.sum(jnp.where(mask, per, 0.0), -1) / jnp.maximum(M, 1.0)
        return jnp.where(M > k, est, 0.0)

    def dc(codes, v, mask, k, dt):
        _, valid = pair_masks(mask)
        v = v.astype(dt)
        inf = jnp.array(jnp.inf, dt)
        same = valid & (codes[:, :, None] == codes[:, None, :])
        n_x = jnp.sum(same, -1) + mask.astype(jnp.int32)
        k_eff = jnp.minimum(k, n_x - 1)
        dv = jnp.abs(v[:, :, None] - v[:, None, :])
        near = -jax.lax.top_k(-jnp.where(same, dv, inf), k)[0]
        idx = jnp.clip(k_eff - 1, 0, k - 1)
        d_i = jnp.take_along_axis(near, idx[..., None], axis=-1)
        m_i = jnp.sum(jnp.where(valid, dv, inf) < d_i, -1)
        keep = mask & (n_x >= 2)
        cnt = jnp.maximum(jnp.sum(keep, -1), 1).astype(jnp.float32)

        def mean(t):
            return jnp.sum(jnp.where(keep, t, 0.0), -1) / cnt

        est = digamma(cnt) \
            + mean(digamma(jnp.maximum(k_eff, 1).astype(jnp.float32))) \
            - mean(digamma(n_x.astype(jnp.float32))) \
            - mean(digamma(m_i.astype(jnp.float32) + 1.0))
        M = jnp.sum(mask, -1)
        return jnp.where(M > k, jnp.maximum(est, 0.0), 0.0)

    @functools.partial(jax.jit, static_argnames=("kind", "k", "dt"))
    def lanes(x, y, mask, *, kind, k, dt):
        with jax.default_matmul_precision("highest"):
            if kind == "mle":
                return mle(x, y, mask, k, dt)
            if kind == "mixed":
                return mixed(x, y, mask, k, dt)
            if kind == "dc_x":   # categorical candidate, numeric target
                return dc(x, y, mask, k, dt)
            return dc(y, x, mask, k, dt)   # numeric candidate, categorical target

    return lanes


_LANES = None


def mi_lanes(x: np.ndarray, y: np.ndarray, mask: np.ndarray, *, kind: str,
             k: int, dtype: str = "float32") -> np.ndarray:
    """MI of L joined samples: x (L, P) candidate values, y (P,) query
    targets, mask (L, P).  Runs on the default device in blocks."""
    global _LANES
    import jax
    import jax.numpy as jnp
    if _LANES is None:
        _LANES = _estimators()
    L, P = x.shape
    if L == 0:
        return np.zeros(0, np.float32)
    # About 2^26 pair elements a block (a few 256 MiB temporaries), in
    # a power of two of lanes so that few shapes compile.
    block = min(max(1, (1 << 26) // (P * P)), 1 << (L - 1).bit_length())
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype]
    xd = x.astype(np.int32 if kind in ("mle", "dc_x") else np.float32)
    yd = y.astype(np.int32 if kind in ("mle", "dc_y") else np.float32)
    out = []
    for a in range(0, L, block):
        xb, mb = xd[a:a + block], mask[a:a + block]
        pad = block - len(xb)
        if pad:
            xb = np.concatenate([xb, np.zeros((pad, P), xb.dtype)])
            mb = np.concatenate([mb, np.zeros((pad, P), bool)])
        yb = np.broadcast_to(yd, (block, P))
        r = _LANES(jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb),
                   kind=kind, k=k, dt=dt)
        out.append(np.asarray(jax.device_get(r))[:block - pad])
    return np.concatenate(out).astype(np.float32)


# ---------------------------------------------------------------------------
# One query, answered
# ---------------------------------------------------------------------------

class Answer:
    """What the reference says of one query over the lake's columns,
    each array indexed by column number.

    ``js`` are the exact join sizes; ``mi`` the estimate of each column
    whose join reaches ``min_join`` among the columns asked for (NaN
    otherwise); ``ambiguous`` columns whose sketch is not defined (rank
    tie at the sketch's edge), which no check holds to anything;
    ``skipped`` when the query's own sketch is not defined, and the
    query goes unchecked."""

    def __init__(self, js, mi, ambiguous, skipped: bool):
        self.js, self.mi = js, mi
        self.ambiguous, self.skipped = ambiguous, skipped


def join(ls: LakeSketches, q: Query, n: int, joins: dict | None = None):
    """(sketch rows, tie, joined mask (C, n), join sizes (C,)) of the
    base table of ``q``; the targets of one base table share them, so
    ``joins`` keeps them by table."""
    if joins is not None and q.table in joins:
        return joins[q.table]
    sel, tie = train_rows(q, n)
    ins = ls.joined(q.z[sel])
    out = (sel, tie, ins, ins.sum(axis=1))
    if joins is not None:
        joins[q.table] = out
    return out


def answer(lake: Lake, ls: LakeSketches, q: Query, *, n: int, k: int,
           min_join: int, only=None, dtype: str = "float32",
           joins: dict | None = None) -> Answer:
    """The reference's answer to query ``q`` over the lake sketches
    ``ls`` (the base table holds at least ``n`` rows, so its sketch is
    full).  With ``only`` (column numbers), MI is estimated for those
    columns alone: enough to hold a served list's entries, and to find
    what it misses among them."""
    sel, tie, ins, js = join(ls, q, n, joins)
    tz, ty = q.z[sel], q.y[sel]
    mi = np.full(len(js), np.nan, np.float32)
    for disc, cols in passing_columns(lake, js, min_join, only):
        x = lake.values(cols[:, None], tz[None, :])
        if disc:
            x = np.floor(np.float32(2.0) * x).astype(np.int64)
        mi[cols] = mi_lanes(x, ty, ins[cols],
                            kind=kind_of(disc, q.discrete), k=k, dtype=dtype)
    return Answer(js, mi, ls.ambiguous.copy(), tie)


def passing_columns(lake: Lake, js: np.ndarray, min_join: int, only=None):
    """(discrete, column numbers) of the columns among ``only`` (all
    where None) whose join reaches ``min_join``, numeric ones first;
    a group with no column is left out."""
    passing = np.flatnonzero(js >= min_join)
    if only is not None:
        passing = np.intersect1d(passing, np.asarray(only, np.int64))
    return [(disc, cols) for disc in (False, True)
            if len(cols := passing[lake.discrete[passing] == disc])]


def ranked(ans: Answer, *, top_k: int, min_join: int) -> list:
    """The reference's own top-k as (column, mi, join size) triples."""
    ok = (ans.js >= min_join) & ~np.isnan(ans.mi)
    idx = np.flatnonzero(ok)
    order = np.lexsort((idx, -ans.mi[idx]))[:top_k]
    return [(int(i), float(ans.mi[i]), int(ans.js[i])) for i in idx[order]]
