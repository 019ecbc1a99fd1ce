"""Pallas TPU kernel: fused streaming kNN statistics (flash-KSG).

Every KSG-family MI estimator reduces to two row-wise statistics over
the implicit P×P pairwise-distance structure of a joined sample
(x_i, y_i):

  1. the k smallest "selected" distances per row (the kNN radii), and
  2. ball counts per row given a per-row radius.

The seed path materialized three P×P Chebyshev matrices in HBM
(``pairwise_cheb``) and re-reduced them per estimator.  This kernel
streams (block × block) distance tiles through VMEM with flash-attention
style online accumulators — a (bm, LANES) running k-smallest buffer for
pass 1 and a (bm, LANES) count accumulator for pass 2 — so peak
intermediate memory is O(P · block) and the P×P matrices never exist.

Selected distance per (i, j) pair, both passes fencing the diagonal and
invalid (masked) endpoints to +inf:

  * mode "joint":  d = max(|x_i−x_j|, |y_i−y_j|)   (KSG / MixedKSG)
  * mode "class":  d = |y_i−y_j| if x_i == x_j else +inf   (Ross DC-KSG
    within-class neighborhoods; x carries dense class codes)

The k-smallest merge uses k unrolled min-extractions (min reduction +
first-occurrence fence via a lane-iota min) — no sort/top_k primitive is
required, so the kernel lowers on TPU and runs under ``interpret=True``
for CPU validation.  Grid is (P/bm, P/bn) with the column axis declared
"arbitrary" so the VMEM accumulators persist across column steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK = 256
# Minimum TPU-tileable lane width.  Also the widest kNN buffer one row
# can carry: the running k-smallest accumulator is one (bm, LANES) VMEM
# tile with lanes [0, k) live, so any requested buffer width — k, or
# the widened class-mode k_max a DC-KSG k_i > k call asks for — must
# fit in LANES (ops.K_MAX re-exports this cap).
LANES = 128
_BIG_LANE = 1 << 30  # python int: jnp constants would be captured as consts

# The kernel's name on the device: every ``pallas_call`` below is named
# ``KERNEL_NAME`` or ``KERNEL_NAME`` plus a suffix, so one substring
# finds all of this kernel's device ops in a profiler trace.
KERNEL_NAME = "knn_stats_radius_counts"



# Extraction loops at most this long are unrolled; longer ones (the
# widened class-mode buffers, up to LANES) run as a loop, since an
# unrolled body keeps every iteration's (bm, bn) temporaries live and
# overflows VMEM on the chip.
_UNROLL_MAX = 8


def _loop(n, body, carry):
    """``body(t, carry)`` for t in [0, n): unrolled when short."""
    if n <= _UNROLL_MAX:
        for t in range(n):
            carry = body(t, carry)
        return carry
    return jax.lax.fori_loop(0, n, body, carry)


def _tile_distances(xc, xr, yc, yr, mc, mr, i, j, bm, bn, mode):
    """One (bm, bn) tile of selected distances (+inf at fenced pairs).

    Returns (d_sel, sel_aux) where sel_aux is the boolean same-class
    selection (class mode) used for the neighborhood-size count.
    """
    dx = jnp.abs(xc - xr)  # (bm, bn)
    dy = jnp.abs(yc - yr)
    valid = (mc > 0) & (mr > 0)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    off_diag = rows != cols
    inf = jnp.float32(jnp.inf)
    if mode == "joint":
        sel = valid & off_diag
        d_sel = jnp.where(sel, jnp.maximum(dx, dy), inf)
        aux = None
    else:  # class: x carries discrete codes, neighborhoods within class
        sel = valid & off_diag & (xc == xr)
        d_sel = jnp.where(sel, dy, inf)
        aux = sel
    return d_sel, aux


def _merge_k_smallest(knn_prev, d_tile, k):
    """k smallest of concat(knn_prev, d_tile) per row, ascending.

    ``knn_prev`` is (bm, LANES) with the running k smallest in lanes
    [0, k) and +inf elsewhere.  k unrolled min-extractions; ties are
    consumed one occurrence at a time via a first-occurrence lane fence.
    """
    bm = knn_prev.shape[0]
    inf = jnp.float32(jnp.inf)
    buf = jnp.concatenate([knn_prev, d_tile], axis=1)
    lane_buf = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
    lane_out = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)

    def extract(t, carry):
        buf, new = carry
        m = jnp.min(buf, axis=1, keepdims=True)  # (bm, 1)
        new = jnp.where(lane_out == t, m, new)
        first = jnp.min(
            jnp.where(buf == m, lane_buf, _BIG_LANE), axis=1, keepdims=True
        )
        return jnp.where(lane_buf == first, inf, buf), new

    new = jnp.full((bm, LANES), inf, jnp.float32)
    return _loop(k, extract, (buf, new))[1]


def _knn_kernel(xc_ref, xr_ref, yc_ref, yr_ref, mc_ref, mr_ref,
                knn_ref, cnt_ref, knn_scr, cnt_scr,
                *, bm: int, bn: int, k: int, mode: str):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        knn_scr[...] = jnp.full_like(knn_scr, jnp.inf)
        cnt_scr[...] = jnp.zeros_like(cnt_scr)

    d_sel, aux = _tile_distances(
        xc_ref[...], xr_ref[...], yc_ref[...], yr_ref[...],
        mc_ref[...], mr_ref[...], i, j, bm, bn, mode,
    )
    knn_scr[...] = _merge_k_smallest(knn_scr[...], d_sel, k)
    if aux is not None:
        s = jnp.sum(aux.astype(jnp.float32), axis=1, keepdims=True)
        cnt_scr[...] = cnt_scr[...] + jnp.broadcast_to(s, cnt_scr.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        knn_ref[...] = knn_scr[...]
        cnt_ref[...] = cnt_scr[...]


def _counts_kernel(xc_ref, xr_ref, yc_ref, yr_ref, mc_ref, mr_ref, rc_ref,
                   cnt_ref, cnt_scr, *, bm: int, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        cnt_scr[...] = jnp.zeros_like(cnt_scr)

    dy = jnp.abs(yc_ref[...] - yr_ref[...])  # (bm, bn)
    valid = (mc_ref[...] > 0) & (mr_ref[...] > 0)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    vo = valid & (rows != cols)
    r = rc_ref[...]  # (bm, 1) per-row radius

    def _acc(cond):
        return jnp.sum((vo & cond).astype(jnp.float32), axis=1, keepdims=True)

    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)
    dx = jnp.abs(xc_ref[...] - xr_ref[...])
    upd = (
        jnp.where(lane == 1, _acc(dy < r), 0.0)
        + jnp.where(lane == 0, _acc(dx < r), 0.0)
        + jnp.where(lane == 2, _acc(dx <= 0.0), 0.0)
        + jnp.where(lane == 3, _acc(dy <= 0.0), 0.0)
        + jnp.where(lane == 4, _acc(jnp.maximum(dx, dy) <= 0.0), 0.0)
    )
    cnt_scr[...] = cnt_scr[...] + upd

    @pl.when(j == nj - 1)
    def _finalize():
        cnt_ref[...] = cnt_scr[...]


def _counts_kernel_y(yc_ref, yr_ref, mc_ref, mr_ref, rc_ref,
                     cnt_ref, cnt_scr, *, bm: int, bn: int):
    """y-only ball counts (lane 1 == #|dy| < r_i; other lanes stay 0).

    A dedicated ``pallas_call`` signature without the x operands: the
    DC-KSG second pass never reads x, so its column/row tiles are not
    DMA'd into VMEM at all (the previous single kernel still streamed
    them and merely skipped the arithmetic).
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        cnt_scr[...] = jnp.zeros_like(cnt_scr)

    dy = jnp.abs(yc_ref[...] - yr_ref[...])  # (bm, bn)
    valid = (mc_ref[...] > 0) & (mr_ref[...] > 0)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    vo = valid & (rows != cols)
    r = rc_ref[...]  # (bm, 1) per-row radius

    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)
    hit = jnp.sum(
        (vo & (dy < r)).astype(jnp.float32), axis=1, keepdims=True
    )
    cnt_scr[...] = cnt_scr[...] + jnp.where(lane == 1, hit, 0.0)

    @pl.when(j == nj - 1)
    def _finalize():
        cnt_ref[...] = cnt_scr[...]


def _extract_order_stat(d_sel, t, T):
    """t-th smallest entry per row (0-based, duplicates counted).

    ``t`` is a (bm, 1) int32 per-row target; ``T`` is its static upper
    bound (t <= T-1).  Count-based run removal: each iteration consumes
    one entire run of equal minima and advances ``done`` by the run's
    multiplicity, so the value landed on for any t in [done, done+c) is
    exactly the t-th lane of the sorted buffer the two-op path reads —
    bit-identical, including tie handling and the +inf tail of rows with
    fewer than t+1 selectable neighbors.
    """
    bm = d_sel.shape[0]
    inf = jnp.float32(jnp.inf)

    def consume_run(_, carry):
        buf, r, done = carry
        mn = jnp.min(buf, axis=1, keepdims=True)
        eq = buf == mn
        c = jnp.sum(eq.astype(jnp.int32), axis=1, keepdims=True)
        take = (done <= t) & (t < done + c)
        return jnp.where(eq, inf, buf), jnp.where(take, mn, r), done + c

    r = jnp.full((bm, 1), inf, jnp.float32)
    done = jnp.zeros((bm, 1), jnp.int32)
    return _loop(T, consume_run, (d_sel, r, done))[1]


# Output lane layout of the fused radius+count kernel: one (P, LANES)
# float32 array carries every statistic the estimators consume.
RC_LANE_R = 0        # per-row radius (k-th / class-clipped extraction)
RC_LANE_CNT = 1      # class-mode within-class neighborhood size
RC_LANE_COUNTS = 2   # lanes 2..6: x_lt, y_lt, x_eq, y_eq, j_eq


def _class_target(cnt_f, mc, kk, kb):
    """Per-row buffer lane of the DC-KSG clipped radius (int32 (bm, 1)).

    Mirrors estimators' `_dc_radius`: n_x includes self, the budget is
    min(kk, n_x - 1), and the lane is clipped into the kb-wide buffer.
    """
    n_x = cnt_f.astype(jnp.int32) + (mc > 0).astype(jnp.int32)
    return jnp.clip(jnp.minimum(kk, n_x - 1) - 1, 0, kb - 1)


def _count_lanes(dx, dy, vo, r, which, bm):
    """Ball/tie count update, placed on the output lanes [2, 7)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)

    def _acc(cond):
        return jnp.sum((vo & cond).astype(jnp.float32), axis=1, keepdims=True)

    upd = jnp.where(lane == RC_LANE_COUNTS + 1, _acc(dy < r), 0.0)
    if which == "all":
        upd = (
            upd
            + jnp.where(lane == RC_LANE_COUNTS + 0, _acc(dx < r), 0.0)
            + jnp.where(lane == RC_LANE_COUNTS + 2, _acc(dx <= 0.0), 0.0)
            + jnp.where(lane == RC_LANE_COUNTS + 3, _acc(dy <= 0.0), 0.0)
            + jnp.where(
                lane == RC_LANE_COUNTS + 4,
                _acc(jnp.maximum(dx, dy) <= 0.0),
                0.0,
            )
        )
    return upd


def _radius_counts_kernel_1(xc_ref, xr_ref, yc_ref, yr_ref, mc_ref, mr_ref,
                            out_ref, *, bm: int, bn: int, k: int, kb: int,
                            kk: int, mode: str, which: str):
    """Single-tile fused radius+count (grid (1, 1), padded P == block).

    The production sketch shape: the whole padded sample is one
    VMEM-resident tile, so distances are formed exactly once and shared
    by the radius extraction and the count sweep — no second pass, no
    scratch, no intermediate HBM round trip.
    """
    dy = jnp.abs(yc_ref[...] - yr_ref[...])  # (bm, bn)
    valid = (mc_ref[...] > 0) & (mr_ref[...] > 0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    vo = valid & (rows != cols)
    inf = jnp.float32(jnp.inf)
    dx = None
    if mode == "joint":
        dx = jnp.abs(xc_ref[...] - xr_ref[...])
        d_sel = jnp.where(vo, jnp.maximum(dx, dy), inf)
        cnt = jnp.zeros((bm, 1), jnp.float32)
        t = jnp.full((bm, 1), k - 1, jnp.int32)
        T = k
    else:  # class: neighborhoods restricted to equal x codes
        sel = vo & (xc_ref[...] == xr_ref[...])
        d_sel = jnp.where(sel, dy, inf)
        cnt = jnp.sum(sel.astype(jnp.float32), axis=1, keepdims=True)
        t = _class_target(cnt, mc_ref[...], kk, kb)
        T = kb
    r = _extract_order_stat(d_sel, t, T)
    if which == "all" and dx is None:
        dx = jnp.abs(xc_ref[...] - xr_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)
    out = (
        jnp.where(lane == RC_LANE_R, jnp.broadcast_to(r, (bm, LANES)), 0.0)
        + jnp.where(lane == RC_LANE_CNT, jnp.broadcast_to(cnt, (bm, LANES)), 0.0)
        + _count_lanes(dx, dy, vo, r, which, bm)
    )
    out_ref[...] = out


def _radius_counts_kernel(xc_ref, xr_ref, yc_ref, yr_ref, mc_ref, mr_ref,
                          out_ref, knn_scr, acc_scr,
                          *, bm: int, bn: int, nj: int, k: int, kb: int,
                          kk: int, mode: str, which: str):
    """General fused radius+count: grid (P/bm, 2*nj), one pallas_call.

    Phase A (j < nj) streams the k-smallest merge over the column tiles
    exactly as ``_knn_kernel`` does; at the phase boundary the radius is
    extracted from the VMEM-resident buffer.  Phase B (j >= nj) revisits
    the same column tiles (the index map wraps at nj) and accumulates
    the ball/tie counts at that radius — the separate count kernel and
    the host round trip between the two ops are gone.
    """
    j = pl.program_id(1)
    jj = jax.lax.rem(j, nj)
    i = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        knn_scr[...] = jnp.full_like(knn_scr, jnp.inf)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, LANES), 1)

    @pl.when(j < nj)
    def _phase_a():
        d_sel, aux = _tile_distances(
            xc_ref[...], xr_ref[...], yc_ref[...], yr_ref[...],
            mc_ref[...], mr_ref[...], i, jj, bm, bn, mode,
        )
        knn_scr[...] = _merge_k_smallest(knn_scr[...], d_sel, kb)
        if aux is not None:
            s = jnp.sum(aux.astype(jnp.float32), axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] + jnp.where(lane == RC_LANE_CNT, s, 0.0)

    @pl.when(j == nj - 1)
    def _radius():
        knn = knn_scr[...]
        if mode == "joint":
            r = knn[:, k - 1:k]
        else:
            cnt = acc_scr[...][:, RC_LANE_CNT:RC_LANE_CNT + 1]
            t = _class_target(cnt, mc_ref[...], kk, kb)
            r = jnp.sum(
                jnp.where(lane == t, knn, 0.0), axis=1, keepdims=True
            )
        acc_scr[...] = acc_scr[...] + jnp.where(lane == RC_LANE_R, r, 0.0)

    @pl.when(j >= nj)
    def _phase_b():
        r = acc_scr[...][:, RC_LANE_R:RC_LANE_R + 1]
        dy = jnp.abs(yc_ref[...] - yr_ref[...])
        valid = (mc_ref[...] > 0) & (mr_ref[...] > 0)
        rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cols = jj * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        vo = valid & (rows != cols)
        dx = None
        if which == "all":
            dx = jnp.abs(xc_ref[...] - xr_ref[...])
        acc_scr[...] = acc_scr[...] + _count_lanes(dx, dy, vo, r, which, bm)

    @pl.when(j == 2 * nj - 1)
    def _finalize():
        out_ref[...] = acc_scr[...]


def _row_col_specs(block):
    col = pl.BlockSpec((block, 1), lambda i, j: (i, 0))
    row = pl.BlockSpec((1, block), lambda i, j: (0, j))
    return col, row


@functools.partial(
    jax.jit, static_argnames=("k", "mode", "block", "interpret")
)
def knn_smallest_padded(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    *,
    k: int,
    mode: str = "joint",
    block: int = BLOCK,
    interpret: bool = False,
):
    """x, y float32 (P,), mask int32 (P,); P divisible by ``block``.

    Returns (knn (P, LANES) — k smallest selected distances ascending in
    lanes [0, k), +inf beyond — and cnt (P, LANES) — same-class
    neighborhood size broadcast along lanes; zeros in joint mode).
    """
    P = x.shape[0]
    assert P % block == 0, (P, block)
    assert 1 <= k <= LANES, k
    grid = (P // block, P // block)
    xc, xr = x.reshape(P, 1), x.reshape(1, P)
    yc, yr = y.reshape(P, 1), y.reshape(1, P)
    mc = mask.astype(jnp.int32).reshape(P, 1)
    mr = mask.astype(jnp.int32).reshape(1, P)
    col, row = _row_col_specs(block)
    out = pl.BlockSpec((block, LANES), lambda i, j: (i, 0))
    shape = jax.ShapeDtypeStruct((P, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_knn_kernel, bm=block, bn=block, k=k, mode=mode),
        name=f"{KERNEL_NAME}_knn",
        grid=grid,
        in_specs=[col, row, col, row, col, row],
        out_specs=(out, out),
        out_shape=(shape, shape),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xc, xr, yc, yr, mc, mr)


@functools.partial(jax.jit, static_argnames=("which", "block", "interpret"))
def ball_counts_padded(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    r: jax.Array,
    *,
    which: str = "all",
    block: int = BLOCK,
    interpret: bool = False,
):
    """x, y, r float32 (P,), mask int32 (P,); P divisible by ``block``.

    Returns cnt (P, LANES) float32 with lanes 0..4 holding, per row i
    over valid j ≠ i:  #|dx|<r_i, #|dy|<r_i, #dx==0, #dy==0, #joint==0.
    ``which="y"`` computes only lane 1 (the others stay zero) through a
    dedicated x-free ``pallas_call`` signature, so the x tiles are never
    DMA'd — the DC-KSG second pass needs nothing else.
    """
    P = x.shape[0]
    assert P % block == 0, (P, block)
    grid = (P // block, P // block)
    yc, yr = y.reshape(P, 1), y.reshape(1, P)
    mc = mask.astype(jnp.int32).reshape(P, 1)
    mr = mask.astype(jnp.int32).reshape(1, P)
    rc = r.reshape(P, 1)
    col, row = _row_col_specs(block)
    out = pl.BlockSpec((block, LANES), lambda i, j: (i, 0))
    common = dict(
        grid=grid,
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((P, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    if which == "y":
        return pl.pallas_call(
            functools.partial(_counts_kernel_y, bm=block, bn=block),
            name=f"{KERNEL_NAME}_xfree",
            in_specs=[col, row, col, row, col],
            **common,
        )(yc, yr, mc, mr, rc)
    xc, xr = x.reshape(P, 1), x.reshape(1, P)
    return pl.pallas_call(
        functools.partial(_counts_kernel, bm=block, bn=block),
        name=f"{KERNEL_NAME}_balls",
        in_specs=[col, row, col, row, col, row, col],
        **common,
    )(xc, xr, yc, yr, mc, mr, rc)


@functools.partial(
    jax.jit,
    static_argnames=("k", "k_buf", "kk", "mode", "which", "block", "interpret"),
)
def radius_counts_padded(
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    *,
    k: int,
    k_buf: int | None = None,
    kk: int | None = None,
    mode: str = "joint",
    which: str = "all",
    block: int = BLOCK,
    interpret: bool = False,
):
    """Fused radius+count in ONE ``pallas_call``: x, y float32 (P,),
    mask int32 (P,); P divisible by ``block``.

    Returns out (P, LANES) float32 — lane :data:`RC_LANE_R` the per-row
    radius (the k-th smallest selected distance in joint mode; the
    DC-KSG class-clipped buffer lane in class mode, with per-point
    budget ``kk``), lane :data:`RC_LANE_CNT` the within-class
    neighborhood size, lanes [:data:`RC_LANE_COUNTS`, +5) the ball/tie
    counts at that radius (x_lt, y_lt, x_eq, y_eq, j_eq; only y_lt for
    ``which="y"``).  Bit-identical to ``knn_smallest_padded`` + radius
    extraction + ``ball_counts_padded``, without the intermediate HBM
    round trip: one-tile samples share a single distance formation, and
    larger samples run a second grid pass over the same column tiles.
    """
    P = x.shape[0]
    assert P % block == 0, (P, block)
    kb = k if k_buf is None else int(k_buf)
    kkv = k if kk is None else int(kk)
    assert 1 <= k <= kb <= LANES, (k, kb)
    nj = P // block
    xc, xr = x.reshape(P, 1), x.reshape(1, P)
    yc, yr = y.reshape(P, 1), y.reshape(1, P)
    mc = mask.astype(jnp.int32).reshape(P, 1)
    mr = mask.astype(jnp.int32).reshape(1, P)
    out = pl.BlockSpec((block, LANES), lambda i, j: (i, 0))
    shape = jax.ShapeDtypeStruct((P, LANES), jnp.float32)
    common = dict(
        out_specs=out,
        out_shape=shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    if nj == 1:
        col, row = _row_col_specs(block)
        return pl.pallas_call(
            functools.partial(
                _radius_counts_kernel_1, bm=block, bn=block,
                k=k, kb=kb, kk=kkv, mode=mode, which=which,
            ),
            name=f"{KERNEL_NAME}_1tile",
            grid=(1, 1),
            in_specs=[col, row, col, row, col, row],
            **common,
        )(xc, xr, yc, yr, mc, mr)
    # The column index map wraps at nj, so phase B re-streams the same
    # column tiles phase A merged from.
    col = pl.BlockSpec((block, 1), lambda i, j: (i, 0))
    row = pl.BlockSpec((1, block), lambda i, j: (0, j % nj))
    return pl.pallas_call(
        functools.partial(
            _radius_counts_kernel, bm=block, bn=block, nj=nj,
            k=k, kb=kb, kk=kkv, mode=mode, which=which,
        ),
        name=KERNEL_NAME,
        grid=(P // block, 2 * nj),
        in_specs=[col, row, col, row, col, row],
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
        ],
        **common,
    )(xc, xr, yc, yr, mc, mr)
