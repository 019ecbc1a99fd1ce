"""Execution backends for planned discovery queries.

Three executors run the same :class:`~repro.core.discovery.planner.QueryPlan`
behind one ``execute(plan, trains)`` interface; all return dense
``(Q, C)`` score / join-size matrices in the original candidate order
(and ``topk`` for collective-light ranked retrieval):

  * :class:`PartitionedLocalExecutor` — one homogeneous compiled program
    per estimator group per query.  All per-group programs for all
    queries are **dispatched before the first host transfer**, so jax's
    async dispatch overlaps estimator groups on device instead of
    serializing compute behind each group's device->host copy.
  * :class:`BatchedExecutor` — the multi-query fast path: one compiled
    program per estimator group with a leading Q axis vmapped over the
    train sketches, scoring Q concurrent queries against the same cached
    candidate arrays.  Bit-identical to Q single-query runs (vmap lanes
    are data-parallel); amortizes dispatch, join layout, and transfer
    overhead over the whole query batch.  Supports *padded-Q* execution
    (``q_bucket=``): the admission controller pads every batch up the
    pow-two Q-ladder, the executor repeats a live query lane into the
    dead lanes and slices them off at collect time — live results stay
    bit-identical to the unpadded run while compile count stays bounded
    under bursty traffic.
  * :class:`GroupMajorDistributedExecutor` — shards each group's
    candidate rows over the mesh 'data' axis.  Because candidates were
    partitioned by estimator *before* ``shard_map``, every shard of
    every program is homogeneous — the seed path ran the 4-way
    ``lax.switch`` scorer inside ``shard_map``, paying all branches on
    every shard.  ``topk`` keeps the collective payload at
    O(groups · shards · k) via per-shard ``lax.top_k`` and merges the
    per-shard/per-group winners **on device** — one ``lax.top_k`` over
    the concatenated group results for all Q queries at once — so the
    host sees O(Q · top_k) scalars per batch instead of
    O(Q · groups · shards · k_shard) (Q-fold less merge traffic than
    the per-query host merge it replaces).

Both batch executors split execution into ``dispatch`` (enqueue every
device program, return a pending handle) and the handle's ``collect``
(first host sync).  A scheduler draining several admission buckets
dispatches them all before collecting any — dispatch-before-transfer
across buckets, the same discipline the partitioned executor applies
across groups.

**Two-phase retrieval** rides the same dispatch/collect split, twice:
``prefilter_dispatch`` enqueues the cheap join-size pass (one
vectorized searchsorted intersect per (query, candidate) pair over the
pre-fenced sorted keys — Q x C counts in one program per group, no
value gathers, no estimator work), whose collected counts the planner
turns into per-group shortlists; ``shortlist_dispatch`` (batched) /
``shortlist_topk_dispatch`` (distributed) then gather and score *only*
the survivors.  The mesh path prefilters shard-locally and merges
shortlist winners on device — and needs no oversampling, because every
scored candidate already passed ``min_join``.  Phase-1 counts are the
scorers' own ``jnp.sum(mask)`` and phase-2 lanes run the same
homogeneous scorer body, so two-phase results are bit-identical to the
dense path at equal ``min_join``.

**Fused two-phase retrieval** goes one step further and removes the
phase boundary entirely: ``fused_dispatch`` (batched) /
``fused_topk_dispatch`` (distributed) run prefilter -> shortlist
compaction -> gather -> score as one device pipeline.  The compaction
is a fixed-shape stable argsort-by-pass/fail (identical selection
discipline to the host :func:`~repro.core.discovery.planner.build_shortlists`,
so results stay bit-identical), its width chosen *before* dispatch from
:class:`~repro.core.discovery.planner.ShortlistHints`; padded lanes are
sentinel-fenced on device.  Nothing crosses the bus between dispatch
and the final collect — the mesh variant compacts and gathers
shard-locally inside the collective, so no shard materializes a global
group array.  A width guess too small for the batch raises
:class:`~repro.core.discovery.planner.ShortlistOverflow` at collect;
the caller then rebuilds host shortlists from the handle's
``js_blocks()`` (the phase-1 work is reused, not recomputed) and runs
the classic two-step path — bit-identically.  The two-step handles
above remain the reference and fallback path.

The estimator-id -> estimator mapping lives in exactly one place
(:func:`_estimate`); the legacy switch scorer (`score_batch`), the seed
reference (`score_batch_reference`), and every partitioned program
dispatch through it, so they cannot drift apart.

**Stage names.**  The shared bodies trace under ``jax.named_scope``s
(:func:`_stage`), so every program above — dense, host-boundary, fused,
tiered, mesh — names its stages alike in its op metadata and in a
profiler trace: ``discovery.gate`` (phase-0 containment gate),
``discovery.prefilter`` (join sizes), ``discovery.compact`` (shortlist
compaction), ``discovery.score`` (row gather and estimators, the
``knn_stats`` kernel included) and ``discovery.topk`` (device merges).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import estimators
from repro.core.join import (
    effective_keys,
    presorted_join_size,
    signature_join_size,
    sketch_join_jax,
    sketch_join_presorted,
)
from repro.core.discovery.planner import (
    EST_DC_XD,
    EST_DC_YD,
    EST_MIXED,
    EST_MLE,
    GroupPlan,
    QueryPlan,
    ShortlistOverflow,
    SurvivorOverflow,
    _next_pow2,
    make_plan,
    pack_group,
    partition_by_estimator,
    stage_min_containment,
    stage_min_join,
)
from repro.core.discovery.resilience import maybe_fault
from repro.parallel.compat import auto_axes, shard_map

__all__ = [
    "score_batch",
    "score_batch_reference",
    "score_batch_partitioned",
    "distributed_topk",
    "stack_trains",
    "stack_trains_host",
    "stage_trains_host",
    "upload_trains",
    "pad_trains_q",
    "Executor",
    "PartitionedLocalExecutor",
    "BatchedExecutor",
    "GroupMajorDistributedExecutor",
    "get_executor",
    "compile_count",
]


def _estimate(est_id: int, xf, xu, y_f, y_u, mask, k: int, impl: str = "fused"):
    """One estimator on one joined sample; ``est_id`` is a static int."""
    if est_id == EST_MLE:
        return estimators.mle_mi(xu, y_u, mask)
    if est_id == EST_MIXED:
        return estimators.mixed_ksg_mi(xf, y_f, mask, k=k, impl=impl)
    if est_id == EST_DC_XD:  # discrete X (candidate feature), continuous Y
        return estimators.dc_ksg_mi(
            estimators.dense_rank(xu, mask), y_f, mask, k=k, impl=impl
        )
    # continuous X, discrete Y
    return estimators.dc_ksg_mi(
        estimators.dense_rank(y_u, mask), xf, mask, k=k, impl=impl
    )


def _score_one(
    train_keys, train_vals_f, train_vals_u, train_mask,
    cand_keys, cand_vals_f, cand_vals_u, cand_mask, est_id, k,
    impl: str = "fused",
):
    """Join one candidate sketch against the train sketch and estimate MI.

    ``est_id`` picks the estimator branch via ``lax.switch`` so a single
    compiled program serves heterogeneous corpora.  NOTE: under ``vmap``
    the switch lowers to ``select_n`` — ALL branches execute for every
    candidate; the partitioned executors are the fast path.
    """
    xf, y_f, mask = sketch_join_jax(
        train_keys, train_vals_f, train_mask, cand_keys, cand_vals_f, cand_mask
    )
    xu, y_u, _ = sketch_join_jax(
        train_keys, train_vals_u, train_mask, cand_keys, cand_vals_u, cand_mask
    )
    branches = [
        (lambda _, i=i: _estimate(i, xf, xu, y_f, y_u, mask, k, impl))
        for i in (EST_MLE, EST_MIXED, EST_DC_XD, EST_DC_YD)
    ]
    mi = jax.lax.switch(est_id, branches, operand=None)
    return mi, jnp.sum(mask)


@functools.partial(jax.jit, static_argnames=("k",))
def score_batch(train: dict, cands: dict, k: int = 3):
    """MI scores of a stacked candidate batch against one train sketch
    (switch-dispatch scorer — all estimator branches under vmap; prefer
    the partitioned executors on the host-driven path).
    Returns (mi_scores (C,), join_sizes (C,))."""
    f = jax.vmap(
        lambda ck, cf, cu, cm, eid: _score_one(
            train["keys"], train["vals_f"], train["vals_u"], train["mask"],
            ck, cf, cu, cm, eid, k,
        )
    )
    return f(
        cands["keys"], cands["vals_f"], cands["vals_u"], cands["mask"],
        cands["est_id"],
    )


@functools.partial(jax.jit, static_argnames=("k",))
def score_batch_reference(train: dict, cands: dict, k: int = 3):
    """Seed-identical scoring path, kept for benchmark comparison:
    double lexsort join per candidate + 4-way switch over the
    *materialized* (P×P) estimators."""
    f = jax.vmap(
        lambda ck, cf, cu, cm, eid: _score_one(
            train["keys"], train["vals_f"], train["vals_u"], train["mask"],
            ck, cf, cu, cm, eid, k,
            impl="materialized",
        )
    )
    return f(
        cands["keys"], cands["vals_f"], cands["vals_u"], cands["mask"],
        cands["est_id"],
    )


def _score_pair(tk, tf, tu, tm, ck, cf, cu, cm, *, est_id: int, k: int):
    """Homogeneous scorer body for one (train, candidate) sketch pair:
    every candidate of a group shares one estimator, so no switch and no
    redundant branches are compiled.  Candidate keys must be in
    effective (ingest-fenced) form — the index store and
    :func:`~repro.core.discovery.planner.pack_group` both guarantee it.
    Returns (mi, join size)."""
    (xf, xu), (y_f, y_u), mask = sketch_join_presorted(
        tk, tm, ck, cm, (cf, cu), (tf, tu), keys_effective=True,
    )
    return _estimate(est_id, xf, xu, y_f, y_u, mask, k), jnp.sum(mask)


# Bound on the (query, candidate, joined-row) elements one step of a
# scoring program works on.  Compiled for a v5e, a step of the kernel
# path keeps about 2 KiB of temporaries per element (each lane's
# (P, 128) float32 kernel output and its lane-padded (P, 1) operands),
# so a step holds about 1 GiB whatever the corpus size; 10^6 candidates
# scored in one step would need about 500 GiB.
_STEP_ELEMS = 1 << 19


def _stage(name: str):
    """Trace the decorated body under ``jax.named_scope(name)``, so every
    device op it emits carries ``name`` in its op metadata (and in the
    profiler's ``tf_op`` stat) whichever program inlines it.  A fresh
    scope per call: one ``named_scope`` object used as a decorator keeps
    its saved name stack on itself, which two threads tracing at once
    would overwrite.  Metadata only: no numerics or fusion change."""
    def wrap(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return traced
    return wrap


def _lane_step(q: int, n: int, p: int) -> int:
    """Candidate lanes per step for q queries over n lanes of P-row
    samples: the largest power of two that divides n and keeps
    q * lanes * p within :data:`_STEP_ELEMS` (n itself when n fits)."""
    budget = max(1, _STEP_ELEMS // (q * p))
    return min(n & -n, 1 << (budget.bit_length() - 1))


@_stage("discovery.score")
def _score_lanes(train, cands, rows=None, *, est_id: int, k: int):
    """(Q, n) MI and join sizes of Q train sketches against candidates.

    ``train`` is the four (Q, cap) train arrays.  ``cands`` is the four
    candidate arrays: (G, cap) group arrays shared by every query, or
    (Q, n, cap) per-query batches.  ``rows`` (Q, n), if given, picks each
    query's lanes out of shared group arrays; otherwise every row is a
    lane.  Lanes are scored ``_lane_step`` at a time in a ``lax.map``, so
    what a step materializes is bounded whatever n is; when all lanes fit
    one step there is no map.  Each step lays its (query, candidate)
    pairs out on one flat lane axis and vmaps :func:`_score_pair` over
    it once: a query axis vmapped around a candidate axis compiles
    many times slower on TPU (the DC-KSG rank sort), and every lane runs
    the same body on its own pair either way, so no result changes.
    """
    Q, p = train[0].shape
    per_query = cands[0].ndim == 3
    if rows is not None:
        n = rows.shape[1]
    else:
        n = cands[0].shape[1 if per_query else 0]
    step = _lane_step(Q, n, p)
    score = jax.vmap(functools.partial(_score_pair, est_id=est_id, k=k))

    def flat(a):  # (Q, step, ...) -> (Q * step, ...)
        return a.reshape((Q * step,) + a.shape[2:])

    def block(lo):
        def cut(a, axis):
            if step == n:
                return a
            return jax.lax.dynamic_slice_in_dim(a, lo, step, axis=axis)

        if rows is not None:
            r = cut(rows, 1)
            lane_cands = [flat(c[r]) for c in cands]
        elif per_query:
            lane_cands = [flat(cut(c, 1)) for c in cands]
        else:
            lane_cands = [
                flat(jnp.broadcast_to(cut(c, 0), (Q, step) + c.shape[1:]))
                for c in cands
            ]
        lane_train = [
            flat(jnp.broadcast_to(t[:, None], (Q, step) + t.shape[1:]))
            for t in train
        ]
        mi, js = score(*lane_train, *lane_cands)
        return mi.reshape(Q, step), js.reshape(Q, step)

    return _map_steps(n, step, block)


def _map_steps(n: int, step: int, block):
    """Run ``block(lo)`` -> tuple of (Q, step) arrays over lanes
    [0, n) in steps of ``step`` and return the (Q, n) arrays; one call,
    with no loop, when ``step == n``."""
    if step == n:
        return block(0)
    out = jax.lax.map(block, jnp.arange(n // step, dtype=jnp.int32) * step)
    # (n // step, Q, step) -> (Q, n), lanes back in order.
    return tuple(
        jnp.moveaxis(a, 0, 1).reshape((a.shape[1], n) + a.shape[3:])
        for a in out
    )


@functools.partial(jax.jit, static_argnames=("est_id", "k"))
def _score_group(
    train_keys, train_vals_f, train_vals_u, train_mask,
    cand_keys, cand_vals_f, cand_vals_u, cand_mask,
    *, est_id: int, k: int,
):
    """Single-query compiled program: (G,) scores for one train sketch."""
    train = (train_keys[None], train_vals_f[None], train_vals_u[None],
             train_mask[None])
    mi, js = _score_lanes(
        train, (cand_keys, cand_vals_f, cand_vals_u, cand_mask),
        est_id=est_id, k=k,
    )
    return mi[0], js[0]


@functools.partial(jax.jit, static_argnames=("est_id", "k"))
def _score_group_many(
    train_keys, train_vals_f, train_vals_u, train_mask,
    cand_keys, cand_vals_f, cand_vals_u, cand_mask,
    *, est_id: int, k: int,
):
    """Multi-query homogeneous scorer: the train arrays carry a leading
    Q axis vmapped over the same candidate group arrays — one compiled
    program returns the (Q, G) score block.  vmap lanes are
    data-parallel, so each row is bit-identical to the single-query
    program on that train sketch."""
    return _score_lanes(
        (train_keys, train_vals_f, train_vals_u, train_mask),
        (cand_keys, cand_vals_f, cand_vals_u, cand_mask),
        est_id=est_id, k=k,
    )


# ---------------------------------------------------------------------------
# Two-phase retrieval programs: join-size prefilter + shortlist scoring.
# ---------------------------------------------------------------------------


@_stage("discovery.prefilter")
def _join_sizes_impl(train_keys, train_mask, cand_keys, cand_mask,
                     rows=None):
    """(Q, n) join sizes: every query against every candidate row, or
    query q against rows ``rows[q]`` when ``rows`` (Q, n) is given.

    The phase-1 prefilter body — one ``searchsorted`` intersect per
    (query, candidate) pair over the pre-fenced sorted keys the device
    store already holds, no value gathers, no estimator work.  The
    reduced ``matched`` vector is the very one the scorers sum, so
    these counts are bit-identical (int32) to the dense path's join
    sizes.  Pairs run ``_lane_step`` at a time, like the scorers.
    """
    Q, p = train_keys.shape
    n = cand_keys.shape[0] if rows is None else rows.shape[1]
    step = _lane_step(Q, n, p)
    size = jax.vmap(presorted_join_size)

    def block(lo):
        if rows is not None:
            r = rows if step == n else jax.lax.dynamic_slice_in_dim(
                rows, lo, step, axis=1)
            ck, cm = cand_keys[r], cand_mask[r]
        else:
            ck, cm = (
                (cand_keys, cand_mask) if step == n else (
                    jax.lax.dynamic_slice_in_dim(cand_keys, lo, step),
                    jax.lax.dynamic_slice_in_dim(cand_mask, lo, step),
                )
            )
            ck = jnp.broadcast_to(ck, (Q,) + ck.shape)
            cm = jnp.broadcast_to(cm, (Q,) + cm.shape)
        tk = jnp.broadcast_to(train_keys[:, None], (Q, step, p))
        tm = jnp.broadcast_to(train_mask[:, None], (Q, step, p))
        js = size(*(a.reshape((Q * step,) + a.shape[2:])
                    for a in (tk, tm, ck, cm)))
        return (js.reshape(Q, step),)

    return _map_steps(n, step, block)[0]


# Local/batched phase-1 program: keyed on (Q-bucket, group bucket, cap)
# shapes only — join sizes are estimator-independent, so every group on
# the same bucket shares one compiled specialization.
_join_sizes = jax.jit(_join_sizes_impl)


@functools.partial(jax.jit, static_argnames=("est_id", "k"))
def _gather_score_group(
    train_keys, train_vals_f, train_vals_u, train_mask,
    cand_keys, cand_vals_f, cand_vals_u, cand_mask, rows,
    *, est_id: int, k: int,
):
    """Phase-2 fused gather-and-score: each query scores only its own
    shortlist rows.

    ``rows`` is (Q, s_bucket) group-row indices; the gather runs inside
    the compiled program (XLA fuses it with the join), so the compact
    (Q, s_bucket, cap) candidate batch never exists as a separate
    dispatch.  Every (query, shortlist-slot) lane runs the exact
    homogeneous scorer body the dense path runs on that (train row,
    candidate row) pair — vmap lanes are data-parallel, so shortlist
    scores are bit-identical to the dense (Q, bucket) run's entries.
    Returns (mi (Q, s_bucket), js (Q, s_bucket)).
    """
    return _score_lanes(
        (train_keys, train_vals_f, train_vals_u, train_mask),
        (cand_keys, cand_vals_f, cand_vals_u, cand_mask), rows,
        est_id=est_id, k=k,
    )


@jax.jit
@_stage("discovery.score")
def _gather_shortlist(keys, vals_f, vals_u, mask, rows):
    """Device gather of shortlist rows into a compact (Q, S, cap) batch
    (the mesh phase-2 operand — ``shard_map`` then shards the S axis)."""
    return keys[rows], vals_f[rows], vals_u[rows], mask[rows]


# Prefix sums over more lanes than this run as a sum within blocks of
# this width plus one over the block totals: XLA's TPU cumsum over a
# whole 2^19-lane row compiles for ~10 s (per program), the two short
# ones in under one.
_PREFIX_BLOCK = 1024


def _prefix_count(passing):
    """Inclusive int32 prefix sum of a (Q, n) bool array along axis 1."""
    Q, n = passing.shape
    b = _PREFIX_BLOCK
    if n <= b or n % b:
        return jnp.cumsum(passing, axis=1, dtype=jnp.int32)
    blk = jnp.cumsum(passing.reshape(Q, n // b, b), axis=2, dtype=jnp.int32)
    tot = blk[:, :, -1]
    off = jnp.cumsum(tot, axis=1, dtype=jnp.int32) - tot
    return (blk + off[:, :, None]).reshape(Q, n)


@_stage("discovery.compact")
def _compact_shortlist(js, live, min_join, sentinel, index, s_bucket: int):
    """Device shortlist compaction — the fused replacement for the host
    :func:`~repro.core.discovery.planner.build_shortlists` boundary.

    Same selection discipline, traced: the cumulative count of passing
    rows is monotone, so the l-th passing row (passing rows first,
    ascending row order — exactly the host path's stable-argsort
    selection) is the first position where the prefix sum reaches
    ``l + 1``; a batched ``searchsorted`` reads all ``s_bucket`` lanes
    off the prefix sum in O(s log bucket), and dead lanes are fenced
    (row -> 0, global id -> sentinel, join size -> 0).  No device sort
    and no scatter (XLA's CPU scatter serialises; this path is an
    order of magnitude cheaper).  Because the ordering, the cut, and
    the fences match the host path bit for bit, everything downstream
    (scores, ranking) is bit-identical.  ``counts`` is returned
    *unclamped* so the collect-side fence can detect
    ``counts > s_bucket`` — the overflow signal.  Returns
    (rows, gidx, jsz, counts), all fixed-shape.
    """
    passing = (js >= min_join) & live[None, :]
    cum = _prefix_count(passing)
    counts = cum[:, -1]
    lanes = jnp.arange(1, s_bucket + 1, dtype=jnp.int32)
    rows_raw = jax.vmap(
        lambda cs: jnp.searchsorted(cs, lanes, side="left")
    )(cum)
    lane_live = (
        jnp.arange(s_bucket, dtype=jnp.int32)[None, :] < counts[:, None]
    )
    rows = jnp.where(lane_live, rows_raw.astype(jnp.int32), 0)
    gidx = jnp.where(lane_live, index[rows], sentinel)
    jsz = jnp.where(
        lane_live, jnp.take_along_axis(js, rows, axis=1), 0
    )
    return rows, gidx, jsz, counts


@functools.partial(jax.jit, static_argnames=("est_id", "k", "s_bucket"))
def _fused_score_group(
    train_keys, train_vals_f, train_vals_u, train_mask,
    cand_keys, cand_vals_f, cand_vals_u, cand_mask,
    index, live, min_join, sentinel,
    *, est_id: int, k: int, s_bucket: int,
):
    """Fused prefilter -> compact -> gather -> score for one group.

    One compiled program per (est_id, Q-bucket, group bucket,
    s_bucket): join sizes, the shortlist compaction, the row gather,
    and the homogeneous scorer all fuse on device.  ``min_join`` and
    ``sentinel`` are traced int32 scalars (device-staged by the caller)
    so varied thresholds don't fork the program ladder.  The full
    (Q, bucket) join-size block rides along in the output: it is only
    transferred if the caller's overflow fallback asks for it.
    Returns (mi (Q, s_bucket), gidx, jsz, js (Q, bucket), counts (Q,)).
    """
    js = _join_sizes_impl(train_keys, train_mask, cand_keys, cand_mask)
    rows, gidx, jsz, counts = _compact_shortlist(
        js, live, min_join, sentinel, index, s_bucket
    )
    mi, _ = _score_lanes(
        (train_keys, train_vals_f, train_vals_u, train_mask),
        (cand_keys, cand_vals_f, cand_vals_u, cand_mask), rows,
        est_id=est_id, k=k,
    )
    return mi, gidx, jsz, js, counts


# ---------------------------------------------------------------------------
# Tiered (phase-0 containment-gated) retrieval programs.
# ---------------------------------------------------------------------------


@_stage("discovery.gate")
def _containment_gate_impl(
    train_keys, train_mask, sig, live, min_cont, *, s_surv: int,
):
    """Phase-0 containment gate for one group.

    One vectorized signature-intersection pass over every candidate row:
    ``sig`` is the group's corpus-resident (rows, width + 1) int32
    signature tier, and each (query, candidate) pair costs one
    ``width``-wide searchsorted probe instead of a capacity-wide one —
    the tier touches ~width ints per candidate where the full prefilter
    reads the whole key row.  Estimated containment is
    ``est_join_size / train_size`` (:func:`signature_join_size`);
    rows at or above the (traced, device-staged) ``min_cont`` threshold
    are compacted into an ``s_surv``-lane survivor buffer with the same
    prefix-sum + batched-searchsorted discipline as
    :func:`_compact_shortlist` — ascending row order, so everything
    downstream keeps the dense path's stable ranking ties.  ``counts``
    is returned unclamped: ``counts > s_surv`` is the survivor-buffer
    overflow fence (the caller falls back to the ungated fused path).
    Returns (rows (Q, s_surv), lane_live (Q, s_surv), counts (Q,)).
    """
    tsize = jnp.maximum(
        jnp.sum(train_mask, axis=1), 1
    ).astype(jnp.float32)
    n = sig.shape[0]
    step = _lane_step(train_keys.shape[0], n, sig.shape[1])
    probe = jax.vmap(jax.vmap(signature_join_size, in_axes=(None, None, 0)),
                     in_axes=(0, 0, None))

    def block(lo):
        s = sig if step == n else jax.lax.dynamic_slice_in_dim(sig, lo, step)
        return (probe(train_keys, train_mask, s),)

    est = _map_steps(n, step, block)[0]
    cont = est / tsize[:, None]
    passing = (cont >= min_cont) & live[None, :]
    cum = _prefix_count(passing)
    counts = cum[:, -1]
    lanes = jnp.arange(1, s_surv + 1, dtype=jnp.int32)
    rows_raw = jax.vmap(
        lambda cs: jnp.searchsorted(cs, lanes, side="left")
    )(cum)
    lane_live = (
        jnp.arange(s_surv, dtype=jnp.int32)[None, :] < counts[:, None]
    )
    rows = jnp.where(lane_live, rows_raw.astype(jnp.int32), 0)
    return rows, lane_live, counts


# Standalone phase-0 program (tests and ad-hoc callers); the tiered
# pipeline below inlines the same body so gate + pipeline fuse into one
# dispatch per group.
_containment_gate = jax.jit(
    _containment_gate_impl, static_argnames=("s_surv",)
)


def _tiered_pipeline_impl(
    train_keys, train_vals_f, train_vals_u, train_mask,
    cand_keys, cand_vals_f, cand_vals_u, cand_mask,
    sig, index, live, min_join, min_cont, sentinel,
    *, est_id: int, k: int, s_surv: int, s_bucket: int,
):
    """Gate -> prefilter -> compact -> gather -> score for one group.

    The phase-0 gate compacts the corpus down to ``s_surv`` survivor
    lanes; every *exact* phase that follows — the join-size prefilter,
    the shortlist compaction, the gather, the homogeneous scorer — runs
    at survivor width instead of corpus width.  That is the tier's
    entire speedup: the only O(corpus) work left per window is the
    ``width``-int signature sweep.  Survivor rows are ascending, the
    within-survivor compaction preserves ascending row order, and the
    scorer body is the dense path's own — so the results for every
    candidate that clears the gate are bit-identical to the ungated
    fused path's entries for those candidates.  Both ``counts`` come
    back unclamped: phase-0 counts fence the survivor buffer, phase-1
    counts fence the shortlist, and either tripping means the caller
    re-runs the window ungated (the PR 6 fence-and-fallback shape).
    Returns (mi (Q, s_bucket), gidx, jsz, counts0 (Q,), counts1 (Q,)).
    """
    rows0, live0, counts0 = _containment_gate_impl(
        train_keys, train_mask, sig, live, min_cont, s_surv=s_surv
    )
    js = _join_sizes_impl(train_keys, train_mask, cand_keys, cand_mask,
                          rows0)
    with jax.named_scope("discovery.compact"):
        passing = (js >= min_join) & live0
        cum = _prefix_count(passing)
        counts1 = cum[:, -1]
        lanes = jnp.arange(1, s_bucket + 1, dtype=jnp.int32)
        pos_raw = jax.vmap(
            lambda cs: jnp.searchsorted(cs, lanes, side="left")
        )(cum)
        lane_live = (
            jnp.arange(s_bucket, dtype=jnp.int32)[None, :]
            < counts1[:, None]
        )
        pos = jnp.where(lane_live, pos_raw.astype(jnp.int32), 0)
        rows = jnp.take_along_axis(rows0, pos, axis=1)
        gidx = jnp.where(lane_live, index[rows], sentinel)
        jsz = jnp.where(
            lane_live, jnp.take_along_axis(js, pos, axis=1), 0
        )
    mi, _ = _score_lanes(
        (train_keys, train_vals_f, train_vals_u, train_mask),
        (cand_keys, cand_vals_f, cand_vals_u, cand_mask), rows,
        est_id=est_id, k=k,
    )
    return mi, gidx, jsz, counts0, counts1


_tiered_score_group = jax.jit(
    _tiered_pipeline_impl,
    static_argnames=("est_id", "k", "s_surv", "s_bucket"),
)


def _pad_rows_q(a: np.ndarray, q_bucket: int) -> np.ndarray:
    """Pad a host (Q, ...) shortlist operand to ``q_bucket`` query lanes
    by repeating lane 0 (the same discipline as :func:`pad_trains_q`)."""
    q = a.shape[0]
    if q_bucket <= q:
        return a
    return np.concatenate(
        [a, np.broadcast_to(a[:1], (q_bucket - q,) + a.shape[1:])]
    )


class _PendingJoinSizes:
    """Dispatched phase-1 prefilter: per-group (Q, bucket) join-size
    matrices pending transfer.  ``collect`` is the first host sync and
    returns [(group, js (q_live, bucket) np.int32), ...] — the operand
    :func:`~repro.core.discovery.planner.build_shortlists` consumes."""

    def __init__(self, blocks: list, q_live: int):
        self._blocks = blocks
        self._q_live = q_live

    def collect(self):
        maybe_fault("collect")
        q = self._q_live
        host = jax.device_get([_cut_q(js, q) for _gp, js in self._blocks])
        return [(gp, js) for (gp, _), js in zip(self._blocks, host)]


class _PendingShortlist:
    """Dispatched phase-2 gather-and-score: per-group (Q, s_bucket)
    score blocks pending transfer.  ``collect`` syncs once and returns
    one (values, global indices, join sizes) triple per live query —
    the concatenated group shortlists, fenced padding included (the
    ranking layer drops sentinel indices)."""

    def __init__(self, blocks: list, q_live: int):
        self._blocks = blocks  # [(Shortlist, mi_dev (Qb, S))]
        self._q_live = q_live

    def collect(self):
        maybe_fault("collect")
        q = self._q_live
        mis = jax.device_get([_cut_q(mi, q) for _sl, mi in self._blocks])
        host = [(sl, mi) for (sl, _), mi in zip(self._blocks, mis)]
        out = []
        for qi in range(q):
            if not host:
                out.append((np.zeros(0, np.float32),
                            np.zeros(0, np.int32),
                            np.zeros(0, np.int32)))
                continue
            out.append((
                np.concatenate([mi[qi] for _, mi in host]),
                np.concatenate([sl.gidx[qi] for sl, _ in host]),
                np.concatenate([sl.js[qi] for sl, _ in host]),
            ))
        return out


class _PendingFused:
    """Dispatched fused two-phase batch (batched backend): per-group
    (Q, s_bucket) score/index/join-size blocks pending transfer.

    ``collect`` transfers the per-group survivor counts and score
    blocks in one batched device sync, then checks the compaction
    fence: any group whose survivor count exceeds its staged
    ``s_bucket`` raises
    :class:`~repro.core.discovery.planner.ShortlistOverflow` *before*
    the resilience layer's collect fault site fires — overflow is part
    of the fused protocol (the caller falls back to the host boundary,
    reusing this handle's ``js_blocks()``), not a failure.  On a clean
    fence it returns the same per-query (values, global indices, join
    sizes) triples as the two-step ``_PendingShortlist``.

    ``observed`` (per-est_id max survivor count) and ``shortlisted``
    are populated at collect/overflow time for hint adaptation and
    admission stats.
    """

    def __init__(self, blocks: list, q_live: int):
        # blocks: [(group, s_bucket, mi, gidx, jsz, js, counts)]
        self._blocks = blocks
        self._q_live = q_live
        self.observed: dict[int, int] = {}
        self.shortlisted = 0

    def _fence_host(self, cs):
        overflow = False
        shortlisted = 0
        for (gp, s_bucket, *_rest), c in zip(self._blocks, cs):
            m = int(c.max(initial=0))
            self.observed[gp.est_id] = max(
                self.observed.get(gp.est_id, 0), m
            )
            shortlisted += int(c.sum())
            if m > s_bucket:
                overflow = True
        self.shortlisted = shortlisted
        if overflow:
            raise ShortlistOverflow(
                "fused shortlist compaction overflowed its staged bucket"
            )

    def _check_fence(self):
        self._fence_host(jax.device_get(
            [_cut_q(c, self._q_live) for *_h, c in self._blocks]
        ))

    def js_blocks(self):
        """Phase-1 join sizes, host-side — the overflow fallback's
        :func:`~repro.core.discovery.planner.build_shortlists` operand.
        The device work already done is reused, not recomputed."""
        q = self._q_live
        return [
            (gp, np.asarray(_cut_q(js, q)))
            for gp, _s, _mi, _gi, _jz, js, _c in self._blocks
        ]

    def collect(self):
        q = self._q_live
        cs, host = jax.device_get((
            [_cut_q(c, q) for *_h, c in self._blocks],
            [(_cut_q(mi, q), _cut_q(gidx, q), _cut_q(jsz, q))
             for _gp, _s, mi, gidx, jsz, _js, _c in self._blocks],
        ))
        self._fence_host(cs)
        maybe_fault("collect")
        out = []
        for qi in range(q):
            if not host:
                out.append((np.zeros(0, np.float32),
                            np.zeros(0, np.int32),
                            np.zeros(0, np.int32)))
                continue
            out.append((
                np.concatenate([mi[qi] for mi, _, _ in host]),
                np.concatenate([gi[qi] for _, gi, _ in host]),
                np.concatenate([jz[qi] for _, _, jz in host]),
            ))
        return out


class _PendingTiered:
    """Dispatched tiered (phase-0-gated) batch (batched backend):
    per-group (Q, s_bucket) score/index/join-size blocks pending
    transfer, plus both compaction fences.

    ``collect`` transfers the survivor counts, shortlist counts, and
    score blocks in one batched device sync, then checks both fences:
    a group whose phase-0 survivor count exceeds its ``s_surv`` lanes
    *or* whose within-survivor shortlist count exceeds its ``s_bucket``
    lanes raises
    :class:`~repro.core.discovery.planner.SurvivorOverflow` before the
    resilience layer's collect fault site fires — the caller re-runs
    the window through the ungated fused path (whose own overflow
    protocol then applies).  ``observed_t0`` / ``observed`` (per-est_id
    max counts) feed the survivor and shortlist hint rungs;
    ``survivors`` / ``shortlisted`` feed admission stats.
    """

    def __init__(self, blocks: list, q_live: int):
        # blocks: [(group, s_surv, s_bucket, mi, gidx, jsz, c0, c1)]
        self._blocks = blocks
        self._q_live = q_live
        self.observed: dict[int, int] = {}
        self.observed_t0: dict[int, int] = {}
        self.shortlisted = 0
        self.survivors = 0

    def _fence_host(self, c0s, c1s):
        overflow = False
        survivors = shortlisted = 0
        for (gp, s_surv, s_bucket, *_rest), c0, c1 in zip(
            self._blocks, c0s, c1s
        ):
            m0 = int(c0.max(initial=0))
            m1 = int(c1.max(initial=0))
            self.observed_t0[gp.est_id] = max(
                self.observed_t0.get(gp.est_id, 0), m0
            )
            self.observed[gp.est_id] = max(
                self.observed.get(gp.est_id, 0), m1
            )
            survivors += int(c0.sum())
            shortlisted += int(c1.sum())
            if m0 > s_surv or m1 > s_bucket:
                overflow = True
        self.survivors = survivors
        self.shortlisted = shortlisted
        if overflow:
            raise SurvivorOverflow(
                "phase-0 containment gate overflowed its staged buffers"
            )

    def collect(self):
        q = self._q_live
        c0s, c1s, host = jax.device_get((
            [_cut_q(c0, q) for *_h, c0, _c1 in self._blocks],
            [_cut_q(c1, q) for *_h, c1 in self._blocks],
            [(_cut_q(mi, q), _cut_q(gidx, q), _cut_q(jsz, q))
             for _gp, _s0, _s1, mi, gidx, jsz, _c0, _c1 in self._blocks],
        ))
        self._fence_host(c0s, c1s)
        maybe_fault("collect")
        out = []
        for qi in range(q):
            if not host:
                out.append((np.zeros(0, np.float32),
                            np.zeros(0, np.int32),
                            np.zeros(0, np.int32)))
                continue
            out.append((
                np.concatenate([mi[qi] for mi, _, _ in host]),
                np.concatenate([gi[qi] for _, gi, _ in host]),
                np.concatenate([jz[qi] for _, _, jz in host]),
            ))
        return out


def stack_trains(trains: list[dict]) -> dict:
    """Stack single-query train dicts into one leading-Q-axis dict."""
    if not trains:
        raise ValueError("no train sketches")
    y_disc = {bool(t.get("y_discrete", False)) for t in trains}
    if len(y_disc) != 1:
        raise ValueError(
            "query_many requires all train targets to share one dtype "
            "(got both discrete and continuous); split the batch"
        )
    out = {
        key: jnp.stack([t[key] for t in trains])
        for key in ("keys", "vals_f", "vals_u", "mask")
    }
    out["y_discrete"] = y_disc.pop()
    return out


def stage_trains_host(sketches: list) -> dict:
    """Stage Q train ``Sketch`` objects into one leading-Q-axis *host*
    dict (contiguous numpy per field) — the CPU half of the bucket
    upload, split out so a scheduler can stack window N+1 while window
    N's programs are still scoring on device.  No device traffic
    happens here; pair with :func:`upload_trains` (or call
    :func:`stack_trains_host`, which composes both).
    """
    if not sketches:
        raise ValueError("no train sketches")
    maybe_fault("staging")
    y_disc = {bool(sk.value_is_discrete) for sk in sketches}
    if len(y_disc) != 1:
        raise ValueError(
            "a train batch must share one target dtype "
            "(got both discrete and continuous); split the batch"
        )
    views = [sk.value_views() for sk in sketches]
    return {
        "keys": np.stack([sk.key_hashes for sk in sketches]),
        "vals_f": np.stack([vf for vf, _ in views]),
        "vals_u": np.stack([vu for _, vu in views]),
        "mask": np.stack([sk.mask for sk in sketches]),
        "y_discrete": y_disc.pop(),
    }


def upload_trains(staged: dict) -> dict:
    """Upload a staged train dict to device — 4 *explicit*
    ``jax.device_put`` calls, one per field.

    Explicit matters: the double-buffered dispatch path runs under
    ``jax.transfer_guard("disallow")`` in tests to prove the overlap
    span performs no hidden host syncs, and ``device_put`` is the only
    H2D legitimately inside that span (it is asynchronous — the copy
    overlaps whatever the device is already running).
    """
    maybe_fault("stack_h2d")
    out = {
        key: jax.device_put(staged[key])
        for key in ("keys", "vals_f", "vals_u", "mask")
    }
    out["y_discrete"] = bool(staged.get("y_discrete", False))
    return out


def stack_trains_host(sketches: list) -> dict:
    """Stack Q train ``Sketch`` objects into one leading-Q-axis device
    dict with a *single* host->device upload per field.

    The per-query path (``train_arrays`` + :func:`stack_trains`) pays
    4 small uploads per query plus a device-side stack; a service
    admitting a 32-query bucket turns that into 128 dispatches of bus
    traffic before any scoring starts.  Stacking on the host first makes
    it 4 uploads per *bucket*.  Values are bit-identical — the same
    bytes, batched.  Composed of :func:`stage_trains_host` (host stack)
    + :func:`upload_trains` (async H2D) so the micro-batch scheduler
    can pipeline the two halves across windows.
    """
    return upload_trains(stage_trains_host(sketches))


def pad_trains_q(trains: dict, q_bucket: int) -> dict:
    """Pad a stacked train dict up to ``q_bucket`` query lanes.

    Dead lanes repeat lane 0 — real data, so every lane runs the exact
    program a live lane runs (no special-cased masks, no NaN paths) and
    the padded program is shape-wise indistinguishable from a full
    bucket.  vmap lanes are data-parallel, so live lanes are
    bit-identical to the unpadded run; callers slice ``[:Q]``.
    """
    Q = int(trains["keys"].shape[0])
    if q_bucket < Q:
        raise ValueError(f"q_bucket {q_bucket} < batch size {Q}")
    if q_bucket == Q:
        return trains
    pad = q_bucket - Q
    out = {
        key: jnp.concatenate(
            [trains[key],
             jnp.broadcast_to(trains[key][:1],
                              (pad,) + trains[key].shape[1:])]
        )
        for key in ("keys", "vals_f", "vals_u", "mask")
    }
    out["y_discrete"] = bool(trains.get("y_discrete", False))
    return out


def _cut_q(a, q_live: int):
    """Drop padded query lanes *on device* so they never cross the bus
    (row-slice before the host transfer; a no-op for unpadded runs)."""
    return a if int(a.shape[0]) == q_live else a[:q_live]


class _PendingScores:
    """Dispatched-but-untransferred dense batch: ``collect`` is the
    first host sync, returning (mi (Q, C), js (Q, C)) with padded query
    lanes already sliced off."""

    def __init__(self, plan: QueryPlan, blocks: list, q_live: int):
        self._plan = plan
        self._blocks = blocks
        self._q_live = q_live

    def collect(self):
        maybe_fault("collect")
        q = self._q_live
        blocks = [
            (gp, _cut_q(mi, q), _cut_q(js, q))
            for gp, mi, js in self._blocks
        ]
        return _scatter(self._plan, blocks, q)


class _PendingTopk:
    """Dispatched distributed top-k: device-merged (Q, k_merge) triples
    pending transfer.  ``collect`` syncs once and returns one
    (values, global indices, join sizes) triple per live query.

    The on-device merge keeps a pow-2-bucketed ``k_merge`` columns (so
    merge programs ride the same k-ladder as the shard scorers);
    ``k_live`` is the exact requested result count, sliced off on the
    host — the merge output is ordered best-first, so the first
    ``k_live`` columns of a wider merge are the same values.  An empty
    handle (``vals is None`` — every shortlist came back empty) yields
    zero-length triples.
    """

    def __init__(self, vals, gidx, jsz, q_live: int, k_live: int | None = None):
        self._vals = vals
        self._gidx = gidx
        self._jsz = jsz
        self._q_live = q_live
        self._k_live = k_live

    def collect(self):
        maybe_fault("collect")
        q = self._q_live
        if self._vals is None:
            empty = (np.zeros(0, np.float32), np.zeros(0, np.int32),
                     np.zeros(0, np.int32))
            return [empty for _ in range(q)]
        kl = self._k_live
        v, gi, js = jax.device_get((
            _cut_q(self._vals, q), _cut_q(self._gidx, q),
            _cut_q(self._jsz, q),
        ))
        if kl is not None and kl < v.shape[1]:
            v, gi, js = v[:, :kl], gi[:, :kl], js[:, :kl]
        return [(v[i], gi[i], js[i]) for i in range(q)]


class _PendingFusedTopk(_PendingTopk):
    """Dispatched fused two-phase top-k (distributed backend): the
    device-merged (Q, k_merge) triples of `_PendingTopk`, plus the
    shard-local compaction fence.

    ``collect`` transfers the per-(group, shard) survivor counts and
    the merged triple in one batched device sync, then checks the
    fence: a shard whose local survivor count exceeds its ``s_shard``
    lanes raises
    :class:`~repro.core.discovery.planner.ShortlistOverflow` (the
    caller rebuilds host shortlists from ``js_blocks()`` and runs the
    two-step mesh path).  Only on a clean fence does the resilience
    layer's collect fault site fire — exactly once, as on the
    ``_PendingTopk`` path.
    """

    def __init__(self, vals, gidx, jsz, q_live: int, k_live: int,
                 fence: list):
        super().__init__(vals, gidx, jsz, q_live, k_live=k_live)
        # fence: [(group, s_shard, counts (Qb, n_shards), js (Qb, rows))]
        self._fence = fence
        self.observed: dict[int, int] = {}
        self.shortlisted = 0

    def _fence_host(self, cs):
        overflow = False
        shortlisted = 0
        for (gp, s_shard, _counts, _js), c in zip(self._fence, cs):
            m = int(c.max(initial=0))
            self.observed[gp.est_id] = max(
                self.observed.get(gp.est_id, 0), m
            )
            shortlisted += int(c.sum())
            if m > s_shard:
                overflow = True
        self.shortlisted = shortlisted
        if overflow:
            raise ShortlistOverflow(
                "fused shard-local compaction overflowed its staged bucket"
            )

    def _check_fence(self):
        self._fence_host(jax.device_get(
            [_cut_q(c, self._q_live) for _gp, _s, c, _js in self._fence]
        ))

    def js_blocks(self):
        q = self._q_live
        return [
            (gp, np.asarray(_cut_q(js, q)))
            for gp, _s, _c, js in self._fence
        ]

    def collect(self):
        q = self._q_live
        if self._vals is None:
            self._check_fence()
            return super().collect()
        cs, v, gi, js = jax.device_get((
            [_cut_q(c, q) for _gp, _s, c, _js in self._fence],
            _cut_q(self._vals, q), _cut_q(self._gidx, q),
            _cut_q(self._jsz, q),
        ))
        self._fence_host(cs)
        maybe_fault("collect")
        kl = self._k_live
        if kl is not None and kl < v.shape[1]:
            v, gi, js = v[:, :kl], gi[:, :kl], js[:, :kl]
        return [(v[i], gi[i], js[i]) for i in range(q)]


class _PendingTieredTopk(_PendingTopk):
    """Dispatched tiered top-k (distributed backend): the device-merged
    (Q, k_merge) triples of ``_PendingTopk`` plus both shard-local
    fences — phase-0 survivor counts and within-survivor shortlist
    counts per (group, shard).  A shard exceeding either staged width
    raises :class:`~repro.core.discovery.planner.SurvivorOverflow`; the
    caller re-runs the window through the ungated fused mesh path.
    Only on a clean fence does the collect fault site fire."""

    def __init__(self, vals, gidx, jsz, q_live: int, k_live: int,
                 fence: list):
        super().__init__(vals, gidx, jsz, q_live, k_live=k_live)
        # fence: [(group, s_surv_shard, s_shard,
        #          counts0 (Qb, shards), counts1 (Qb, shards))]
        self._fence = fence
        self.observed: dict[int, int] = {}
        self.observed_t0: dict[int, int] = {}
        self.shortlisted = 0
        self.survivors = 0

    def _fence_host(self, c0s, c1s):
        overflow = False
        survivors = shortlisted = 0
        for (gp, s_surv, s_shard, _c0, _c1), c0, c1 in zip(
            self._fence, c0s, c1s
        ):
            m0 = int(c0.max(initial=0))
            m1 = int(c1.max(initial=0))
            self.observed_t0[gp.est_id] = max(
                self.observed_t0.get(gp.est_id, 0), m0
            )
            self.observed[gp.est_id] = max(
                self.observed.get(gp.est_id, 0), m1
            )
            survivors += int(c0.sum())
            shortlisted += int(c1.sum())
            if m0 > s_surv or m1 > s_shard:
                overflow = True
        self.survivors = survivors
        self.shortlisted = shortlisted
        if overflow:
            raise SurvivorOverflow(
                "shard-local containment gate overflowed its staged "
                "buffers"
            )

    def collect(self):
        q = self._q_live
        if self._vals is None:
            self._fence_host(*jax.device_get((
                [_cut_q(c0, q) for _g, _s0, _s1, c0, _c1 in self._fence],
                [_cut_q(c1, q) for _g, _s0, _s1, _c0, c1 in self._fence],
            )))
            return super().collect()
        c0s, c1s, v, gi, js = jax.device_get((
            [_cut_q(c0, q) for _g, _s0, _s1, c0, _c1 in self._fence],
            [_cut_q(c1, q) for _g, _s0, _s1, _c0, c1 in self._fence],
            _cut_q(self._vals, q), _cut_q(self._gidx, q),
            _cut_q(self._jsz, q),
        ))
        self._fence_host(c0s, c1s)
        maybe_fault("collect")
        kl = self._k_live
        if kl is not None and kl < v.shape[1]:
            v, gi, js = v[:, :kl], gi[:, :kl], js[:, :kl]
        return [(v[i], gi[i], js[i]) for i in range(q)]


def _as_stacked_trains(trains: dict | list[dict]) -> dict:
    if isinstance(trains, dict):
        if trains["keys"].ndim == 1:  # single query -> Q == 1
            return {
                **{key: trains[key][None] for key in
                   ("keys", "vals_f", "vals_u", "mask")},
                "y_discrete": bool(trains.get("y_discrete", False)),
            }
        return trains
    return stack_trains(trains)


def _train_row(trains: dict, q: int) -> tuple:
    return (trains["keys"][q], trains["vals_f"][q],
            trains["vals_u"][q], trains["mask"][q])


def _cand_args(gp: GroupPlan) -> tuple:
    a = gp.arrays
    return (a["keys"], a["vals_f"], a["vals_u"], a["mask"])


def _scatter(plan: QueryPlan, blocks, Q: int):
    """Device results -> dense (Q, C) host matrices in candidate order.

    ``blocks`` entries are (group, mi, js) with mi/js of shape
    (Q, bucket).  np.asarray here is the first host sync — callers
    dispatch every group program before building the output.
    """
    mi_out = np.zeros((Q, plan.n_candidates), np.float32)
    js_out = np.zeros((Q, plan.n_candidates), np.int32)
    for gp, mi, js in blocks:
        g = gp.size
        mi_out[:, gp.index[:g]] = np.asarray(mi)[:, :g]
        js_out[:, gp.index[:g]] = np.asarray(js)[:, :g]
    return mi_out, js_out


class Executor:
    """Backend interface: dense scoring + ranked retrieval of a plan."""

    def execute(self, plan: QueryPlan, trains: dict | list[dict]):
        """Score every (query, candidate) pair.

        ``trains`` is a stacked leading-Q-axis dict (see
        :func:`stack_trains`), a list of per-query train dicts, or a
        single train dict.  Returns (mi (Q, C), js (Q, C)) numpy arrays
        in the original candidate order.
        """
        raise NotImplementedError

    def topk(self, plan: QueryPlan, trains: dict | list[dict], top_k: int):
        """Per-query top-k: list of (values, global indices, join sizes),
        one triple per query, best first.  Default = dense + argsort;
        the distributed executor overrides with the per-shard merge."""
        trains = _as_stacked_trains(trains)
        mi, js = self.execute(plan, trains)
        out = []
        for q in range(mi.shape[0]):
            order = np.argsort(-mi[q], kind="stable")[:min(top_k, mi.shape[1])]
            out.append((mi[q][order], order.astype(np.int32), js[q][order]))
        return out


class PartitionedLocalExecutor(Executor):
    """Per-query estimator-partitioned scoring (the single-query path).

    Every (query, group) program is dispatched before any result is
    copied to the host, so group programs overlap on device instead of
    running compute -> transfer -> compute lockstep.
    """

    def __init__(self, k: int = 3):
        self.k = k

    def execute(self, plan, trains):
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        blocks = []
        for gp in plan.groups:
            per_q = [
                _score_group(
                    *_train_row(trains, q), *_cand_args(gp),
                    est_id=gp.est_id, k=self.k,
                )
                for q in range(Q)
            ]
            blocks.append((
                gp,
                jnp.stack([mi for mi, _ in per_q]),
                jnp.stack([js for _, js in per_q]),
            ))
        return _scatter(plan, blocks, Q)


class BatchedExecutor(Executor):
    """Multi-query batched scoring: one program per group, leading Q
    axis, with optional admission-controlled Q padding."""

    def __init__(self, k: int = 3):
        self.k = k

    def dispatch(self, plan, trains, *, q_bucket: int | None = None):
        """Enqueue every group program without syncing; returns a
        pending handle whose ``collect`` performs the first transfer.

        ``q_bucket`` pads the query axis up the pow-two ladder (see
        :func:`pad_trains_q`); results for the live lanes are
        bit-identical to the unpadded run and the dead lanes never
        leave the device.
        """
        maybe_fault("dispatch", "batched")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        blocks = [
            (gp, *_score_group_many(*t_args, *_cand_args(gp),
                                    est_id=gp.est_id, k=self.k))
            for gp in plan.groups
        ]
        return _PendingScores(plan, blocks, Q)

    def execute(self, plan, trains, *, q_bucket: int | None = None):
        return self.dispatch(plan, trains, q_bucket=q_bucket).collect()

    # -- two-phase retrieval ------------------------------------------------

    def prefilter_dispatch(self, plan, trains, *, q_bucket: int | None = None):
        """Phase 1: enqueue the join-size prefilter for every group —
        no scoring, no host sync.  The returned handle's ``collect``
        yields the (group, join-size matrix) pairs that
        :func:`~repro.core.discovery.planner.build_shortlists` turns
        into phase-2 shortlists."""
        maybe_fault("prefilter_dispatch", "batched")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        blocks = [
            (gp, _join_sizes(trains["keys"], trains["mask"],
                             gp.arrays["keys"], gp.arrays["mask"]))
            for gp in plan.groups
        ]
        return _PendingJoinSizes(blocks, Q)

    def shortlist_dispatch(
        self, plan, trains, shortlists, *, q_bucket: int | None = None,
    ):
        """Phase 2: enqueue the fused gather-and-score program for every
        non-empty shortlist; the handle's ``collect`` returns per-query
        (values, global indices, join sizes) triples over exactly the
        candidates that passed the prefilter."""
        maybe_fault("shortlist_dispatch", "batched")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        qb = q_bucket or Q
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        blocks = []
        for sl in shortlists:
            if sl is None:
                continue
            rows = jnp.asarray(_pad_rows_q(sl.rows, qb))
            mi, _ = _gather_score_group(
                *t_args, *_cand_args(sl.group), rows,
                est_id=sl.group.est_id, k=self.k,
            )
            blocks.append((sl, mi))
        return _PendingShortlist(blocks, Q)

    def fused_dispatch(
        self, plan, trains, spec, min_join, *, q_bucket: int | None = None,
    ):
        """Fused two-phase: one program per group runs prefilter,
        shortlist compaction, gather, and score without leaving the
        device — nothing crosses the bus until the handle's
        ``collect``.  ``spec`` is a
        :class:`~repro.core.discovery.planner.FusedSpec` carrying the
        pre-chosen per-group compaction widths; ``min_join`` may be a
        python int (staged through the memo cache) or an already-staged
        device scalar.  The handle raises ``ShortlistOverflow`` at
        collect when a width guess was too small — fall back to the
        host boundary via its ``js_blocks()``."""
        maybe_fault("fused_dispatch", "batched")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        mj = (min_join if isinstance(min_join, jax.Array)
              else stage_min_join(min_join))
        sentinel = plan.sentinel_dev
        if sentinel is None:
            sentinel = jnp.asarray(np.int32(plan.n_candidates))
        blocks = []
        for gp, s_bucket in zip(plan.groups, spec.s_buckets):
            index_dev = gp.index_dev
            if index_dev is None:
                index_dev = jnp.asarray(gp.index.astype(np.int32))
            mi, gidx, jsz, js, counts = _fused_score_group(
                *t_args, *_cand_args(gp), index_dev, gp.live, mj,
                sentinel, est_id=gp.est_id, k=self.k,
                s_bucket=int(s_bucket),
            )
            blocks.append((gp, int(s_bucket), mi, gidx, jsz, js, counts))
        return _PendingFused(blocks, Q)

    def tiered_dispatch(
        self, plan, trains, tspec, spec, min_join, min_containment,
        *, q_bucket: int | None = None,
    ):
        """Tiered retrieval: the phase-0 containment gate plus the
        fused pipeline, one dispatch per group, nothing across the bus
        until the handle's ``collect``.  ``tspec`` is a
        :class:`~repro.core.discovery.planner.TierSpec` carrying the
        survivor-buffer widths, ``spec`` the usual
        :class:`~repro.core.discovery.planner.FusedSpec` (each group's
        shortlist width is clamped to its survivor width — phase 1
        cannot pass more rows than phase 0 kept).  ``min_containment``
        may be a float (staged through the memo cache) or an
        already-staged device scalar.  The handle raises
        ``SurvivorOverflow`` at collect when either staged width was
        too small — re-run the window through ``fused_dispatch``."""
        maybe_fault("tiered_dispatch", "batched")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        mj = (min_join if isinstance(min_join, jax.Array)
              else stage_min_join(min_join))
        mc = (min_containment if isinstance(min_containment, jax.Array)
              else stage_min_containment(min_containment))
        sentinel = plan.sentinel_dev
        if sentinel is None:
            sentinel = jnp.asarray(np.int32(plan.n_candidates))
        blocks = []
        for gp, s_surv, s_bucket in zip(
            plan.groups, tspec.s_survivors, spec.s_buckets
        ):
            if gp.sig is None:
                raise ValueError(
                    "tiered dispatch on a plan without a signature tier"
                )
            sb = min(int(s_bucket), int(s_surv))
            index_dev = gp.index_dev
            if index_dev is None:
                index_dev = jnp.asarray(gp.index.astype(np.int32))
            mi, gidx, jsz, c0, c1 = _tiered_score_group(
                *t_args, *_cand_args(gp), gp.sig, index_dev, gp.live,
                mj, mc, sentinel, est_id=gp.est_id, k=self.k,
                s_surv=int(s_surv), s_bucket=sb,
            )
            blocks.append((gp, int(s_surv), sb, mi, gidx, jsz, c0, c1))
        return _PendingTiered(blocks, Q)


def _shard_topk_plan(c_padded: int, n_shards: int, top_k: int) -> tuple[int, int]:
    """Per-shard and global result counts for a distributed top-k.

    ``k_shard`` rides a small pow-2 ladder (next power of two >=
    ``top_k``, clamped to the shard size): each (Q-bucket, k-bucket)
    pair — not each exact ``top_k`` — compiles its own ``shard_map``
    program, so varied top-k traffic stops minting shard programs.  A
    ladder ``k_shard`` only ever *over*-keeps per shard, and clamping
    must never shrink the *global* result below ``min(top_k, C)``:
    every shard keeps ``min(k_bucket, shard_size)`` (all global top-k
    could live in one shard), and the merge returns
    ``min(top_k, shards · per_shard)``.
    """
    shard_size = c_padded // n_shards
    k_shard = max(min(_next_pow2(top_k), shard_size), 1)
    k_final = min(top_k, n_shards * k_shard)
    return k_shard, k_final


@functools.lru_cache(maxsize=128)
def _make_group_shard_scorer(mesh: Mesh, est_id: int, k_shard: int, k: int):
    """Compiled homogeneous shard_map scorer for one estimator group.

    The candidate rows of the group are sharded over the 'data' axis;
    the (Q, cap) train arrays are replicated.  ``k_shard == 0`` returns
    the dense (Q, rows) scores; otherwise each shard emits its top
    ``k_shard`` per query (dead rows fenced to -inf via ``live``).
    Cached per (mesh, est_id, k_shard, k) so repeat queries re-trace
    nothing; jit's shape cache handles the bucket ladder underneath.
    """
    axis = "data"
    sh = P(None, axis)  # (Q, rows) outputs / (rows, cap) inputs use P(axis)
    rep = P()

    def local(tk, tf, tu, tm, ck, cf, cu, cm, live):
        mi, js = _score_lanes(
            (tk, tf, tu, tm), (ck, cf, cu, cm), est_id=est_id, k=k
        )
        if k_shard == 0:
            return mi, js
        fenced = jnp.where(live[None, :], mi, -jnp.inf)
        v, i = jax.lax.top_k(fenced, k_shard)
        return v, i, jnp.take_along_axis(js, i, axis=1)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(rep, rep, rep, rep,
                  P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(sh, sh) if k_shard == 0 else (sh, sh, sh),
        check=False,
    )
    return _register_shard_scorer(jax.jit(fn))


# Every jitted shard scorer built, so compile_count() can see them (the
# lru_cache above does not expose its values).  Scorers the lru_cache
# evicts are deliberately retained up to the registry cap so
# compile_count() stays monotone for delta assertions; past the cap the
# oldest entry (and its compiled executables) is dropped to bound
# memory — far beyond any workload the bounded-compile tests model.
_SHARD_SCORERS: list = []
_SHARD_SCORER_REGISTRY_MAX = 512


def _register_shard_scorer(jitted):
    _SHARD_SCORERS.append(jitted)
    if len(_SHARD_SCORERS) > _SHARD_SCORER_REGISTRY_MAX:
        del _SHARD_SCORERS[0]
    return jitted


@functools.lru_cache(maxsize=16)
def _make_join_size_shard_scorer(mesh: Mesh):
    """Compiled shard_map join-size prefilter: candidate rows sharded
    over 'data', the (Q, cap) train keys/mask replicated, (Q, rows)
    int32 join sizes out.  Estimator-independent — one program per mesh
    serves every group; jit's shape cache handles the bucket ladder."""
    axis = "data"
    fn = shard_map(
        _join_sizes_impl,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=P(None, axis),
        check=False,
    )
    return _register_shard_scorer(jax.jit(fn))


@functools.lru_cache(maxsize=128)
def _make_shortlist_shard_scorer(mesh: Mesh, est_id: int, k_shard: int, k: int):
    """Compiled shard_map phase-2 scorer for one estimator group's
    shortlist: the gathered compact (Q, s_bucket, cap) candidate batch
    is sharded over the shortlist axis, trains replicated; each shard
    scores its slots (every (query, slot) lane runs the homogeneous
    scorer body on its own gathered row), fences dead slots to -inf via
    ``live``, and emits its top ``k_shard`` per query with global
    candidate ids and join sizes gathered alongside — ready for the
    cross-group on-device merge."""
    axis = "data"
    sh = P(None, axis)

    def local(tk, tf, tu, tm, ck, cf, cu, cm, gi, live):
        mi, js = _score_lanes(
            (tk, tf, tu, tm), (ck, cf, cu, cm), est_id=est_id, k=k
        )
        fenced = jnp.where(live, mi, -jnp.inf)
        v, i = jax.lax.top_k(fenced, k_shard)
        return (
            v,
            jnp.take_along_axis(gi, i, axis=1),
            jnp.take_along_axis(js, i, axis=1),
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), sh, sh, sh, sh, sh, sh),
        out_specs=(sh, sh, sh),
        check=False,
    )
    return _register_shard_scorer(jax.jit(fn))


@functools.lru_cache(maxsize=128)
def _make_fused_shard_scorer(
    mesh: Mesh, est_id: int, s_shard: int, k_shard: int, k: int
):
    """Compiled shard_map fused two-phase scorer for one group.

    Everything happens shard-locally: each shard prefilters its own
    candidate rows, compacts its own top-``s_shard`` shortlist (the
    same stable-argsort discipline as the host boundary, over local
    rows), gathers from its *local* arrays, scores, and emits its top
    ``k_shard`` winners — no shard ever touches a global group array,
    and the gather payload stays O(s_shard · cap) per shard.  Survivor
    counts ((Q, 1) per shard -> (Q, shards)) and the local join-size
    blocks ride along for the collect-side overflow fence and the
    host-boundary fallback respectively.  ``gi`` rows already hold
    *global* candidate ids (the plan's device-resident index, sharded),
    so winners merge across groups without re-indexing.
    """
    axis = "data"
    sh = P(None, axis)
    rep = P()

    def local(tk, tf, tu, tm, ck, cf, cu, cm, gi, live, mj, sentinel):
        js = _join_sizes_impl(tk, tm, ck, cm)
        rows, gidx, jsz, counts = _compact_shortlist(
            js, live, mj, sentinel, gi, s_shard
        )
        mi, _ = _score_lanes(
            (tk, tf, tu, tm), (ck, cf, cu, cm), rows, est_id=est_id, k=k
        )
        lane_live = gidx != sentinel
        fenced = jnp.where(lane_live, mi, -jnp.inf)
        v, pos = jax.lax.top_k(fenced, k_shard)
        return (
            v,
            jnp.take_along_axis(gidx, pos, axis=1),
            jnp.take_along_axis(jsz, pos, axis=1),
            counts[:, None],
            js,
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(rep, rep, rep, rep,
                  P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), rep, rep),
        out_specs=(sh, sh, sh, sh, sh),
        check=False,
    )
    return _register_shard_scorer(jax.jit(fn))


@functools.lru_cache(maxsize=128)
def _make_tiered_shard_scorer(
    mesh: Mesh, est_id: int, s_surv: int, s_bucket: int, k_shard: int,
    k: int,
):
    """Compiled shard_map tiered scorer for one group.

    The corpus is partitioned across shards (signature tier and full
    store sharded identically over 'data', so the survivor gather stays
    shard-local); each shard runs the whole gate -> prefilter ->
    compact -> gather -> score pipeline on its own rows and emits its
    top ``k_shard`` winners for the usual on-device cross-group merge.
    Both compaction fences ((Q, 1) per shard -> (Q, shards)) ride along
    for the collect-side overflow check.  Widths are per shard.
    """
    axis = "data"
    sh = P(None, axis)
    rep = P()

    def local(tk, tf, tu, tm, ck, cf, cu, cm, sig, gi, live, mj, mc,
              sentinel):
        mi, gidx, jsz, c0, c1 = _tiered_pipeline_impl(
            tk, tf, tu, tm, ck, cf, cu, cm, sig, gi, live, mj, mc,
            sentinel, est_id=est_id, k=k, s_surv=s_surv,
            s_bucket=s_bucket,
        )
        lane_live = gidx != sentinel
        fenced = jnp.where(lane_live, mi, -jnp.inf)
        v, pos = jax.lax.top_k(fenced, k_shard)
        return (
            v,
            jnp.take_along_axis(gidx, pos, axis=1),
            jnp.take_along_axis(jsz, pos, axis=1),
            c0[:, None],
            c1[:, None],
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(rep, rep, rep, rep,
                  P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(axis), rep, rep, rep),
        out_specs=(sh, sh, sh, sh, sh),
        check=False,
    )
    return _register_shard_scorer(jax.jit(fn))


def compile_count() -> int:
    """Total compiled specializations across the discovery scorer
    programs — the admission-control test hook.

    Sums the jit-cache entry counts of every scorer entry point (each
    entry is one traced+compiled (est_id, shape) specialization), so a
    test can assert that a bursty mixed workload compiles at most
    |estimator signatures| x |Q-buckets| x |group buckets| programs —
    and, for two-phase retrieval, that randomized ``min_join``
    selectivity stays bounded by the shortlist-bucket ladder.
    """
    fns = [_score_group, _score_group_many, score_batch,
           score_batch_reference, _globalize_rows, _merge_topk_device,
           _join_sizes, _gather_score_group, _gather_shortlist,
           _fused_score_group, _containment_gate, _tiered_score_group,
           *_SHARD_SCORERS]
    return sum(
        f._cache_size() for f in fns if hasattr(f, "_cache_size")
    )


@functools.partial(jax.jit, static_argnames=("k_shard", "shard_rows"))
@_stage("discovery.topk")
def _globalize_rows(i, index_dev, *, k_shard: int, shard_rows: int):
    """Map per-shard top-k row indices (Q, shards·k_shard) to global
    candidate indices on device: undo the shard-local numbering, then
    gather through the group's row->candidate index (dead rows hit the
    sentinel and are filtered by the ranking layer).  The output keeps
    the indices' sharding: on an Explicit-axis mesh the gather's output
    sharding cannot be inferred from a replicated operand and sharded
    indices, so it is named here."""
    total = i.shape[1]
    shard = jnp.arange(total, dtype=jnp.int32) // k_shard
    return index_dev.at[i + (shard * shard_rows)[None, :]].get(
        out_sharding=jax.typeof(i).sharding
    )


def _concat1(xs):
    """Cross-group concat that skips the dispatch when there is only
    one group — the common single-estimator corpus would otherwise pay
    three no-op device programs per query window."""
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)


@functools.partial(jax.jit, static_argnames=("k_final",))
@_stage("discovery.topk")
def _merge_topk_device(v, gi, js, *, k_final: int):
    """Cross-group merge on device: one ``lax.top_k`` over the
    concatenated per-group/per-shard winners, all Q rows at once.  The
    host then transfers O(Q · k_final) scalars instead of the full
    O(Q · groups · shards · k_shard) winner set."""
    vals, pos = jax.lax.top_k(v, k_final)
    return (
        vals,
        jnp.take_along_axis(gi, pos, axis=1),
        jnp.take_along_axis(js, pos, axis=1),
    )


# Replicated stagings of tiny scalars (min_join, sentinel) per mesh:
# keyed by (mesh, id(source)) with a strong reference to the source so
# the id cannot be recycled while the entry lives.  Bounded: the
# min_join cache upstream is itself bounded and sentinels are one per
# live plan.
_REPL_CACHE: dict = {}
_REPL_CACHE_MAX = 256


def _stage_replicated(mesh: Mesh, arr: jax.Array) -> jax.Array:
    """Memoized mesh-replicated copy of a device scalar, so repeat
    dispatches re-ship nothing (the fused transfer-guard contract)."""
    key = (mesh, id(arr))
    hit = _REPL_CACHE.get(key)
    if hit is not None and hit[0] is arr:
        return hit[1]
    if len(_REPL_CACHE) >= _REPL_CACHE_MAX:
        _REPL_CACHE.clear()
    out = jax.device_put(arr, jax.NamedSharding(mesh, P()))
    _REPL_CACHE[key] = (arr, out)
    return out


def _pad_group_to_shards(
    gp: GroupPlan, n_shards: int, sentinel: int
) -> GroupPlan:
    """Zero-pad a group bucket whose row count doesn't divide the shard
    count (only reachable for non-power-of-two meshes on plans built
    without the mesh hint — the planner ladder normally absorbs this).
    ``sentinel`` is the dead-row global index (= plan.n_candidates)."""
    b = gp.bucket
    if b % n_shards == 0:
        return gp
    b_new = -(-b // n_shards) * n_shards
    pad = b_new - b
    arrays = {
        name: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        for name, a in gp.arrays.items()
    }
    # Padded key rows must stay searchsorted-safe: re-fence through the
    # one effective-keys helper (idempotent for the live rows).
    arrays["keys"] = effective_keys(arrays["keys"], arrays["mask"])
    index = np.concatenate(
        [gp.index.astype(np.int32), np.full(pad, sentinel, np.int32)]
    )
    live = jnp.pad(gp.live, (0, pad))
    sig = gp.sig
    if sig is not None:
        # Signature pad rows carry the -1 key fence (and a -1 live-key
        # count, clamped to 0 in the gate); they are dead via ``live``
        # regardless.
        sig = jnp.pad(sig, ((0, pad), (0, 0)), constant_values=-1)
    return GroupPlan(gp.est_id, arrays, index, live, gp.size,
                     jnp.asarray(index), sig)


class GroupMajorDistributedExecutor(Executor):
    """Mesh-sharded scoring with estimator partitioning *outside* the
    collective: one homogeneous shard_map program per group, candidates
    sharded over the 'data' axis, train replicated.  ``topk`` reduces
    the merge payload to O(groups · shards · k_shard) scalars."""

    # One live plan per target dtype is the steady state (the index
    # caches exactly that), so two entries suffice; a deeper cache would
    # pin superseded plans' device buffers during ingest-while-serving.
    _PAD_CACHE_MAX = 2

    def __init__(self, mesh: Mesh, k: int = 3):
        self.mesh = auto_axes(mesh)
        self.k = k
        # Shard-padded groups (+ device-resident row->candidate index
        # arrays) per plan: keyed by plan identity, holding a strong
        # reference to the plan so the id cannot be recycled while the
        # entry lives.  Repeat queries against a cached plan re-pad and
        # re-upload nothing (pad is a no-op device-array passthrough for
        # buckets that already divide the shard count, a jnp.pad per
        # group otherwise).
        self._pad_cache: dict[
            int, tuple[QueryPlan, list[GroupPlan], list[jax.Array]]
        ] = {}

    def _groups(self, plan):
        n_shards = self.mesh.shape["data"]
        hit = self._pad_cache.get(id(plan))
        if hit is not None and hit[0] is plan:
            return n_shards, hit[1], hit[2]
        groups = [
            _pad_group_to_shards(gp, n_shards, plan.n_candidates)
            for gp in plan.groups
        ]
        # Stage every group buffer mesh-resident once per plan —
        # candidate arrays, live mask, and row->candidate index sharded
        # over 'data' exactly as the shard_map in_specs consume them.
        # Repeat dispatches against a cached plan then move *nothing*
        # across the bus (the fused path's transfer-guard contract);
        # without this, jit would silently re-shard the single-device
        # plan buffers on every call.
        row_sh = jax.NamedSharding(self.mesh, P("data"))
        groups = [
            GroupPlan(
                gp.est_id,
                {
                    name: jax.device_put(
                        a, jax.NamedSharding(
                            self.mesh,
                            P("data", *(None,) * (a.ndim - 1)),
                        )
                    )
                    for name, a in gp.arrays.items()
                },
                gp.index,
                jax.device_put(gp.live, row_sh),
                gp.size,
                jax.device_put(
                    gp.index_dev if gp.index_dev is not None
                    else jnp.asarray(gp.index.astype(np.int32)),
                    row_sh,
                ),
                # Signature tier rows partition across shards exactly
                # like the full store, so the tiered pipeline's
                # survivor gather never leaves the shard.
                None if gp.sig is None else jax.device_put(
                    gp.sig, jax.NamedSharding(self.mesh, P("data", None))
                ),
            )
            for gp in groups
        ]
        # Replicated row->candidate index per group for the *post*-
        # collective merge (``_globalize_rows`` consumes it outside
        # shard_map, so it needs the un-sharded layout).
        gi_devs = [
            jax.device_put(gp.index_dev, jax.NamedSharding(self.mesh, P()))
            for gp in groups
        ]
        while len(self._pad_cache) >= self._PAD_CACHE_MAX:
            self._pad_cache.pop(next(iter(self._pad_cache)))
        self._pad_cache[id(plan)] = (plan, groups, gi_devs)
        return n_shards, groups, gi_devs

    def execute(self, plan, trains):
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        _, groups, _ = self._groups(plan)
        blocks = []
        for gp in groups:
            fn = _make_group_shard_scorer(self.mesh, gp.est_id, 0, self.k)
            mi, js = fn(*t_args, *_cand_args(gp), gp.live)
            blocks.append((gp, mi, js))
        return _scatter(plan, blocks, Q)

    def topk_dispatch(self, plan, trains, top_k: int,
                      *, q_bucket: int | None = None):
        """Enqueue per-group shard scorers and the on-device cross-group
        merge; no host sync happens until the returned handle's
        ``collect``.  One ``lax.top_k`` over the concatenated group
        winners replaces the former per-query host merge loop, so merge
        traffic no longer scales with Q."""
        maybe_fault("dispatch", "distributed")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        n_shards, groups, gi_devs = self._groups(plan)
        vs, gis, jss = [], [], []
        for gp, gi_dev in zip(groups, gi_devs):
            k_shard, _ = _shard_topk_plan(gp.bucket, n_shards, top_k)
            fn = _make_group_shard_scorer(self.mesh, gp.est_id, k_shard, self.k)
            v, i, js = fn(*t_args, *_cand_args(gp), gp.live)
            vs.append(v)
            gis.append(_globalize_rows(
                i, gi_dev, k_shard=k_shard,
                shard_rows=gp.bucket // n_shards,
            ))
            jss.append(js)
        flat_v = _concat1(vs)
        flat_gi = _concat1(gis)
        flat_js = _concat1(jss)
        width = int(flat_v.shape[1])
        # Merge on the same pow-2 k-ladder as the shard scorers; the
        # exact result count is sliced off host-side at collect.
        k_merge = min(_next_pow2(top_k), width)
        vals, gidx, jsz = _merge_topk_device(
            flat_v, flat_gi, flat_js, k_final=k_merge
        )
        return _PendingTopk(vals, gidx, jsz, Q, k_live=min(top_k, width))

    def topk(self, plan, trains, top_k):
        return self.topk_dispatch(plan, trains, top_k).collect()

    # -- two-phase retrieval ------------------------------------------------

    def prefilter_dispatch(self, plan, trains, *, q_bucket: int | None = None):
        """Phase 1 on the mesh: every group's join-size prefilter runs
        shard-locally (candidate rows sharded over 'data', trains
        replicated) — the cheap pass scales with the mesh exactly like
        the scorers do.  Returns the shard-padded groups' join sizes;
        pass ``multiple=mesh.shape['data']`` to ``build_shortlists`` so
        phase-2 shortlist buckets stay shardable."""
        maybe_fault("prefilter_dispatch", "distributed")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        _, groups, _ = self._groups(plan)
        fn = _make_join_size_shard_scorer(self.mesh)
        blocks = [
            (gp, fn(trains["keys"], trains["mask"],
                    gp.arrays["keys"], gp.arrays["mask"]))
            for gp in groups
        ]
        return _PendingJoinSizes(blocks, Q)

    def shortlist_topk_dispatch(
        self, plan, trains, shortlists, top_k: int,
        *, q_bucket: int | None = None,
    ):
        """Phase 2 on the mesh: gather each non-empty shortlist into a
        compact (Q, s_bucket, cap) batch, score it sharded over the
        shortlist axis, and merge the per-shard/per-group winners on
        device (the same single ``lax.top_k`` discipline as the dense
        path).  No oversampling: every scored candidate already passed
        ``min_join``, so ``top_k`` winners are exact — the 4x dense-path
        oversample against post-hoc filtering starvation is gone."""
        maybe_fault("shortlist_dispatch", "distributed")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        qb = q_bucket or Q
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        n_shards = self.mesh.shape["data"]
        vs, gis, jss = [], [], []
        for sl in shortlists:
            if sl is None:
                continue
            rows = jnp.asarray(_pad_rows_q(sl.rows, qb))
            cands = _gather_shortlist(*_cand_args(sl.group), rows)
            gi = jnp.asarray(_pad_rows_q(sl.gidx, qb))
            live = jnp.asarray(
                _pad_rows_q(sl.gidx < plan.n_candidates, qb)
            )
            k_shard, _ = _shard_topk_plan(sl.s_bucket, n_shards, top_k)
            fn = _make_shortlist_shard_scorer(
                self.mesh, sl.group.est_id, k_shard, self.k
            )
            v, g, j = fn(*t_args, *cands, gi, live)
            vs.append(v)
            gis.append(g)
            jss.append(j)
        if not vs:
            return _PendingTopk(None, None, None, Q)
        flat_v = _concat1(vs)
        flat_gi = _concat1(gis)
        flat_js = _concat1(jss)
        width = int(flat_v.shape[1])
        k_merge = min(_next_pow2(top_k), width)
        vals, gidx, jsz = _merge_topk_device(
            flat_v, flat_gi, flat_js, k_final=k_merge
        )
        return _PendingTopk(vals, gidx, jsz, Q, k_live=min(top_k, width))

    def fused_topk_dispatch(
        self, plan, trains, spec, min_join, top_k: int,
        *, q_bucket: int | None = None,
    ):
        """Fused two-phase on the mesh: prefilter, shortlist
        compaction, gather, score, and per-shard top-k all run
        shard-locally inside one collective per group, followed by the
        usual on-device cross-group merge — no shard materializes a
        global group array and no host sync happens before the
        handle's ``collect``.  ``spec.s_buckets`` must be divisible by
        the shard count (build it with ``multiple=n_shards``); each
        shard compacts ``s_bucket // n_shards`` lanes, so the overflow
        fence is per (group, shard).  Overflow at collect falls back to
        the two-step mesh path via the handle's ``js_blocks()``."""
        maybe_fault("fused_dispatch", "distributed")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        n_shards, groups, _ = self._groups(plan)
        mj = _stage_replicated(
            self.mesh,
            min_join if isinstance(min_join, jax.Array)
            else stage_min_join(min_join),
        )
        sentinel = plan.sentinel_dev
        if sentinel is None:
            sentinel = jnp.asarray(np.int32(plan.n_candidates))
        sentinel = _stage_replicated(self.mesh, sentinel)
        vs, gis, jss, fence = [], [], [], []
        for gp, s_bucket in zip(groups, spec.s_buckets):
            s_shard = max(min(int(s_bucket), gp.bucket) // n_shards, 1)
            k_shard = max(min(_next_pow2(top_k), s_shard), 1)
            fn = _make_fused_shard_scorer(
                self.mesh, gp.est_id, s_shard, k_shard, self.k
            )
            v, g, j, counts, js = fn(
                *t_args, *_cand_args(gp), gp.index_dev, gp.live,
                mj, sentinel,
            )
            vs.append(v)
            gis.append(g)
            jss.append(j)
            fence.append((gp, s_shard, counts, js))
        if not vs:
            return _PendingFusedTopk(None, None, None, Q, 0, fence)
        flat_v = _concat1(vs)
        flat_gi = _concat1(gis)
        flat_js = _concat1(jss)
        width = int(flat_v.shape[1])
        k_merge = min(_next_pow2(top_k), width)
        vals, gidx, jsz = _merge_topk_device(
            flat_v, flat_gi, flat_js, k_final=k_merge
        )
        return _PendingFusedTopk(
            vals, gidx, jsz, Q, min(top_k, width), fence
        )

    def tiered_topk_dispatch(
        self, plan, trains, tspec, spec, min_join, min_containment,
        top_k: int, *, q_bucket: int | None = None,
    ):
        """Tiered retrieval on the mesh: the phase-0 containment gate
        and the whole fused pipeline run shard-locally inside one
        collective per group (corpus partitioned across shards, the
        signature tier sharded identically to the full store), followed
        by the usual on-device winner merge.  Build ``tspec`` and
        ``spec`` with ``multiple=n_shards`` so the staged widths divide
        the shard count; both fences are per (group, shard).  Overflow
        at collect re-runs the window through
        :meth:`fused_topk_dispatch` (ungated)."""
        maybe_fault("tiered_dispatch", "distributed")
        trains = _as_stacked_trains(trains)
        Q = int(trains["keys"].shape[0])
        if q_bucket is not None:
            trains = pad_trains_q(trains, q_bucket)
        t_args = (trains["keys"], trains["vals_f"],
                  trains["vals_u"], trains["mask"])
        n_shards, groups, _ = self._groups(plan)
        mj = _stage_replicated(
            self.mesh,
            min_join if isinstance(min_join, jax.Array)
            else stage_min_join(min_join),
        )
        mc = _stage_replicated(
            self.mesh,
            min_containment
            if isinstance(min_containment, jax.Array)
            else stage_min_containment(min_containment),
        )
        sentinel = plan.sentinel_dev
        if sentinel is None:
            sentinel = jnp.asarray(np.int32(plan.n_candidates))
        sentinel = _stage_replicated(self.mesh, sentinel)
        vs, gis, jss, fence = [], [], [], []
        for gp, s_surv, s_bucket in zip(
            groups, tspec.s_survivors, spec.s_buckets
        ):
            if gp.sig is None:
                raise ValueError(
                    "tiered dispatch on a plan without a signature tier"
                )
            rows_local = max(gp.bucket // n_shards, 1)
            s_surv_shard = max(min(int(s_surv), gp.bucket) // n_shards, 1)
            s_surv_shard = min(s_surv_shard, rows_local)
            s_shard = max(min(int(s_bucket), gp.bucket) // n_shards, 1)
            s_shard = min(s_shard, s_surv_shard)
            k_shard = max(min(_next_pow2(top_k), s_shard), 1)
            fn = _make_tiered_shard_scorer(
                self.mesh, gp.est_id, s_surv_shard, s_shard, k_shard,
                self.k,
            )
            v, g, j, c0, c1 = fn(
                *t_args, *_cand_args(gp), gp.sig, gp.index_dev, gp.live,
                mj, mc, sentinel,
            )
            vs.append(v)
            gis.append(g)
            jss.append(j)
            fence.append((gp, s_surv_shard, s_shard, c0, c1))
        if not vs:
            return _PendingTieredTopk(None, None, None, Q, 0, fence)
        flat_v = _concat1(vs)
        flat_gi = _concat1(gis)
        flat_js = _concat1(jss)
        width = int(flat_v.shape[1])
        k_merge = min(_next_pow2(top_k), width)
        vals, gidx, jsz = _merge_topk_device(
            flat_v, flat_gi, flat_js, k_final=k_merge
        )
        return _PendingTieredTopk(
            vals, gidx, jsz, Q, min(top_k, width), fence
        )


def get_executor(
    spec: str | Executor | None, mesh: Mesh | None = None, k: int = 3
) -> Executor:
    """Resolve an executor: an instance passes through; None picks the
    distributed backend when a mesh is given, else the local one."""
    if isinstance(spec, Executor):
        return spec
    if spec is None:
        spec = "distributed" if mesh is not None else "partitioned"
    if spec == "partitioned":
        return PartitionedLocalExecutor(k=k)
    if spec == "batched":
        return BatchedExecutor(k=k)
    if spec == "distributed":
        if mesh is None:
            raise ValueError("distributed executor requires a mesh")
        return GroupMajorDistributedExecutor(mesh, k=k)
    raise ValueError(f"unknown executor {spec!r}")


# ---------------------------------------------------------------------------
# Back-compat functional entry points (pre-planner API).
# ---------------------------------------------------------------------------


def score_batch_partitioned(
    train: dict, cands: dict, k: int = 3,
    groups: list[tuple] | None = None,
):
    """Estimator-partitioned batch scoring of raw stacked arrays.

    Plans the corpus ad hoc (``groups`` — legacy ``(est_id, indices)``
    entries — overrides the partition when given) and runs the local
    partitioned executor.  Matches :func:`score_batch` output exactly.
    Prefer ``SketchIndex.query`` / ``query_many``, which reuse the
    incrementally-maintained plan instead of re-packing per call.
    Returns (mi_scores (C,), join_sizes (C,)).
    """
    C = int(np.asarray(cands["est_id"]).shape[0])
    y_disc = bool(train.get("y_discrete", False))
    if groups is None:
        plan = make_plan(cands, y_discrete=y_disc)
    else:
        plan = QueryPlan(y_disc, C, [
            pack_group(cands, int(entry[0]), np.asarray(entry[1]), C)
            for entry in groups
        ])
    mi, js = PartitionedLocalExecutor(k=k).execute(plan, train)
    return jnp.asarray(mi[0]), jnp.asarray(js[0])


def distributed_topk(train: dict, cands: dict, mesh: Mesh, top_k: int, k: int = 3):
    """Mesh-sharded discovery query with per-shard top-k merge.

    Group-major: candidates are partitioned by estimator *before*
    ``shard_map`` (each shard runs homogeneous programs), sharded over
    the 'data' mesh axis, and merged on the host from O(groups · shards
    · k_shard) scalars.  Returns (values, global indices, join sizes) of
    the global top ``min(top_k, C)``, best first.

    Ad-hoc entry point: the plan (per-group gather + pad) is rebuilt on
    every call.  Repeated callers should hold a
    :class:`GroupMajorDistributedExecutor` and the index's cached
    ``plan()`` instead — that is what ``SketchIndex.query(mesh=...)``
    does.
    """
    plan = make_plan(cands, y_discrete=bool(train.get("y_discrete", False)),
                     pad_multiple=mesh.shape["data"])
    ex = GroupMajorDistributedExecutor(mesh, k=k)
    v, gi, js = ex.topk(plan, train, top_k)[0]
    return v, gi, js
