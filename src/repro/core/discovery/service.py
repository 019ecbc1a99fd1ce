"""Admission-controlled discovery service: the serving front-end.

Everything below this module answers *one* well-shaped batch fast: the
planner fixes a layout per (corpus version, target dtype), the executors
run one compiled program per estimator group, the index keeps the corpus
device-resident under live ingest.  What none of them owns is the gap
between "a list of user queries" and those well-shaped batches — a real
queue is *mixed* (discrete and continuous targets interleaved), *bursty*
(3 queries, then 40, then 9), and *concurrent with ingest*.  Fed raw to
``query_many`` such a queue either raises (mixed dtypes) or compiles a
fresh leading-Q program per observed batch size.

:class:`DiscoveryService` is that missing layer — the online-service
front-end that Correlation Sketches (Santos et al., 2021) and Table
Enrichment (Dong & Oyamada, 2022) frame discovery as.  ``submit`` runs
admission control over an arbitrary queue:

  1. **Split** — queries are partitioned by target dtype and therefore
     by *estimator signature* (the (est_id, group-bucket) tuple that
     determines compiled-program identity; see
     :func:`~repro.core.discovery.planner.plan_signature`).  Every
     admitted batch is homogeneous, so the mixed-queue crash mode is
     gone by construction.
  2. **Chunk + Q-bucket** — each signature's queries are chunked to the
     ``max_q_bucket`` cap and padded up the pow-two Q-ladder
     (:func:`~repro.core.discovery.planner.bucket_queries`).  Compile
     count under *any* traffic pattern is bounded by |signatures| x
     |Q-buckets| x |group buckets| — asserted by the admission tests via
     :func:`~repro.core.discovery.executors.compile_count`.
  3. **Schedule** — every admitted bucket is dispatched before any
     result is transferred (the executors' ``dispatch``/``collect``
     split), so bucket programs overlap on device exactly like group
     programs do within one bucket.  On a mesh the cross-group top-k
     merge also stays on device (one ``lax.top_k`` per bucket for all
     its queries), so collection moves O(Q · top_k) scalars.

Results are scattered back to arrival order and are bit-identical to
looping :meth:`SketchIndex.query` over the same queue — padded query
lanes repeat a live lane and are sliced off on device; vmap lanes are
data-parallel.  ``add``/``add_table`` delegate to the index's amortized
O(1) ingest (buffer-donated in-place flushes where the backend supports
it), so a queue interleaved with ingest serves from a corpus that is
current as of each ``submit``.

``submit`` threads ``min_join`` into planning rather than ranking:
each admitted bucket runs two-phase retrieval (join-size prefilter ->
shortlist gather-and-score — see ``executors.py``), so the expensive
kNN-MI work scales with the *joinable* fraction of the corpus, not the
corpus.  By default both phases run as one *fused* device pipeline:
compaction widths come from the index's adaptive
:class:`~repro.core.discovery.planner.ShortlistHints` and the only
host sync a bucket pays is its final result collect (counted in
``host_syncs``; ``fused_windows`` counts buckets the fused path
delivered).  A compaction-width overflow falls back to the
host-boundary path for that bucket — bit-identically, reusing the
device join sizes already computed.  ``stats()`` reports the candidate
pairs the gate filtered out of estimator scoring, alongside the
shortlist-bucket ladder traffic.

**Fault isolation** (see ``resilience.py``): ``submit_safe`` wraps the
same pipeline in the resilience layer and returns ``(results,
outcomes)`` — one :class:`~repro.core.discovery.resilience.QueryOutcome`
per submitted query.  Sketches failing admission validation are
*quarantined* (structured error, no executor ever sees them) while the
rest of the queue serves bit-identically; a bucket whose dispatch or
collect raises is *retried* under the service's
:class:`~repro.core.discovery.resilience.RetryPolicy` and then degrades
down the executor ladder (distributed mesh -> single-device batched ->
reference per-query loop), every rung bit-identical to the dense path,
with every other bucket's results unaffected; non-finite MI lanes are
*fenced* — demoted to the materialized reference estimator instead of
silently ranked.  Stats discipline in both surfaces: arrival counters
(``submits``/``submitted``/``signatures``/``split_batches``/
``quarantined``) commit at admission, but delivery counters
(``batches``/``padded_lanes``/``prefiltered``/``cands_*``/buckets) are
*staged per bucket* and committed only after that bucket's collect —
a raise mid-submit can no longer leave ``stats()`` claiming work that
never delivered.  Failures are counted explicitly
(``failed_buckets``/``retries``/``fallbacks``/``lost_queries``).

**Tracing**: the layer boundaries that the synchronous and scheduler
surfaces share are ``jax.profiler.TraceAnnotation`` spans, so under a
profiler session they land on the device trace's clock:
``discovery.submit`` (a whole synchronous submit),
``discovery.dispatch`` (one per bucket: plan-cache lookup, train
staging, the enqueue; the host-boundary path adds one for its phase-2
dispatch), ``discovery.collect`` (one per bucket: the host
sync and the overflow fence) and ``discovery.rank`` (one per bucket:
fencing, ranking, the stat commit).  Each carries ``window`` (the
``submits`` count that admitted it) and, below submit, ``bucket`` (the
job's index in its window).  With no profiler session a span costs a
microsecond or so.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

import numpy as np

from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.core.discovery import executors as _ex
from repro.core.discovery import resilience
from repro.core.discovery.index import SketchIndex, topk_oversample
from repro.core.discovery.planner import (
    MAX_Q_BUCKET,
    PlanCache,
    ShortlistOverflow,
    SurvivorOverflow,
    bucket_queries,
    build_shortlists,
    coalesce_queries,
    fused_shortlist_spec,
    plan_signature,
    shortlist_signature,
    tier_spec,
)
from repro.core.discovery.resilience import QueryOutcome, RetryPolicy
from repro.core.sketch import Sketch

__all__ = ["AdmissionStats", "DiscoveryService"]


@dataclass
class AdmissionStats:
    """What admission control did to the traffic so far.

    Arrival counters commit when a submit is admitted; delivery
    counters (``batches`` onwards) only after the owning bucket's
    results were actually collected, so the ledger always matches the
    results callers received — even across mid-submit failures.
    """

    submitted: int = 0       # queries accepted across all submit() calls
    submits: int = 0         # submit() calls
    quarantined: int = 0     # queries rejected at admission validation
    batches: int = 0         # (signature, Q-bucket) buckets that delivered
    split_batches: int = 0   # chunks forced by the max_q_bucket cap
    padded_lanes: int = 0    # dead query lanes paid to ride the ladder
    prefiltered: int = 0     # queries served via two-phase retrieval
    cands_considered: int = 0   # (query, candidate) pairs seen by phase 1
    cands_shortlisted: int = 0  # pairs that reached phase-2 scoring
    fused_windows: int = 0   # buckets delivered by the fused device path
    gated_windows: int = 0   # buckets delivered by the phase-0-gated path
    cands_considered_t0: int = 0  # (query, candidate) pairs swept by the
    #                               phase-0 signature gate
    cands_gated_t0: int = 0  # pairs the gate passed into the exact phases
    host_syncs: int = 0      # device->host sync points paid by delivered
    #                          buckets (fused/dense/tiered: 1;
    #                          host-boundary two-phase: 2; fused overflow
    #                          fallback: 3; tiered overflow adds 1 on top
    #                          of whatever the ungated re-run pays)
    failed_buckets: int = 0  # buckets whose primary executor pass raised
    retries: int = 0         # same-rung re-attempts across all buckets
    fallbacks: int = 0       # executor-ladder descents across all buckets
    nonfinite_lanes: int = 0  # score lanes fenced to the reference path
    lost_queries: int = 0    # queries whose bucket exhausted the ladder
    signatures: set = field(default_factory=set)
    q_buckets: set = field(default_factory=set)
    s_buckets: set = field(default_factory=set)

    def as_dict(self) -> dict:
        return {
            "submitted": self.submitted,
            "submits": self.submits,
            "quarantined": self.quarantined,
            "batches": self.batches,
            "split_batches": self.split_batches,
            "padded_lanes": self.padded_lanes,
            "prefiltered": self.prefiltered,
            "cands_considered": self.cands_considered,
            "cands_shortlisted": self.cands_shortlisted,
            "fused_windows": self.fused_windows,
            "gated_windows": self.gated_windows,
            "cands_considered_t0": self.cands_considered_t0,
            "cands_gated_t0": self.cands_gated_t0,
            # Phase-0 selectivity: the fraction of swept (query,
            # candidate) pairs the containment gate let through to the
            # exact prefilter/compact/gather/score phases.
            "t0_selectivity": (
                self.cands_gated_t0 / self.cands_considered_t0
                if self.cands_considered_t0 else None
            ),
            "host_syncs": self.host_syncs,
            # What the joinability gate saved: estimator work the dense
            # path would have paid for candidates min_join discards.
            "cands_filtered_out":
                self.cands_considered - self.cands_shortlisted,
            "failed_buckets": self.failed_buckets,
            "retries": self.retries,
            "fallbacks": self.fallbacks,
            "nonfinite_lanes": self.nonfinite_lanes,
            "lost_queries": self.lost_queries,
            "signatures": len(self.signatures),
            "q_buckets": sorted(self.q_buckets),
            "s_buckets": sorted(self.s_buckets),
        }


class _BucketJob:
    """One admitted (signature, Q-bucket) bucket moving through the
    dispatch -> collect pipeline, carrying its staged stat deltas (only
    committed after a successful collect) and its recovery bookkeeping.
    """

    __slots__ = (
        "chunk", "y_disc", "q_bucket", "sp", "sketches", "trains",
        "pend1", "handle", "rung", "retries", "fallbacks", "error",
        "staged",
    )

    def __init__(self, chunk: list[int], y_disc: bool):
        self.chunk = chunk
        self.y_disc = y_disc
        self.q_bucket = 0
        self.sp = None
        self.sketches = None
        self.trains = None
        self.pend1 = None
        self.handle = None
        self.rung = None
        self.retries = 0
        self.fallbacks = 0
        self.error = None
        self.staged: dict = {}


class _Window:
    """One dispatched-but-uncollected admission window.

    Everything ranking needs is captured at dispatch time — the corpus
    size/version the programs were planned against, the serving options
    — so :meth:`DiscoveryService._window_collect` can run arbitrarily
    later (after other windows dispatched, after an ingest landed) and
    still produce results bit-identical to a synchronous submit.
    ``leases`` pin the window's query plans against donated ingest
    flushes for exactly that span.
    """

    __slots__ = (
        "queries", "jobs", "results", "outcomes", "C", "version",
        "top_k", "min_join", "min_containment", "rank", "isolate",
        "use_pref", "n_shards", "leases", "wid",
    )

    def __init__(self, queries: list, isolate: bool):
        self.queries = queries
        self.jobs: list[_BucketJob] = []
        self.results: list = [None] * len(queries)
        self.outcomes: list = [None] * len(queries)
        self.C = 0
        self.version = 0
        self.top_k = 0
        self.min_join = 0
        self.min_containment = 0.0
        self.rank = "mi"
        self.isolate = isolate
        self.use_pref = False
        self.n_shards = 1
        self.leases: list = []
        self.wid = 0  # admission.submits at dispatch: the trace spans' id

    def release(self) -> None:
        """Release the window's plan leases (idempotent)."""
        leases, self.leases = self.leases, []
        for lease in leases:
            lease.release()


class DiscoveryService:
    """Serving surface: live ingest + concurrent mixed queries.

    ``add``/``add_table`` ingest candidate columns; ``submit`` answers a
    queue of train sketches (``submit_safe`` does the same behind
    per-query quarantine, a retry/fallback executor ladder, and numeric
    fences — see ``resilience.py``).  One service owns one
    :class:`SketchIndex` (pass ``index=`` to wrap an existing corpus)
    and, optionally, one mesh — with ``mesh=`` every admitted bucket
    runs the group-major distributed executor and returns ranked
    results from the on-device top-k merge.
    """

    def __init__(
        self,
        index: SketchIndex | None = None,
        *,
        n: int = 256,
        method: str = "tupsk",
        agg: str = "first",
        k: int = 3,
        mesh: Mesh | None = None,
        max_q_bucket: int = MAX_Q_BUCKET,
        plan_cache_size: int = 32,
        retry_policy: RetryPolicy | None = None,
        sig_width: int = 16,
    ):
        self.index = index if index is not None else SketchIndex(
            n=n, method=method, agg=agg, sig_width=sig_width
        )
        self.k = k
        self.mesh = mesh
        max_q_bucket = int(max_q_bucket)
        # The chunker cuts queues to max_q_bucket and the ladder pads up
        # to the next power of two <= the cap, so a non-pow-2 cap would
        # make a full chunk unbucketable.
        if max_q_bucket < 1 or max_q_bucket & (max_q_bucket - 1):
            raise ValueError(
                f"max_q_bucket must be a power of two >= 1 (the Q-axis "
                f"bucket ladder is pow-2), got {max_q_bucket}"
            )
        self.max_q_bucket = max_q_bucket
        self.plan_cache = PlanCache(plan_cache_size)
        self.admission = AdmissionStats()
        self.retry_policy = retry_policy if retry_policy is not None \
            else RetryPolicy()
        self._batched = _ex.BatchedExecutor(k=k)
        # Share the index's per-(mesh, k) distributed executor so the
        # service and direct index.query(mesh=...) callers hit one
        # shard-pad cache (one set of padded device arrays per plan).
        self._dist = (
            self.index._distributed_executor(mesh, k)
            if mesh is not None else None
        )
        # Always-on micro-batch scheduler, attached lazily on the first
        # submit_async (see scheduler.py) so synchronous-only users pay
        # no background thread.  The lock makes concurrent first-time
        # attachment mint exactly one scheduler (one loop thread, one
        # telemetry stream) instead of one per racing caller.
        self._scheduler = None
        self._scheduler_lock = threading.Lock()

    # ------------------------------------------------------------------
    # Ingest (delegates to the index; flushes ride the next submit)
    # ------------------------------------------------------------------

    def add(self, *args, **kwargs) -> None:
        """Ingest one candidate column (see :meth:`SketchIndex.add`)."""
        self.index.add(*args, **kwargs)

    def add_table(self, table, key_column: str) -> None:
        """Ingest every (key, value) pair of a table (atomic — see
        :meth:`SketchIndex.add_table`)."""
        self.index.add_table(table, key_column)

    def __len__(self) -> int:
        return len(self.index)

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------

    def submit(
        self,
        queries: list[Sketch],
        *,
        top_k: int = 10,
        min_join: int = 8,
        prefilter: bool | None = None,
        fused: bool | None = None,
        min_containment: float = 0.0,
        rank: str = "mi",
    ) -> list[list]:
        """Answer a mixed, arbitrarily-sized queue of discovery queries.

        Returns one ranked result list per query, in arrival order —
        each entry bit-identical to ``index.query(sk, top_k=...,
        min_join=..., mesh=..., k=self.k)`` on the same corpus (the
        estimator neighbor count must match for parity, which sharing
        ``self.k`` guarantees).  Internally the
        queue is admission-controlled (split per estimator signature,
        chunked to ``max_q_bucket``, Q padded up the pow-two ladder) and
        every admitted bucket is dispatched before the first transfer.

        ``min_join`` is threaded into *planning*, not applied post-hoc:
        with ``prefilter`` on (the default whenever ``min_join`` > 0)
        each bucket runs two-phase retrieval — a cheap join-size pass
        over every candidate, then estimator scoring of only the
        shortlist that can pass ``min_join``.  ``fused`` (default on
        when the prefilter engages) runs both phases as one device
        pipeline per bucket: no host sync between them, one collect at
        the end (``fused=False`` forces the host-boundary reference
        path, whose phase-1 programs for all buckets are dispatched
        before any phase-1 transfer, and likewise for phase 2).
        ``stats()`` reports how many candidate pairs the gate filtered
        out of estimator scoring, plus ``fused_windows``/``host_syncs``.

        ``min_containment`` > 0 engages the phase-0 containment tier in
        front of the fused pipeline: one signature sweep over the whole
        corpus estimates each candidate's containment of the query keys
        (est_join_size / train_size) and only candidates at or above
        the threshold reach the exact phases — the window still pays
        exactly one host sync.  At 0 (the default) every bucket routes
        through the untouched fused path, bit-identically to the
        ungated contract.  ``rank="hybrid"`` re-weights the final
        ranking by *exact* containment (mi x join_size / train_size —
        the join sizes the pipeline already returns), favoring
        candidates that both inform the target and actually join it.

        This is the legacy all-or-nothing surface: the first bucket
        failure is counted (``failed_buckets``) and re-raised, with the
        failed submit's delivery counters left uncommitted.  Use
        :meth:`submit_safe` for per-query quarantine, the executor
        fallback ladder, and numeric fencing.
        """
        results, _ = self._submit(
            list(queries), top_k=top_k, min_join=min_join,
            prefilter=prefilter, fused=fused, isolate=False,
            min_containment=min_containment, rank=rank,
        )
        return results

    def submit_safe(
        self,
        queries: list[Sketch],
        *,
        top_k: int = 10,
        min_join: int = 8,
        prefilter: bool | None = None,
        fused: bool | None = None,
        min_containment: float = 0.0,
        rank: str = "mi",
    ) -> tuple[list, list]:
        """Fault-isolated :meth:`submit`: ``(results, outcomes)``.

        Every query gets a :class:`QueryOutcome`.  Invalid sketches are
        quarantined at admission (``status="quarantined"``, ``results``
        entry None) and never reach an executor; the remaining queue is
        served bit-identically to a clean :meth:`submit`.  A bucket
        whose dispatch or collect raises retries under
        ``self.retry_policy`` and then descends the executor ladder
        (distributed -> batched -> reference per-query loop, each rung
        bit-identical); only if the whole ladder is exhausted do that
        bucket's queries come back ``status="failed"``.  Non-finite MI
        lanes are fenced to the materialized reference estimator and
        counted per query (``nonfinite_lanes``) instead of being
        ranked.

        The phase-0 containment gate (``min_containment`` > 0) runs on
        the primary rung only: a bucket that descends the recovery
        ladder re-executes *ungated* (the gate is a perf tier, and a
        failing one must not stand between a query and its result), so
        fallback rungs deliver the ungated ranking.
        """
        return self._submit(
            list(queries), top_k=top_k, min_join=min_join,
            prefilter=prefilter, fused=fused, isolate=True,
            min_containment=min_containment, rank=rank,
        )

    # ------------------------------------------------------------------
    # Async serving tier (micro-batch scheduler)
    # ------------------------------------------------------------------

    def scheduler(self, **kwargs):
        """The service's micro-batch scheduler, creating (and starting)
        it on first use.  ``kwargs`` configure the first creation
        (``window_ms``, ``max_depth``, ``pipeline_depth``, ``start``);
        passing them after the scheduler exists is an error — the tier
        is always-on, not per-call."""
        if self._scheduler is None:
            from repro.core.discovery.scheduler import MicroBatchScheduler
            with self._scheduler_lock:
                if self._scheduler is None:
                    self._scheduler = MicroBatchScheduler(self, **kwargs)
                    return self._scheduler
        if kwargs:
            raise ValueError(
                "scheduler already attached; its configuration is fixed "
                f"at creation (got {sorted(kwargs)})"
            )
        return self._scheduler

    def submit_async(
        self,
        queries,
        *,
        priority: str = "interactive",
        top_k: int = 10,
        min_join: int = 8,
        prefilter: bool | None = None,
        fused: bool | None = None,
        min_containment: float = 0.0,
        rank: str = "mi",
    ):
        """Future-style :meth:`submit_safe` through the always-on
        micro-batch tier: returns one
        :class:`~repro.core.discovery.scheduler.QueryHandle` per query
        (a single handle for a single ``Sketch``), resolving to the
        ranked results and a
        :class:`~repro.core.discovery.resilience.QueryOutcome`.

        Queries from *different callers* arriving within the
        scheduler's coalescing window are packed into shared pow-2
        Q-buckets — same compiled programs, bit-identical results, a
        fraction of the dispatch round-trips — and the PR-5 resilience
        ladder applies per coalesced bucket, so no caller ever sees
        another caller's failure.  ``priority`` is ``"interactive"``
        (dispatched first) or ``"batch"``; each class has its own
        bounded queue, and a full queue raises
        :class:`~repro.core.discovery.scheduler.SchedulerBackpressure`
        instead of stalling the caller.
        """
        return self.scheduler().submit_async(
            queries, priority=priority, top_k=top_k, min_join=min_join,
            prefilter=prefilter, fused=fused,
            min_containment=min_containment, rank=rank,
        )

    def close(self) -> None:
        """Drain and stop the attached scheduler, if any (idempotent;
        synchronous surfaces keep working after close)."""
        if self._scheduler is not None:
            self._scheduler.close()
            self._scheduler = None

    def _submit(
        self, queries: list[Sketch], *, top_k: int, min_join: int,
        prefilter: bool | None, isolate: bool,
        fused: bool | None = None,
        min_containment: float = 0.0,
        rank: str = "mi",
    ) -> tuple[list, list]:
        # The window id _window_dispatch is about to give this submit.
        with TraceAnnotation("discovery.submit",
                             window=self.admission.submits + 1):
            window = self._window_dispatch(
                queries, top_k=top_k, min_join=min_join,
                prefilter=prefilter, isolate=isolate, fused=fused,
                min_containment=min_containment, rank=rank,
            )
            if window is None:
                return [], []
            return self._window_collect(window)

    def _window_dispatch(
        self, queries: list[Sketch], *, top_k: int, min_join: int,
        prefilter: bool | None, isolate: bool,
        fused: bool | None = None,
        min_containment: float = 0.0,
        rank: str = "mi",
        priorities: list[int] | None = None,
        coalesced: bool = False,
    ) -> "_Window | None":
        """Admission + dispatch half of a submit: validate, split by
        estimator signature (:func:`coalesce_queries`), Q-bucket, and
        enqueue every bucket's device work — *no host sync happens
        here*.  Returns an in-flight :class:`_Window` (None for an
        empty queue) whose results materialize at
        :meth:`_window_collect`.

        The window captures the corpus size/version its programs were
        planned against and holds a
        :class:`~repro.core.discovery.planner.PlanLease` per plan, so
        the micro-batch scheduler can overlap the next window's staging
        — and even an ingest — with this window's device scoring and
        still collect bit-identical results.  ``priorities`` (one rank
        per query, lower = sooner) orders coalesced buckets for the
        scheduler; ``coalesced`` marks the window's plan-cache traffic
        as cross-caller in the ledger.
        """
        if rank not in ("mi", "hybrid"):
            raise ValueError(
                f"rank must be 'mi' or 'hybrid', got {rank!r}"
            )
        if not queries:
            return None
        st = self.admission
        st.submits += 1
        win = _Window(list(queries), isolate)
        win.wid = st.submits
        results, outcomes = win.results, win.outcomes

        # 0. admission validation: quarantine sketches the pipeline
        # cannot serve (isolate mode only — the legacy surface keeps
        # its raise-from-the-depths behavior for invalid inputs).
        admitted: list[int] = []
        for qi, sk in enumerate(queries):
            if isolate:
                bad = resilience.validate_query(sk, self.index)
                if bad is not None:
                    code, detail = bad
                    outcomes[qi] = QueryOutcome(
                        qi, "quarantined", error=code, detail=detail
                    )
                    st.quarantined += 1
                    continue
            admitted.append(qi)
        st.submitted += len(admitted)
        if not admitted:
            return win

        C = win.C = len(self.index)
        version = win.version = self.index._version
        use_pref = self.index._use_prefilter(prefilter, min_join)
        use_fused = use_pref and (True if fused is None else bool(fused))
        use_gate = use_fused and float(min_containment) > 0.0
        if float(min_containment) > 0.0 and not use_fused:
            raise ValueError(
                "min_containment > 0 requires the fused two-phase "
                "pipeline (prefilter off or fused=False disables the "
                "path the phase-0 gate fronts)"
            )
        n_shards = self.mesh.shape["data"] if self.mesh is not None else 1
        primary_rung = "distributed" if self._dist is not None else "batched"
        win.top_k, win.min_join = top_k, min_join
        win.min_containment, win.rank = min_containment, rank
        win.use_pref, win.n_shards = use_pref, n_shards

        # 1. split the queue per target dtype -> estimator signature and
        # coalesce into shared pow-2 Q-buckets (signature is constant
        # per dtype within one window: nothing can flush mid-dispatch,
        # so compute it once per dtype, not per query).
        entries: list[tuple] = []
        try:
            plans: dict[bool, object] = {}
            sigs: dict[bool, tuple] = {}
            for qi in admitted:
                y_disc = bool(queries[qi].value_is_discrete)
                if y_disc not in plans:
                    plans[y_disc] = self.index.plan(y_disc, k=self.k)
                    sigs[y_disc] = plan_signature(plans[y_disc])
                entries.append((
                    qi, sigs[y_disc],
                    0 if priorities is None else int(priorities[qi]),
                ))
        except Exception as e:  # noqa: BLE001 — isolate into outcomes
            if not isolate:
                raise
            # Planning failed for the whole queue (e.g. empty index):
            # there is no per-bucket ladder to descend yet.
            for qi in admitted:
                outcomes[qi] = QueryOutcome(
                    qi, "failed", error="plan_failed", detail=repr(e)
                )
            st.lost_queries += len(admitted)
            return win

        # Pin every plan the window dispatched against: a donated
        # ingest flush between this dispatch and the window's collect
        # would otherwise repack the very device buffers the in-flight
        # programs read.  Released at collect (see _Window.release).
        for plan in plans.values():
            try:
                win.leases.append(plan.retain())
            except ValueError:
                pass  # ad-hoc plan without pins: nothing to lease

        buckets = coalesce_queries(entries, self.max_q_bucket)
        per_sig: dict[tuple, int] = {}
        for b in buckets:
            per_sig[b.signature] = per_sig.get(b.signature, 0) + 1
        for sig, n_chunks in per_sig.items():
            st.signatures.add(sig)
            st.split_batches += n_chunks - 1
        jobs = win.jobs = [
            _BucketJob(list(b.chunk), b.signature[0]) for b in buckets
        ]

        # 2. dispatch every bucket before any collect (dispatch-before-
        # transfer across buckets).  With the prefilter on, "dispatch"
        # here is phase 1 — the join-size pass; scoring work is not
        # enqueued until its shortlist exists.  Stat deltas are *staged*
        # on the job and committed only after its collect succeeds.
        try:
            for bi, job in enumerate(jobs):
                job.rung = primary_rung
                with TraceAnnotation("discovery.dispatch", window=win.wid,
                                     bucket=bi):
                    try:
                        job.q_bucket = bucket_queries(
                            len(job.chunk), self.max_q_bucket
                        )
                        job.sp = self.plan_cache.lookup(
                            version, job.y_disc, job.q_bucket,
                            lambda y=job.y_disc: self.index.plan(
                                y, k=self.k),
                            coalesced=coalesced,
                        )
                        job.staged = {
                            "batches": 1,
                            "padded_lanes": job.q_bucket - len(job.chunk),
                            "q_buckets": {job.q_bucket},
                            "host_syncs": 1,
                        }
                        job.sketches = [queries[i] for i in job.chunk]
                        job.trains = _ex.stack_trains_host(job.sketches)
                        if use_gate:
                            # Tiered: the phase-0 containment sweep plus the
                            # whole fused pipeline in one dispatch; the
                            # bucket's only host sync is still its collect.
                            job.handle = self._tiered_dispatch(
                                job, min_join, min_containment, top_k,
                                n_shards, C, version,
                            )
                        elif use_fused:
                            # Fused two-phase: the whole prefilter ->
                            # compact -> gather -> score pipeline is
                            # enqueued here; the bucket's only host sync is
                            # its collect.
                            job.handle = self._fused_dispatch(
                                job, min_join, top_k, n_shards, C, version
                            )
                        elif use_pref:
                            ex = self._dist if self._dist is not None \
                                else self._batched
                            job.pend1 = ex.prefilter_dispatch(
                                job.sp.plan, job.trains, q_bucket=job.q_bucket
                            )
                        elif self._dist is not None:
                            want = topk_oversample(top_k, C)
                            job.handle = self._dist.topk_dispatch(
                                job.sp.plan, job.trains, want,
                                q_bucket=job.q_bucket,
                            )
                        else:
                            job.handle = self._batched.dispatch(
                                job.sp.plan, job.trains, q_bucket=job.q_bucket
                            )
                    except Exception as e:  # noqa: BLE001 — bucket-isolated
                        job.error = e
                        if not isolate:
                            st.failed_buckets += 1
                            raise

            # 2b. host-boundary two-phase buckets only: collect join
            # sizes, build shortlists, and dispatch phase 2 for every
            # bucket before collecting any phase-2 result (bucket i+1's
            # prefilter overlaps bucket i's shortlist build on device).
            # Fused buckets were fully enqueued in step 2 and skip this
            # phase entirely.
            if use_pref and not use_fused:
                for bi, job in enumerate(jobs):
                    if job.error is not None:
                        continue
                    try:
                        with TraceAnnotation("discovery.dispatch",
                                             window=win.wid, bucket=bi):
                            job.handle = self._shortlist_phase(
                                job, min_join, top_k, n_shards, C, version
                            )
                    except Exception as e:  # noqa: BLE001
                        job.error = e
                        if not isolate:
                            st.failed_buckets += 1
                            raise
        except Exception:
            win.release()
            raise
        return win

    def _window_collect(self, win: "_Window") -> tuple[list, list]:
        """Collect half of a submit: sync each in-flight bucket's
        results, fence, rank, scatter to arrival order, and run the
        recovery ladder for failed buckets.  Ranks against the corpus
        size the window *dispatched* with, so results are bit-identical
        whether or not an ingest landed while the window was in flight.
        """
        st = self.admission
        queries = win.queries
        results, outcomes = win.results, win.outcomes
        C, version = win.C, win.version
        top_k, min_join, rank = win.top_k, win.min_join, win.rank
        n_shards, isolate = win.n_shards, win.isolate
        try:
            # 3. collect (first host sync of each handle's result set),
            # fence, rank, scatter to arrival order, and only then
            # commit the bucket's staged counters.
            for bi, job in enumerate(win.jobs):
                if job.error is not None:
                    continue
                span = {"window": win.wid, "bucket": bi}
                try:
                    with TraceAnnotation("discovery.collect", **span):
                        triples = self._collect_triples(
                            job, C, min_join, top_k, n_shards, version,
                            min_containment=win.min_containment,
                        )
                except Exception as e:  # noqa: BLE001
                    job.error = e
                    if not isolate:
                        st.failed_buckets += 1
                        raise
                    continue
                with TraceAnnotation("discovery.rank", **span):
                    self._finish(job, triples, queries, results, outcomes,
                                 top_k, min_join, isolate, rank=rank, C=C)

            # 4. recovery (isolate mode): failed buckets retry with
            # backoff, then descend the executor ladder — *ungated*
            # (the phase-0 containment tier is a perf optimization; a
            # rung that exists to rescue a failing bucket must not add
            # an approximate filter on top); every other bucket already
            # delivered.
            for job in win.jobs:
                if job.error is not None:
                    st.failed_buckets += 1
                    self._recover(job, queries, results, outcomes,
                                  top_k, min_join, win.use_pref,
                                  n_shards, C, version, rank=rank)
        finally:
            win.release()
        return results, outcomes

    def _shortlist_phase(
        self, job: _BucketJob, min_join: int, top_k: int,
        n_shards: int, C: int, version: int, rung: str | None = None,
    ):
        """Collect a bucket's phase-1 join sizes, build + cache its
        shortlists, stage the prefilter stat deltas, and dispatch
        phase 2; returns the pending phase-2 handle."""
        rung = rung or job.rung
        on_mesh = rung == "distributed"
        pend1 = job.pend1
        # A fused handle that overflowed its shortlist rungs replays its
        # phase-1 join sizes here (already computed on device — no extra
        # scoring pass), so the fallback costs one more sync, not a full
        # re-dispatch.
        js = pend1.js_blocks() if hasattr(pend1, "js_blocks") \
            else pend1.collect()
        job.staged["host_syncs"] = job.staged.get("host_syncs", 1) + 1
        shortlists = build_shortlists(
            job.sp.plan, js, min_join,
            multiple=n_shards if on_mesh else 1,
        )
        s_key = shortlist_signature(shortlists)
        # Grow the plan-cache key by the shortlist signature: the
        # ladder makes its value set finite, so cache size — and
        # the compiled-program population it fronts — stays bounded
        # under arbitrarily varied min_join selectivity.
        self.plan_cache.lookup(
            version, job.y_disc, job.q_bucket,
            lambda p=job.sp.plan: p, s_key=s_key,
        )
        job.staged["prefiltered"] = len(job.chunk)
        job.staged["cands_considered"] = len(job.chunk) * C
        job.staged["cands_shortlisted"] = sum(
            sl.shortlisted for sl in shortlists if sl is not None
        )
        job.staged["s_buckets"] = {b for _, b in s_key}
        if on_mesh:
            return self._dist.shortlist_topk_dispatch(
                job.sp.plan, job.trains, shortlists, top_k,
                q_bucket=job.q_bucket,
            )
        return self._batched.shortlist_dispatch(
            job.sp.plan, job.trains, shortlists, q_bucket=job.q_bucket
        )

    def _fused_dispatch(
        self, job: _BucketJob, min_join: int, top_k: int,
        n_shards: int, C: int, version: int, rung: str | None = None,
    ):
        """Enqueue a bucket's whole fused two-phase pipeline (prefilter,
        on-device shortlist compaction, shard-local gather, scoring) in
        one dispatch; returns the pending fused handle.  The shortlist
        widths come from the adaptive hint ladder, so the plan-cache key
        — and the compiled-program population — stays bounded exactly
        as on the host-boundary path."""
        rung = rung or job.rung
        on_mesh = rung == "distributed"
        spec = fused_shortlist_spec(
            job.sp.plan, self.index.shortlist_hints, min_join,
            multiple=n_shards if on_mesh else 1, sharded=on_mesh,
        )
        self.plan_cache.lookup(
            version, job.y_disc, job.q_bucket,
            lambda p=job.sp.plan: p, s_key=spec.signature,
        )
        job.staged["prefiltered"] = len(job.chunk)
        job.staged["cands_considered"] = len(job.chunk) * C
        job.staged["s_buckets"] = {s for _, _, s in spec.signature}
        job.staged["fused_windows"] = 1
        if on_mesh:
            return self._dist.fused_topk_dispatch(
                job.sp.plan, job.trains, spec, min_join, top_k,
                q_bucket=job.q_bucket,
            )
        return self._batched.fused_dispatch(
            job.sp.plan, job.trains, spec, min_join,
            q_bucket=job.q_bucket,
        )

    def _tiered_dispatch(
        self, job: _BucketJob, min_join: int, min_containment: float,
        top_k: int, n_shards: int, C: int, version: int,
    ):
        """Enqueue a bucket's phase-0-gated pipeline: the corpus-wide
        signature containment sweep plus the fused prefilter -> compact
        -> gather -> score chain, one dispatch, one collect.  Survivor
        widths come from the tier hint ladder and join the plan-cache
        key next to the shortlist widths (``"tier0"`` entries are
        disjoint from ``"fused"`` ones), so a gated window and its
        ungated twin never collide and the compiled-program population
        stays bounded under any (min_containment, min_join) traffic."""
        on_mesh = job.rung == "distributed"
        tspec = tier_spec(
            job.sp.plan, self.index.tier_hints, min_containment,
            multiple=n_shards if on_mesh else 1, sharded=on_mesh,
        )
        spec = fused_shortlist_spec(
            job.sp.plan, self.index.tier_hints, min_join,
            multiple=n_shards if on_mesh else 1, sharded=on_mesh,
        )
        self.plan_cache.lookup(
            version, job.y_disc, job.q_bucket,
            lambda p=job.sp.plan: p,
            s_key=spec.signature + tspec.signature,
        )
        job.staged["prefiltered"] = len(job.chunk)
        job.staged["cands_considered"] = len(job.chunk) * C
        job.staged["cands_considered_t0"] = len(job.chunk) * C
        job.staged["s_buckets"] = {s for _, _, s in spec.signature}
        job.staged["fused_windows"] = 1
        job.staged["gated_windows"] = 1
        if on_mesh:
            return self._dist.tiered_topk_dispatch(
                job.sp.plan, job.trains, tspec, spec, min_join,
                min_containment, top_k, q_bucket=job.q_bucket,
            )
        return self._batched.tiered_dispatch(
            job.sp.plan, job.trains, tspec, spec, min_join,
            min_containment, q_bucket=job.q_bucket,
        )

    def _collect_triples(
        self, job: _BucketJob, C: int, min_join: int, top_k: int,
        n_shards: int, version: int, min_containment: float = 0.0,
    ) -> list:
        """First host sync of a bucket's handle -> one (values, global
        indices, join sizes) triple per live query.

        A fused handle checks its overflow fence here: if any query's
        surviving-candidate count exceeded its shortlist rung, the hints
        ladder is grown and the bucket falls back to the host-boundary
        path — reusing the fused pass's device-resident join sizes, so
        only phase 2 re-executes."""
        handle = job.handle
        if isinstance(handle, _ex._PendingScores):
            mi, js = handle.collect()
            gi = np.arange(C, dtype=np.int32)
            return [(mi[q], gi, js[q]) for q in range(len(job.chunk))]
        if isinstance(handle, (_ex._PendingTiered, _ex._PendingTieredTopk)):
            on_mesh = isinstance(handle, _ex._PendingTieredTopk)
            hints = self.index.tier_hints
            mc_key = round(float(min_containment), 6)
            try:
                triples = handle.collect()
            except SurvivorOverflow:
                # Either staged width was too small: grow the rungs and
                # re-run the window through the ungated fused path
                # (whose own overflow protocol then applies).  The gate
                # did not deliver this window — its staged tier
                # counters are withdrawn; the extra host sync the
                # tiered fence already paid is added back on top of
                # whatever the re-run's own accounting stages.
                for eid, seen in handle.observed_t0.items():
                    hints.observe(
                        ("tier0", job.y_disc, eid, mc_key, on_mesh),
                        seen, overflowed=True,
                    )
                for eid, seen in handle.observed.items():
                    # The truncated survivor buffer truncated this
                    # count too; its sound upper bound is the survivor
                    # count — growing to it re-converges in one round.
                    hints.observe(
                        (job.y_disc, eid, int(min_join), on_mesh),
                        max(seen, handle.observed_t0.get(eid, 0)),
                        overflowed=True,
                    )
                job.staged["gated_windows"] = 0
                job.staged.pop("cands_considered_t0", None)
                job.handle = self._fused_dispatch(
                    job, min_join, top_k, n_shards, C, version
                )
                triples = self._collect_triples(
                    job, C, min_join, top_k, n_shards, version
                )
                job.staged["host_syncs"] = \
                    job.staged.get("host_syncs", 1) + 1
                return triples
            for eid, seen in handle.observed_t0.items():
                hints.observe(
                    ("tier0", job.y_disc, eid, mc_key, on_mesh), seen
                )
            for eid, seen in handle.observed.items():
                hints.observe(
                    (job.y_disc, eid, int(min_join), on_mesh), seen
                )
            job.staged["cands_gated_t0"] = handle.survivors
            job.staged["cands_shortlisted"] = handle.shortlisted
            return triples
        if isinstance(handle, (_ex._PendingFused, _ex._PendingFusedTopk)):
            on_mesh = isinstance(handle, _ex._PendingFusedTopk)
            hints = self.index.shortlist_hints
            try:
                triples = handle.collect()
            except ShortlistOverflow:
                for eid, seen in handle.observed.items():
                    hints.observe(
                        (job.y_disc, eid, int(min_join), on_mesh),
                        seen, overflowed=True,
                    )
                job.pend1 = handle
                job.handle = self._shortlist_phase(
                    job, min_join, top_k, n_shards, C, version
                )
                job.staged["host_syncs"] = 3
                job.staged["fused_windows"] = 0
                return self._collect_triples(
                    job, C, min_join, top_k, n_shards, version
                )
            for eid, seen in handle.observed.items():
                hints.observe(
                    (job.y_disc, eid, int(min_join), on_mesh), seen
                )
            job.staged["cands_shortlisted"] = handle.shortlisted
            return triples
        return handle.collect()

    def _finish(
        self, job: _BucketJob, triples: list, queries: list,
        results: list, outcomes: list, top_k: int, min_join: int,
        isolate: bool, rank: str = "mi", C: int | None = None,
    ) -> None:
        """Rank a delivered bucket (fencing non-finite lanes first in
        isolate mode), scatter results, emit outcomes, and commit the
        bucket's staged stat deltas.

        ``C`` is the corpus size the bucket's scores were computed
        against — the window captures it at dispatch, so a collect that
        lands after a mid-flight ingest still ranks (and drops sentinel
        lanes) against the right corpus.  None falls back to the
        current size, which is correct for synchronous callers.

        ``rank="hybrid"`` re-weights each lane's score by its *exact*
        containment before ranking: mi x (join_size / train_size), with
        the join sizes every retrieval path already returns — no extra
        device work.  The ``min_join`` eligibility filter is unchanged;
        only the order among eligible candidates moves (toward ones
        whose keys actually cover the query's)."""
        st = self.admission
        C = len(self.index) if C is None else int(C)
        for row, qi in enumerate(job.chunk):
            v, gi, js = triples[row]
            nf = 0
            if isolate:
                v = np.asarray(v)
                gi = np.asarray(gi)
                js = np.asarray(js)
                eligible = (gi < C) & (js >= min_join)
                v = resilience.corrupt_scores(v, eligible)
                v, nf = resilience.fence_nonfinite(
                    v, gi, js, self.index, queries[qi], min_join, self.k
                )
                st.nonfinite_lanes += nf
            if rank == "hybrid":
                tsize = max(int(queries[qi].size), 1)
                v = np.asarray(v, np.float32) * (
                    np.asarray(js, np.float32) / np.float32(tsize)
                )
            results[qi] = self.index._rank(v, gi, js, top_k, min_join,
                                           C=C)
            if isolate:
                outcomes[qi] = QueryOutcome(
                    qi, "ok", rung=job.rung, retries=job.retries,
                    fallbacks=job.fallbacks, nonfinite_lanes=nf,
                )
        staged = job.staged
        st.batches += staged.get("batches", 0)
        st.padded_lanes += staged.get("padded_lanes", 0)
        st.prefiltered += staged.get("prefiltered", 0)
        st.cands_considered += staged.get("cands_considered", 0)
        st.cands_shortlisted += staged.get("cands_shortlisted", 0)
        st.q_buckets.update(staged.get("q_buckets", ()))
        st.s_buckets.update(staged.get("s_buckets", ()))
        st.host_syncs += staged.get("host_syncs", 0)
        st.fused_windows += staged.get("fused_windows", 0)
        st.gated_windows += staged.get("gated_windows", 0)
        st.cands_considered_t0 += staged.get("cands_considered_t0", 0)
        st.cands_gated_t0 += staged.get("cands_gated_t0", 0)

    # ------------------------------------------------------------------
    # Recovery ladder
    # ------------------------------------------------------------------

    def _recover(
        self, job: _BucketJob, queries: list, results: list,
        outcomes: list, top_k: int, min_join: int, use_pref: bool,
        n_shards: int, C: int, version: int, rank: str = "mi",
    ) -> None:
        """Retry a failed bucket with bounded backoff, descending the
        executor ladder between rungs; other buckets are untouched.

        Rung 0 is whatever the primary pass ran (its failed attempt
        counts as the rung's first try, so only retries remain); each
        lower rung gets a fresh attempt plus retries.  The final rung
        is the hook-free reference per-query loop — the exact dense
        path of :meth:`SketchIndex.query` — so anything that can
        execute at all delivers bit-identical rankings from there.
        """
        st = self.admission
        policy = self.retry_policy
        rungs = (["distributed"] if self._dist is not None else []) \
            + ["batched", "reference"]
        last_err = job.error
        for ri, rung in enumerate(rungs):
            if ri > 0:
                job.fallbacks += 1
                st.fallbacks += 1
            delays = policy.delays()
            # attempt 0 = the rung's first try; for rung 0 the primary
            # pass already spent it.
            for attempt in range(1 if ri == 0 else 0, 1 + len(delays)):
                if attempt > 0:
                    policy.sleep(delays[attempt - 1])
                    job.retries += 1
                    st.retries += 1
                try:
                    triples = self._run_bucket(
                        job, queries, top_k, min_join, use_pref,
                        n_shards, C, version, rung,
                    )
                    job.rung = rung
                    job.error = None
                    self._finish(job, triples, queries, results,
                                 outcomes, top_k, min_join, True,
                                 rank=rank, C=C)
                    return
                except Exception as e:  # noqa: BLE001 — keep descending
                    last_err = e
        for qi in job.chunk:
            outcomes[qi] = QueryOutcome(
                qi, "failed", rung=rungs[-1], error="ladder_exhausted",
                detail=repr(last_err), retries=job.retries,
                fallbacks=job.fallbacks,
            )
        st.lost_queries += len(job.chunk)

    def _run_bucket(
        self, job: _BucketJob, queries: list, top_k: int,
        min_join: int, use_pref: bool, n_shards: int, C: int,
        version: int, rung: str,
    ) -> list:
        """Synchronously re-execute one bucket on the given rung and
        return its per-query triples (job.staged is rebuilt to match
        what this run actually did)."""
        job.staged = {
            "batches": 1,
            "padded_lanes": (job.q_bucket - len(job.chunk)
                             if rung != "reference" else 0),
            "q_buckets": {job.q_bucket} if rung != "reference" else set(),
            "host_syncs": 1,
        }
        if rung == "reference":
            # Per-query dense scoring through the partitioned local
            # executor — exactly SketchIndex.query's prefilter=False
            # path, and free of every fault-injection site by
            # construction.
            ex = _ex.PartitionedLocalExecutor(k=self.k)
            triples = []
            for qi in job.chunk:
                train = self.index.train_arrays(queries[qi])
                mi, js = ex.execute(job.sp.plan, train)
                triples.append((mi[0], np.arange(C), js[0]))
            return triples
        ex = self._dist if rung == "distributed" else self._batched
        job.trains = _ex.stack_trains_host(job.sketches)
        if use_pref:
            job.pend1 = ex.prefilter_dispatch(
                job.sp.plan, job.trains, q_bucket=job.q_bucket
            )
            job.handle = self._shortlist_phase(
                job, min_join, top_k, n_shards, C, version, rung=rung,
            )
        elif rung == "distributed":
            job.handle = ex.topk_dispatch(
                job.sp.plan, job.trains, topk_oversample(top_k, C),
                q_bucket=job.q_bucket,
            )
        else:
            job.handle = ex.dispatch(
                job.sp.plan, job.trains, q_bucket=job.q_bucket
            )
        return self._collect_triples(
            job, C, min_join, top_k, n_shards, version
        )

    # ------------------------------------------------------------------
    # Observability
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Serving counters: admission decisions, resilience traffic
        (quarantine/retry/fallback/fence), plan-cache traffic, compiled-
        program population, ingest transfer accounting, and per-tier
        device-memory accounting (full-sketch bucket bytes vs the
        corpus-resident phase-0 signature bytes, both at allocated
        capacity — the memory side of the signature-width tradeoff)."""
        ingest = self.index.ingest_stats
        return {
            "admission": self.admission.as_dict(),
            "plan_cache": self.plan_cache.stats,
            "compiled_programs": _ex.compile_count(),
            "ingest": ingest,
            "tiers": {
                "sketch_bytes": ingest["sketch_bytes"],
                "signature_bytes": ingest["signature_bytes"],
                "signature_width": self.index._sig_cols(),
            },
            # Micro-batch tier telemetry (None until the first
            # submit_async attaches the scheduler): per-priority-class
            # queue-wait / end-to-end latency percentiles, coalesce
            # ratio, loop occupancy, backpressure + overlap counters.
            "scheduler": (
                self._scheduler.stats() if self._scheduler is not None
                else None
            ),
        }
