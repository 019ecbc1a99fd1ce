"""Phase-0 containment tier tests (ISSUE 8 acceptance):

  (a) the signature estimator: exact whenever a candidate holds at most
      ``sig_width`` keys, bounded-error and empirically unbiased above
      that, swept over skewed raw-id overlap patterns (hashing makes
      the key space uniform — the property the KMV sub-sample needs);
  (b) ``min_containment=0`` routes through the untouched fused path —
      bit-identical results by construction, asserted anyway — and a
      capacity-wide signature makes the gate *exact*, so gated ==
      ungated holds as a theorem across min_join/dtype sweeps;
  (c) recall: every candidate the ungated ranking returns whose exact
      containment clears the threshold with margin survives the gate;
  (d) both tiers flush transactionally — an injected flush fault leaves
      sketch rows and signature rows consistent, and the signature
      store always equals a host-side recomputation after interleaved
      ingest;
  (e) survivor overflow is a protocol: the window re-runs ungated
      bit-identically, tier hints grow, the service accounts the extra
      sync, and the warm window delivers gated;
  (f) the (survivor, shortlist) pow-2 ladders bound the gated compiled-
      program population; and the gated dispatch -> collect span passes
      under ``jax.transfer_guard("disallow")`` on both backends.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from _hypothesis_compat import given, settings, st
from repro.core import hashing, join
from repro.core.discovery import (
    BatchedExecutor,
    DiscoveryService,
    InjectedFault,
    MIN_SURVIVORS,
    RetryPolicy,
    SketchIndex,
    SurvivorOverflow,
    compile_count,
    fused_shortlist_spec,
    inject_faults,
    stack_trains,
    stage_min_containment,
    stage_min_join,
    tier_spec,
)
from repro.core.discovery import index as index_mod
from repro.core.discovery import planner as planner_mod
from repro.core.discovery.index import _signature_block
from repro.core.sketch import build_sketch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ROWS = 1200
SK_N = 64
RNG = np.random.default_rng(21)
_KEY_MAX = np.uint32(0xFFFFFFFF)


def _keys(seed=9, lo=0):
    raw = np.arange(lo, lo + N_ROWS, dtype=np.uint32)
    return np.asarray(hashing.murmur3_32_np(raw, seed=np.uint32(seed)))


def _train(keys, v, disc=False):
    return build_sketch(keys, v, n=SK_N, method="tupsk", side="train",
                        value_is_discrete=disc)


def _mixed_index(keys, y, rng, n_joinable=3, n_disjoint=3, n_disc=2,
                 sig_width=16):
    """Joinable core + disjoint tail — the selectivity regime the
    phase-0 gate exists for."""
    index = SketchIndex(n=SK_N, method="tupsk", sig_width=sig_width)
    for i in range(n_joinable):
        index.add(f"cont{i}", "k", "v", keys,
                  (y + (0.2 + i) * rng.normal(size=N_ROWS))
                  .astype(np.float32), False)
    for i in range(n_disc):
        index.add(f"disc{i}", "k", "v", keys,
                  rng.integers(0, 4 + i, size=N_ROWS), True)
    for i in range(n_disjoint):
        other = _keys(seed=9, lo=(i + 1) * N_ROWS)
        index.add(f"far{i}", "k", "v", other,
                  rng.normal(size=N_ROWS).astype(np.float32), False)
    return index


def _queue(keys, y, rng, q, disc_every=3):
    out = []
    for i in range(q):
        noisy = y + (0.1 + 0.25 * i) * rng.normal(size=N_ROWS)
        if i % disc_every == disc_every - 1:
            out.append(_train(keys, (noisy > 0).astype(np.int64), True))
        else:
            out.append(_train(keys, noisy.astype(np.float32), False))
    return out


def _flat(res):
    return [(m.table, mi, js) for m, mi, js in res]


def _effective_row(keys: np.ndarray, cap: int) -> tuple:
    """Store-format key row: valid prefix first, ascending, fenced."""
    ks = np.sort(np.unique(keys.astype(np.uint32)))[:cap]
    row = np.full(cap, _KEY_MAX, dtype=np.uint32)
    row[: ks.size] = ks
    mask = np.zeros(cap, dtype=bool)
    mask[: ks.size] = True
    return row, mask


def _sig_row(row: np.ndarray, mask: np.ndarray, w: int) -> np.ndarray:
    count = np.int32(mask.sum())
    return np.concatenate([row[:w].view(np.int32),
                           np.asarray([count], np.int32)])


class TestSignatureEstimator:
    """join.signature_join_size vs join.presorted_join_size."""

    def _raw_overlap(self, rng, mode, cand_n, overlap_n, space=10**6):
        """Skewed overlap patterns in raw-id space (hashing uniformizes
        the key space the signature samples from)."""
        train_ids = np.arange(0, 300, dtype=np.uint32)
        if mode == "head":
            shared = train_ids[:overlap_n]
        elif mode == "tail":
            shared = train_ids[-overlap_n:]
        else:  # zipf-ish: clustered low ids
            shared = np.unique(
                (rng.zipf(1.7, size=4 * overlap_n) % 300)
            ).astype(np.uint32)[:overlap_n]
        extra = np.arange(space, space + cand_n, dtype=np.uint32)
        cand_ids = np.concatenate([shared, extra])[:cand_n]
        return train_ids, cand_ids

    @given(seed=st.integers(0, 2**16),
           mode=st.sampled_from(["head", "tail", "zipf"]),
           cand_n=st.sampled_from([10, 40, 64]))
    @settings(max_examples=8, deadline=None)
    def test_bounds_and_exactness_property(self, seed, mode, cand_n):
        self._check_bounds(seed, mode, cand_n)

    @pytest.mark.parametrize("seed", [7, 1234, 40961])
    @pytest.mark.parametrize("mode", ["head", "tail", "zipf"])
    @pytest.mark.parametrize("cand_n", [10, 40, 64])
    def test_bounds_and_exactness_fixed_seeds(self, seed, mode, cand_n):
        """Deterministic twin of the property test above — runs in
        hypothesis-free environments."""
        self._check_bounds(seed, mode, cand_n)

    def _check_bounds(self, seed, mode, cand_n):
        rng = np.random.default_rng(seed)
        overlap = max(2, cand_n // 3)
        train_ids, cand_ids = self._raw_overlap(rng, mode, cand_n, overlap)
        tk = np.sort(np.asarray(
            hashing.murmur3_32_np(train_ids, seed=np.uint32(seed % 97))))
        ck = np.asarray(hashing.murmur3_32_np(
            cand_ids, seed=np.uint32(seed % 97)))
        row, mask = _effective_row(ck, SK_N)
        tmask = np.ones(tk.size, dtype=bool)
        exact = int(join.presorted_join_size(tk, tmask, row, mask))
        cand_valid = int(mask.sum())
        for w in (16, SK_N):
            est = float(join.signature_join_size(
                tk, tmask, _sig_row(row, mask, w)))
            if cand_valid <= w:
                assert est == exact, (w, mode)
            else:
                assert abs(est - exact) <= 2.0 * cand_valid / np.sqrt(w), \
                    (w, mode, est, exact)

    def test_empirically_unbiased(self):
        """Mean signature-estimate error over many candidates ~ 0."""
        rng = np.random.default_rng(3)
        tk_raw = np.arange(0, 400, dtype=np.uint32)
        tk = np.sort(np.asarray(hashing.murmur3_32_np(
            tk_raw, seed=np.uint32(11))))
        tmask = np.ones(tk.size, dtype=bool)
        errs, sizes = [], []
        for trial in range(40):
            ids = np.concatenate([
                rng.choice(tk_raw, size=30, replace=False),
                np.arange(10**6 + 100 * trial, 10**6 + 100 * trial + 34,
                          dtype=np.uint32),
            ])
            ck = np.asarray(hashing.murmur3_32_np(ids, seed=np.uint32(11)))
            row, mask = _effective_row(ck, SK_N)
            exact = int(join.presorted_join_size(tk, tmask, row, mask))
            est = float(join.signature_join_size(
                tk, tmask, _sig_row(row, mask, 16)))
            errs.append(est - exact)
            sizes.append(int(mask.sum()))
        assert abs(np.mean(errs)) <= 0.15 * np.mean(sizes)

    def test_fence_collision_key_dropped(self):
        """A candidate key equal to 0xFFFFFFFF is indistinguishable
        from the fence inside a signature; the estimate survives it."""
        tk = np.sort(RNG.integers(0, 2**31, size=50).astype(np.uint32))
        tmask = np.ones(50, dtype=bool)
        ck = np.concatenate([tk[:10], np.asarray([0xFFFFFFFF], np.uint32)])
        row, mask = _effective_row(ck, SK_N)
        est = float(join.signature_join_size(
            tk, tmask, _sig_row(row, mask, SK_N)))
        assert np.isfinite(est) and est >= 10


class TestGateParity:
    """min_containment=0 identity + exact-gate (capacity-wide
    signature) identity."""

    def test_zero_threshold_is_fused_path(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        index = _mixed_index(keys, y, np.random.default_rng(0))
        sk = _train(keys, y)
        a = index.query(sk, top_k=6, min_join=4)
        b = index.query(sk, top_k=6, min_join=4, min_containment=0.0)
        assert _flat(a) == _flat(b)

    def test_exact_gate_equals_ungated_sweep(self):
        """sig_width == sketch capacity makes phase 0 exact, so any
        threshold <= min_join/train_size keeps a superset of the exact
        survivors: gated == ungated bitwise across the sweep."""
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        index = _mixed_index(keys, y, np.random.default_rng(1),
                             sig_width=SK_N)
        for disc in (False, True):
            sk = _train(keys, (y > 0).astype(np.int64) if disc else y, disc)
            for mj in (1, 4, 16):
                gated = index.query(sk, top_k=6, min_join=mj,
                                    min_containment=1e-6)
                plain = index.query(sk, top_k=6, min_join=mj)
                assert _flat(gated) == _flat(plain), (disc, mj)

    def test_high_margin_gate_equals_ungated(self):
        """Noisy width (16 of 64 keys), but the corpus splits into
        containment ~1 and containment 0 — a 0.05 threshold cannot
        misclassify either side."""
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        index = _mixed_index(keys, y, np.random.default_rng(2))
        sk = _train(keys, y)
        gated = index.query(sk, top_k=6, min_join=4, min_containment=0.05)
        plain = index.query(sk, top_k=6, min_join=4)
        assert _flat(gated) == _flat(plain)

    def test_query_many_gated_parity_interleaved_ingest(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(3)
        index = _mixed_index(keys, y, rng, sig_width=SK_N)
        sks = _queue(keys, y, rng, 5, disc_every=99)
        for step in range(3):
            gated = index.query_many(sks, top_k=5, min_join=4,
                                     min_containment=1e-6)
            plain = index.query_many(sks, top_k=5, min_join=4)
            assert [_flat(g) for g in gated] == [_flat(p) for p in plain]
            index.add(f"late{step}", "k", "v", keys,
                      (0.5 * y + rng.normal(size=N_ROWS))
                      .astype(np.float32), False)

    @given(seed=st.integers(0, 2**16), min_join=st.sampled_from([1, 8]),
           disc=st.booleans())
    @settings(max_examples=6, deadline=None)
    def test_property_exact_gate_random_corpora(self, seed, min_join, disc):
        rng = np.random.default_rng(seed)
        keys = _keys(seed=seed % 97)
        y = rng.normal(size=N_ROWS).astype(np.float32)
        index = _mixed_index(keys, y, rng, n_joinable=2 + seed % 3,
                             n_disjoint=1 + seed % 2, sig_width=SK_N)
        sk = _train(keys, (y > 0).astype(np.int64) if disc else y, disc)
        gated = index.query(sk, top_k=5, min_join=min_join,
                            min_containment=1e-6)
        plain = index.query(sk, top_k=5, min_join=min_join)
        assert _flat(gated) == _flat(plain)


class TestRecall:
    def test_margin_survivors_always_recalled(self):
        """Every candidate of the ungated ranking whose *exact*
        containment clears the threshold with >= 4-sigma margin must
        appear in the gated ranking (sigma = 0.5/sqrt(w))."""
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(4)
        index = SketchIndex(n=SK_N, method="tupsk", sig_width=16)
        # overlap fractions spread across the containment range
        for i, frac in enumerate((1.0, 0.9, 0.75, 0.5, 0.25, 0.0)):
            n_shared = int(N_ROWS * frac)
            ids = np.concatenate([
                np.arange(n_shared, dtype=np.uint32),
                np.arange(10**6 + i * N_ROWS,
                          10**6 + i * N_ROWS + (N_ROWS - n_shared),
                          dtype=np.uint32),
            ])
            ck = np.asarray(hashing.murmur3_32_np(ids, seed=np.uint32(9)))
            index.add(f"c{i}", "k", "v", ck,
                      (y + 0.3 * rng.normal(size=N_ROWS))
                      .astype(np.float32), False)
        sk = _train(keys, y)
        tsize = max(sk.size, 1)
        mc = 0.05
        plain = index.query(sk, top_k=10, min_join=1)
        gated = index.query(sk, top_k=10, min_join=1, min_containment=mc)
        gated_tables = {m.table for m, _, _ in gated}
        margin = 4 * 0.5 / np.sqrt(16)
        for m, _, js in plain:
            if js / tsize >= mc + margin:
                assert m.table in gated_tables, m.table
        # the gate never invents candidates: gated subset of ungated
        assert gated_tables <= {m.table for m, _, _ in plain}


class TestValidation:
    def _index(self, **kw):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        return _mixed_index(keys, y, np.random.default_rng(5), **kw), \
            _train(keys, y)

    def test_gate_requires_fused(self):
        index, sk = self._index()
        with pytest.raises(ValueError, match="fused"):
            index.query(sk, min_join=4, min_containment=0.1, fused=False)

    def test_gate_requires_prefilter(self):
        index, sk = self._index()
        with pytest.raises(ValueError, match="two-phase"):
            index.query(sk, min_join=4, min_containment=0.1,
                        prefilter=False)

    def test_gate_requires_signature_tier(self):
        index, sk = self._index(sig_width=0)
        with pytest.raises(ValueError, match="sig_width"):
            index.query(sk, min_join=4, min_containment=0.1)
        # min_containment=0 stays available without the tier
        assert index.query(sk, top_k=3, min_join=4,
                           min_containment=0.0)

    def test_query_many_gate_rejects_executor(self):
        index, sk = self._index()
        with pytest.raises(ValueError, match="two-phase"):
            index.query_many([sk], min_join=4, min_containment=0.1,
                             executor="batched")

    def test_service_rank_validated(self):
        index, sk = self._index()
        svc = DiscoveryService(index=index)
        with pytest.raises(ValueError, match="rank"):
            svc.submit([sk], top_k=3, min_join=4, rank="bogus")

    def test_service_gate_requires_fused(self):
        index, sk = self._index()
        svc = DiscoveryService(index=index)
        with pytest.raises(ValueError, match="fused"):
            svc.submit([sk], top_k=3, min_join=4, min_containment=0.1,
                       fused=False)


class TestTierConsistency:
    """Both device tiers flush in one transaction."""

    @staticmethod
    def _assert_tiers_consistent(index):
        for y_disc, state in index._groups.items():
            for eid, store in state.stores.items():
                if not store.sig_cols:
                    continue
                idx = state.index[eid][: store.rows]
                want = _signature_block(
                    index._host_block(idx), store.sig_cols
                )
                got = np.asarray(store.arrays["sig"])[: store.rows]
                np.testing.assert_array_equal(got, want, err_msg=str(eid))
                # dead rows stay fenced
                tail = np.asarray(store.arrays["sig"])[store.rows:]
                assert tail.size == 0 or (tail == -1).all()

    def test_signature_store_matches_host_recompute(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(6)
        index = _mixed_index(keys, y, rng)
        sk = _train(keys, y)
        for step in range(3):
            index.query(sk, top_k=5, min_join=4, min_containment=0.05)
            self._assert_tiers_consistent(index)
            index.add(f"late{step}", "k", "v", keys,
                      (0.4 * y + rng.normal(size=N_ROWS))
                      .astype(np.float32), False)

    def test_flush_fault_leaves_tiers_consistent(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(7)
        index = _mixed_index(keys, y, rng)
        sk = _train(keys, y)
        want = _flat(index.query(sk, top_k=5, min_join=4,
                                 min_containment=0.05))
        index.add("late", "k", "v", keys,
                  (0.4 * y + rng.normal(size=N_ROWS))
                  .astype(np.float32), False)
        with inject_faults({"flush": 1}):
            with pytest.raises(InjectedFault):
                index.query(sk, top_k=5, min_join=4, min_containment=0.05)
        # the failed flush mutated nothing; the retry flushes the same
        # pending block into BOTH tiers and serves
        got = index.query(sk, top_k=5, min_join=4, min_containment=0.05)
        self._assert_tiers_consistent(index)
        plain = index.query(sk, top_k=5, min_join=4)
        assert _flat(got) == _flat(plain)
        assert len(got) >= len(want)


class TestOverflowProtocol:
    def _overflow_corpus(self):
        """> MIN_SURVIVORS fully-joinable candidates in one estimator
        group: cold tier hints (rung = MIN_SURVIVORS) must overflow."""
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(8)
        index = SketchIndex(n=SK_N, method="tupsk", sig_width=16)
        for i in range(MIN_SURVIVORS + 4):
            index.add(f"cont{i}", "k", "v", keys,
                      (y + (0.2 + i) * rng.normal(size=N_ROWS))
                      .astype(np.float32), False)
        return index, keys, y

    def test_executor_raises_and_reports(self):
        index, keys, y = self._overflow_corpus()
        sk = _train(keys, y)
        plan = index.plan(False)
        bx = BatchedExecutor()
        trains = stack_trains([index.train_arrays(sk)])
        hints = planner_mod.ShortlistHints()
        tspec = tier_spec(plan, hints, 0.05)
        spec = fused_shortlist_spec(plan, hints, 1)
        handle = bx.tiered_dispatch(plan, trains, tspec, spec, 1, 0.05)
        with pytest.raises(SurvivorOverflow):
            handle.collect()
        assert max(handle.observed_t0.values()) > MIN_SURVIVORS

    def test_service_fallback_accounting_and_warm_delivery(self):
        index, keys, y = self._overflow_corpus()
        svc = DiscoveryService(index=index, max_q_bucket=4)
        sk = _train(keys, y)
        # warm the UNGATED fused rungs so the overflow fallback is the
        # 1-sync fused window, making the deltas deterministic
        plain = svc.submit([sk], top_k=20, min_join=1)
        base = svc.stats()["admission"]
        cold = svc.submit([sk], top_k=20, min_join=1, min_containment=0.05)
        st1 = svc.stats()["admission"]
        # tiered overflow: +1 sync on top of the ungated re-run's 1
        assert st1["host_syncs"] - base["host_syncs"] == 2
        assert st1["gated_windows"] == base["gated_windows"]
        assert index.tier_hints.overflows > 0
        warm = svc.submit([sk], top_k=20, min_join=1, min_containment=0.05)
        st2 = svc.stats()["admission"]
        assert st2["host_syncs"] - st1["host_syncs"] == 1
        assert st2["gated_windows"] - st1["gated_windows"] == 1
        assert st2["cands_gated_t0"] >= MIN_SURVIVORS + 4
        assert 0.0 < st2["t0_selectivity"] <= 1.0
        assert svc.stats()["ingest"]["signature_bytes"] > 0
        assert _flat(cold[0]) == _flat(warm[0]) == _flat(plain[0])

    def test_tiered_dispatch_fault_recovers_ungated(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(9)
        index = _mixed_index(keys, y, rng)
        svc = DiscoveryService(index=index, max_q_bucket=4,
                               retry_policy=RetryPolicy(
                                   max_retries=1, sleep=lambda s: None))
        sks = _queue(keys, y, rng, 4)
        with inject_faults({"tiered_dispatch@batched": 1}):
            res, outs = svc.submit_safe(sks, top_k=5, min_join=4,
                                        min_containment=0.05)
        assert all(o.ok for o in outs)
        assert any(o.retries > 0 or o.fallbacks > 0 for o in outs)
        # recovery rungs are ungated — results match the ungated path
        want = svc.submit(sks, top_k=5, min_join=4)
        assert [_flat(r) for r in res] == [_flat(w) for w in want]


class TestHybridRanking:
    def test_hybrid_reweights_by_containment(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(10)
        index = _mixed_index(keys, y, rng, sig_width=SK_N)
        svc = DiscoveryService(index=index)
        sk = _train(keys, y)
        tsize = max(sk.size, 1)
        mi_res = svc.submit([sk], top_k=20, min_join=1)[0]
        hyb = svc.submit([sk], top_k=20, min_join=1,
                         min_containment=1e-6, rank="hybrid")[0]
        want = sorted(
            [(m.table, np.float32(mi) * (np.float32(js) / np.float32(tsize)))
             for m, mi, js in mi_res],
            key=lambda t: -t[1],
        )
        got = [(m.table, v) for m, v, _ in hyb]
        assert [t for t, _ in got] == [t for t, _ in want]
        np.testing.assert_allclose([v for _, v in got],
                                   [v for _, v in want], rtol=1e-6)

    def test_stats_surface_tiers(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        index = _mixed_index(keys, y, np.random.default_rng(11))
        svc = DiscoveryService(index=index)
        sk = _train(keys, y)
        svc.submit([sk], top_k=5, min_join=4, min_containment=0.05)
        stats = svc.stats()
        tiers = stats["tiers"]
        assert tiers["signature_width"] == 16
        assert 0 < tiers["signature_bytes"] < tiers["sketch_bytes"]
        adm = stats["admission"]
        assert adm["cands_considered_t0"] > 0
        assert adm["t0_selectivity"] is None or \
            0.0 <= adm["t0_selectivity"] <= 1.0


class TestCompileBound:
    def test_gated_program_population_bounded(self):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        rng = np.random.default_rng(12)
        index = _mixed_index(keys, y, rng)
        svc = DiscoveryService(index=index, max_q_bucket=4)

        def sweep(r):
            for q in (1, 3):
                for mc in (0.02, 0.05):
                    svc.submit(_queue(keys, y, r, q), top_k=5,
                               min_join=4, min_containment=mc)

        sweep(np.random.default_rng(100))
        warm = compile_count()
        sweep(np.random.default_rng(200))
        assert compile_count() == warm


@pytest.mark.transfer_guard
class TestTransferGuard:
    """The gated dispatch -> collect span moves nothing across the host
    boundary: phase-0 mask, survivor compaction, prefilter, shortlist
    compaction, and gather all stay device-resident."""

    def test_batched_gated_no_transfers(self, monkeypatch):
        keys = _keys()
        y = RNG.normal(size=N_ROWS).astype(np.float32)
        index = _mixed_index(keys, y, np.random.default_rng(13))
        sk = _train(keys, y)
        # warm: hints, compiled programs, staged scalars, plan arrays
        index.query(sk, top_k=5, min_join=4, min_containment=0.05)
        index.query(sk, top_k=5, min_join=4, min_containment=0.05)

        def boom(*a, **k):
            raise AssertionError("host shortlist build on gated path")

        monkeypatch.setattr(planner_mod, "build_shortlists", boom)
        monkeypatch.setattr(index_mod, "build_shortlists", boom)
        plan = index.plan(False)
        trains = stack_trains([index.train_arrays(sk)])
        bx = BatchedExecutor()
        tspec = tier_spec(plan, index.tier_hints, 0.05)
        spec = fused_shortlist_spec(plan, index.tier_hints, 4)
        stage_min_join(4)
        stage_min_containment(0.05)
        bx.tiered_dispatch(plan, trains, tspec, spec, 4, 0.05).collect()
        with jax.transfer_guard("disallow"):
            handle = bx.tiered_dispatch(
                plan, trains, tspec, spec, 4, 0.05
            )
            triples = handle.collect()
        assert len(triples) >= 1 and len(triples[0][0]) > 0


class TestFourShardParity:
    """Gated retrieval through real 4-shard programs (subprocess —
    device count is fixed at jax init): hash-partitioned phase 0,
    shard-local survivor compaction, on-device winner merge."""

    SCRIPT = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax
        from repro.core import hashing
        from repro.core.discovery import DiscoveryService, SketchIndex
        from repro.core.sketch import build_sketch
        from repro.parallel.compat import make_mesh

        N, SK = 1200, 64
        rng = np.random.default_rng(14)
        keys = np.asarray(hashing.murmur3_32_np(
            np.arange(N, dtype=np.uint32), seed=np.uint32(9)))
        y = rng.normal(size=N).astype(np.float32)
        index = SketchIndex(n=SK, method="tupsk", sig_width=16)
        for i in range(5):
            index.add(f"cont{i}", "k", "v", keys,
                      (y + (0.2 + i) * rng.normal(size=N))
                      .astype(np.float32), False)
        for i in range(5):
            far = np.asarray(hashing.murmur3_32_np(
                np.arange((i + 1) * N, (i + 2) * N, dtype=np.uint32),
                seed=np.uint32(9)))
            index.add(f"far{i}", "k", "v", far,
                      rng.normal(size=N).astype(np.float32), False)
        sk = build_sketch(keys, y, n=SK, method="tupsk", side="train",
                          value_is_discrete=False)
        flat = lambda r: [(m.table, mi, js) for m, mi, js in r]
        mesh = make_mesh((4,), ("data",))

        # mesh gated == mesh ungated == local gated (cold + warm)
        for _ in range(2):
            g_mesh = index.query(sk, top_k=5, min_join=4, mesh=mesh,
                                 min_containment=0.05)
            p_mesh = index.query(sk, top_k=5, min_join=4, mesh=mesh)
            g_loc = index.query(sk, top_k=5, min_join=4,
                                min_containment=0.05)
            assert flat(g_mesh) == flat(p_mesh) == flat(g_loc)
        print("TIER-SHARD-PARITY-OK")

        # service on the mesh: gated windows deliver after warm-up and
        # match the ungated submit
        svc = DiscoveryService(index=index, mesh=mesh, max_q_bucket=2)
        sks = [build_sketch(keys, (y + 0.2 * (q + 1)
                                   * rng.normal(size=N)).astype(np.float32),
                            n=SK, method="tupsk", side="train",
                            value_is_discrete=False) for q in range(3)]
        svc.submit(sks, top_k=5, min_join=4, min_containment=0.05)
        got = svc.submit(sks, top_k=5, min_join=4, min_containment=0.05)
        want = svc.submit(sks, top_k=5, min_join=4)
        assert [flat(g) for g in got] == [flat(w) for w in want]
        adm = svc.stats()["admission"]
        assert adm["gated_windows"] > 0, adm
        assert adm["cands_gated_t0"] > 0
        print("TIER-SERVICE-OK")

        # gated dispatch -> collect with zero host syncs on the mesh
        from repro.core.discovery import (
            fused_shortlist_spec, stack_trains, stage_min_containment,
            stage_min_join, tier_spec,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P
        ex = index._distributed_executor(mesh)
        tr1 = stack_trains([index.train_arrays(sks[0])])
        rep = NamedSharding(mesh, P())
        tr1 = {k: jax.device_put(v, rep) if hasattr(v, "shape") else v
               for k, v in tr1.items()}
        plan = index.plan(False)
        tspec = tier_spec(plan, index.tier_hints, 0.05, multiple=4,
                          sharded=True)
        spec = fused_shortlist_spec(plan, index.tier_hints, 4,
                                    multiple=4, sharded=True)
        stage_min_join(4)
        stage_min_containment(0.05)
        ex.tiered_topk_dispatch(plan, tr1, tspec, spec, 4, 0.05,
                                5).collect()  # warm
        with jax.transfer_guard("disallow"):
            h = ex.tiered_topk_dispatch(plan, tr1, tspec, spec, 4,
                                        0.05, 5)
            triples = h.collect()
        assert len(triples) >= 1 and len(triples[0][0]) > 0
        print("TIER-GUARD-OK")
    """)

    def test_four_shard_gated(self):
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT],
            capture_output=True, text=True, timeout=420,
            env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "TIER-SHARD-PARITY-OK" in out.stdout
        assert "TIER-SERVICE-OK" in out.stdout
        assert "TIER-GUARD-OK" in out.stdout
