"""The names the service gives its work on the profiler's clock.

The retrieval programs' stages are ``jax.named_scope``s in the shared
bodies (so every program carries them, inside its ``lax.map`` loops
too), the ``knn_stats`` kernel's ``pallas_call``s carry its name, and
the service's layer boundaries are ``TraceAnnotation`` spans that nest
under their submit and share its window id.
"""

from __future__ import annotations

import glob
import os
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.discovery import DiscoveryService, SketchIndex
from repro.core.discovery import executors as ex
from repro.core.discovery.planner import EST_MIXED
from repro.core.sketch import build_sketch
from repro.kernels.knn_stats import kernel as K

# Rows enough that the prefilter and the scorer step through a lax.map
# (_lane_step caps a step at 2^19 elements); shapes only, nothing runs.
Q, ROWS, CAP = 2, 1 << 15, 16


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sketch_args(lead):
    return tuple(_spec(lead + (CAP,), dt) for dt in
                 (jnp.uint32, jnp.float32, jnp.uint32, jnp.bool_))


def _lowered_fused():
    return ex._fused_score_group.lower(
        *_sketch_args((Q,)), *_sketch_args((ROWS,)),
        _spec((ROWS,), jnp.int32), _spec((ROWS,), jnp.bool_),
        _spec((), jnp.int32), _spec((), jnp.int32),
        est_id=EST_MIXED, k=3, s_bucket=ROWS,
    )


def _lowered_tiered():
    return ex._tiered_score_group.lower(
        *_sketch_args((Q,)), *_sketch_args((ROWS,)),
        _spec((ROWS, 9), jnp.int32), _spec((ROWS,), jnp.int32),
        _spec((ROWS,), jnp.bool_), _spec((), jnp.int32),
        _spec((), jnp.float32), _spec((), jnp.int32),
        est_id=EST_MIXED, k=3, s_surv=ROWS, s_bucket=ROWS,
    )


@pytest.fixture(scope="module")
def program_text():
    return {"fused": _lowered_fused().as_text(debug_info=True),
            "tiered": _lowered_tiered().as_text(debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("fused", "discovery.prefilter"),
    ("fused", "discovery.compact"),
    ("fused", "discovery.score"),
    ("tiered", "discovery.gate"),
    ("tiered", "discovery.prefilter"),
    ("tiered", "discovery.compact"),
    ("tiered", "discovery.score"),
])
def test_stage_scope_in_program_metadata(program_text, program, scope):
    assert f"/{scope}/" in program_text[program]


@pytest.mark.parametrize("program,scope", [
    ("fused", "discovery.prefilter"),
    ("fused", "discovery.score"),
    ("tiered", "discovery.score"),
])
def test_mapped_stage_loop_carries_its_scope(program_text, program, scope):
    """The stage's lax.map loop is one ``while`` op on the device: it
    carries the scope, so its device time falls under the stage."""
    assert f"/{scope}/while" in program_text[program]


def test_gate_scope_stays_out_of_the_ungated_program(program_text):
    assert "discovery.gate" not in program_text["fused"]


def _pallas_names(jaxpr) -> list[str]:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params["name"]))
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                names += _pallas_names(inner)
    return names


@pytest.mark.parametrize("call,name", [
    (lambda x, m: K.radius_counts_padded(x, x, m, k=3, block=128,
                                         interpret=True),
     "knn_stats_radius_counts"),
    (lambda x, m: K.radius_counts_padded(x, x, m, k=3, block=256,
                                         interpret=True),
     "knn_stats_radius_counts_1tile"),
    (lambda x, m: K.knn_smallest_padded(x, x, m, k=3, block=128,
                                        interpret=True),
     "knn_stats_radius_counts_knn"),
    (lambda x, m: K.ball_counts_padded(x, x, m, x, block=128,
                                       interpret=True),
     "knn_stats_radius_counts_balls"),
    (lambda x, m: K.ball_counts_padded(x, x, m, x, which="y", block=128,
                                       interpret=True),
     "knn_stats_radius_counts_xfree"),
])
def test_kernel_pallas_call_is_named(call, name):
    jaxpr = jax.make_jaxpr(call)(_spec((256,), jnp.float32),
                                 _spec((256,), jnp.int32))
    assert _pallas_names(jaxpr.jaxpr) == [name]
    assert name.startswith(K.KERNEL_NAME)


# -- host spans --------------------------------------------------------------

N_ROWS, SK_N = 600, 32


def _service():
    keys = np.asarray(hashing.murmur3_32_np(
        np.arange(N_ROWS, dtype=np.uint32), seed=np.uint32(9)))
    rng = np.random.default_rng(3)
    y = rng.normal(size=N_ROWS).astype(np.float32)
    index = SketchIndex(n=SK_N, method="tupsk")
    for i in range(3):
        index.add(f"cont{i}", "k", "v", keys,
                  (y + (0.3 + i) * rng.normal(size=N_ROWS))
                  .astype(np.float32), False)
        index.add(f"disc{i}", "k", "v", keys,
                  rng.integers(0, 3 + i, size=N_ROWS), True)

    def sketch(v, disc):
        return build_sketch(keys, v, n=SK_N, method="tupsk", side="train",
                            value_is_discrete=disc)

    queue = [sketch(y, False), sketch((y > 0).astype(np.int64), True),
             sketch(-y, False)]
    return DiscoveryService(index=index, max_q_bucket=4), queue


def _spans(log_dir) -> list[tuple]:
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("discovery."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                {k: v for k, v in e.stats}))
    return out


def test_submit_spans_nest_and_share_a_window_id(tmp_path):
    svc, queue = _service()
    want = svc.submit(queue, top_k=3, min_join=4)  # compiles outside
    with jax.profiler.trace(str(tmp_path)):
        got = [svc.submit(queue, top_k=3, min_join=4) for _ in range(2)]
    assert got == [want, want]
    spans = _spans(str(tmp_path))
    submits = [s for s in spans if s[0] == "discovery.submit"]
    assert len(submits) == 2
    windows = [s[3]["window"] for s in submits]
    assert len(set(windows)) == 2
    buckets = 2  # one per target dtype
    for name in ("discovery.dispatch", "discovery.collect",
                 "discovery.rank"):
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == 2 * buckets, name
        for _, start, end, stats in inner:
            parent, = [p for p in submits
                       if p[1] <= start and end <= p[2]]
            assert stats["window"] == parent[3]["window"]
        per_window = Counter((s[3]["window"], s[3]["bucket"])
                             for s in inner)
        assert set(per_window) == {(w, b) for w in windows
                                   for b in range(buckets)}
        assert set(per_window.values()) == {1}
    # dispatch, then collect, then rank, bucket by bucket
    for w in windows:
        for b in range(buckets):
            (d,), (c,), (r,) = (
                [s for s in spans if s[0] == n and s[3]["window"] == w
                 and s[3]["bucket"] == b]
                for n in ("discovery.dispatch", "discovery.collect",
                          "discovery.rank"))
            assert d[2] <= c[1] and c[2] <= r[1]
