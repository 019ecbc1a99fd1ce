"""The per-layer readers of the program's own names: device time of the
estimator kernel's ops and host time of the service's spans, per query,
with nested or overlapping events counted once."""

import pytest

import tinybench
from harness import cell, tracing

READERS = ("knn_stats_ms_per_query.screen",
           "host_rank_ms_per_query.screen",
           "host_dispatch_ms_per_query.screen")

# Two chips and a host thread, times in picoseconds from 1000 ns.  Device
# ops are named as a TPU names them, by their HLO instruction, which
# cites its operands.  Chip 0 runs a loop (0-500 ns) whose body holds two
# kernel calls that overlap (100-300, 250-350) and an op that reads the
# kernel's output (400-500); chip 1 runs one kernel call (0-150).  The
# host holds two overlapping rank spans (900-1000, 950-1100), a dispatch
# (0-50) and a span whose name only starts like the rank's.
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 250000 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 400000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.27 = (s32[]) while(s32[] %tuple.1)" } }
  event_metadata { key: 2 value { id: 2 name: "%knn_stats_radius_counts_1tile.7 = f32[8] custom-call(f32[8] %copy.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.69 = f32[8] fusion(f32[8] %knn_stats_radius_counts_1tile.7)" } }
}
planes {
  id: 2
  name: "/device:TPU:1"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 150000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%knn_stats_radius_counts.10 = f32[8] custom-call(f32[8] %copy.1)" } }
}
planes {
  id: 3
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 900000 duration_ps: 100000 }
    events { metadata_id: 1 offset_ps: 950000 duration_ps: 150000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 50000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "discovery.rank" } }
  event_metadata { key: 2 value { id: 2 name: "discovery.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "discovery.ranking" } }
}
"""

QUERIES = 2


@pytest.fixture(scope="module")
def readers():
    return {m: cell.load_reader(str(tinybench.ROOT), m) for m in READERS}


def _ctx(tmp_path, xspace):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace))
    events = tracing.read_events(str(path))
    return {"events": events, "queries": QUERIES,
            "trace": tracing.reduce(events) if events["devices"] else None}


@pytest.mark.parametrize("metric,ns", [
    # the kernel by its own name: 100-350 on chip 0 (the two calls once,
    # not the loop around them nor the op that reads their output), 150
    # on chip 1, averaged over the chips
    ("knn_stats_ms_per_query.screen", 200),
    # overlapping spans once; not discovery.ranking
    ("host_rank_ms_per_query.screen", 200),
    ("host_dispatch_ms_per_query.screen", 50),
])
def test_reader_counts_overlapping_events_once(tmp_path, readers, metric,
                                               ns):
    got = readers[metric](_ctx(tmp_path, XSPACE))
    assert got == pytest.approx(ns * 1e-6 / QUERIES)


@pytest.mark.parametrize("metric", READERS)
def test_reader_without_a_trace_reads_nothing(readers, metric):
    assert readers[metric]({"events": None, "trace": None,
                            "queries": QUERIES}) is None
    assert readers[metric]({"queries": QUERIES}) is None


# What a program without the names leaves: a kernel op named by the jit
# around its pallas_call, and Python tracer events on the host.
UNNAMED = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.27 = (s32[]) while(s32[] %tuple.1)" } }
  event_metadata { key: 2 value { id: 2 name: "%vmap(jit(radius_counts_padded)).8 = f32[8] custom-call(f32[8] %copy.1)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000 }
  }
  event_metadata { key: 1 value { id: 1 name: "$service.py:1002 _finish" } }
}
"""


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_an_unnamed_program_reads_nothing(tmp_path, readers,
                                                    metric):
    assert readers[metric](_ctx(tmp_path, UNNAMED)) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_without_a_device_plane_reads_nothing(tmp_path, readers,
                                                     metric):
    host_only = XSPACE[XSPACE.index("planes {\n  id: 3"):]
    assert readers[metric](_ctx(tmp_path, host_only)) is None


def _four_chips() -> str:
    """Four chips; chip i (0-3) runs a fusion over [0, 100(i+1)) ns and
    inside it a kernel call over [0, 50(i+1)) ns."""
    planes = []
    for i in range(4):
        planes.append(f"""
planes {{
  id: {i + 1}
  name: "/device:TPU:{i}"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {100000 * (i + 1)} }}
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: {50000 * (i + 1)} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] fusion(f32[8] %p)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%knn_stats_radius_counts.3 = f32[8] custom-call(f32[8] %p), custom_call_target=\\"tpu_custom_call\\"" }} }}
}}""")
    return "".join(planes)


def test_readers_on_four_chips_read_per_chip(tmp_path):
    from harness import peaks, work
    ctx = _ctx(tmp_path, _four_chips())
    assert ctx["trace"]["chips"] == 4
    # Busy: the union on each chip (100..400 ns), averaged over chips.
    assert ctx["trace"]["busy_s"] == pytest.approx(250e-9)
    pk = peaks.peaks("TPU v5 lite")
    lanes = {"mixed": 3, "dc_x": 2}
    ctx.update(window_s=1000e-9, knn_lanes=lanes, P=256, peaks=pk)
    read = {m: cell.load_reader(str(tinybench.ROOT), m)(ctx) for m in (
        "device_busy_ms_per_query.screen", "device_idle_pct.screen",
        "knn_stats_ms_per_query.screen", "knn_stats_roofline.screen")}
    assert read["device_busy_ms_per_query.screen"] == \
        pytest.approx(250e-6 / QUERIES)
    assert read["device_idle_pct.screen"] == pytest.approx(75.0)
    # The kernel: 50..200 ns a chip, averaged.
    assert read["knn_stats_ms_per_query.screen"] == \
        pytest.approx(125e-6 / QUERIES)
    # The work of all chips over the kernel time of all chips (500 ns) at
    # one chip's peaks: the mean share of a chip.
    assert read["knn_stats_roofline.screen"] == pytest.approx(
        work.roofline_pct(lanes, 256, 500e-9, pk))
    one = work.roofline_pct(lanes, 256, 125e-9, pk) / 4
    assert read["knn_stats_roofline.screen"] == pytest.approx(one)
