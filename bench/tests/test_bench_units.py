"""The trace reduction, the kernel work count, the peaks table, the
memory peak over a cell's chips and the ingest loop."""

import json

import numpy as np
import pytest

import tinybench  # puts bench/ and src/ on the path
from harness import peaks, tracing, work


def _events(devs, host=()):
    return {"devices": devs, "host": list(host)}


def test_busy_is_the_union_of_overlapping_ops():
    ev = _events({"/device:TPU:0": [("a", 0, 100, "a"), ("b", 50, 150, "b"),
                                    ("c", 300, 400, "c | x"),
                                    ("d", 310, 320, "d")]},
                 host=[("outer", 0, 1000), ("collect", 160, 290)])
    red = tracing.reduce(ev)
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["device_ops"][0] == ["a", pytest.approx(100e-9)]
    # The one gap (150, 300) is named by the innermost host event over it.
    assert red["idle_gaps"] == [["collect", pytest.approx(150e-9)]]
    assert tracing.kernel_seconds(ev, ("b", "d")) == pytest.approx(110e-9)
    assert tracing.kernel_seconds(ev, ("x",)) == pytest.approx(100e-9)


def test_busy_is_averaged_over_chips():
    ev = _events({"/device:TPU:0": [("a", 0, 100, "a")],
                  "/device:TPU:1": [("a", 0, 300, "a")]})
    assert tracing.reduce(ev)["busy_s"] == pytest.approx(200e-9)


# A small trace in the profiler's own format (an XSpace): one chip whose
# "XLA Ops" line holds two fusions and a Mosaic kernel that overlaps the
# first, and a host thread.  Times in picoseconds from 1000 ns.
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 100000
             stats { metadata_id: 1 str_value: "custom_call_target=tpu_custom_call" } }
    events { metadata_id: 1 offset_ps: 300000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.7" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 160000 duration_ps: 130000 }
  }
  event_metadata { key: 1 value { id: 1 name: "outer" } }
  event_metadata { key: 2 value { id: 2 name: "collect" } }
}
"""


def test_trace_file_reduction(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert tracing.find_xplane(str(tmp_path)) == str(path)
    ev = tracing.read_events(str(path))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    red = tracing.reduce(ev)
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert red["idle_gaps"] == [["collect", pytest.approx(150e-9)]]
    assert tracing.kernel_seconds(ev, ("tpu_custom_call",)) == \
        pytest.approx(100e-9)


def test_kernel_work_count():
    assert work.lane_work("mixed", 256) == (15 * 256 * 256, 9 * 4 * 256)
    assert work.lane_work("dc_x", 1024) == (6 * 1024 ** 2, 6 * 4 * 1024)
    assert work.lane_work("mle", 256) == (0.0, 0.0)
    pk = peaks.peaks("TPU v5 lite")
    lanes = {"mixed": 1000, "dc_y": 500, "mle": 9999}
    f = 1000 * 15 * 256 ** 2 + 500 * 6 * 256 ** 2
    b = 1000 * 9 * 4 * 256 + 500 * 6 * 4 * 256
    least = max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
    assert work.roofline_pct(lanes, 256, least, pk) == pytest.approx(100.0)
    assert work.roofline_pct(lanes, 256, 10 * least, pk) == pytest.approx(10.0)
    assert work.roofline_pct(lanes, 256, 0.0, pk) is None
    assert work.roofline_pct({"mle": 5}, 256, 1.0, pk) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_is_the_fullest_chip():
    from harness import cell
    chips = [_Device({"peak_bytes_in_use": b}) for b in (5, 9, 7, 1)]
    assert cell.memory_peak(chips) == 9
    assert cell.memory_peak([_Device(None), _Device({})]) == 0


class _ColumnByColumn:
    def __init__(self):
        self.calls = []

    def add(self, *column):
        self.calls.append(("add", column))


class _Bulk(_ColumnByColumn):
    def add_columns(self, tables, key_column, value_column, keys, vals_f,
                    vals_c, discrete):
        self.calls.append(("add_columns", [
            (t, key_column, value_column, keys[j],
             vals_c[j] if discrete[j] else vals_f[j], bool(discrete[j]))
            for j, t in enumerate(tables)]))


def test_ingest_makes_one_call_per_chunk_with_the_adds_in_order():
    from harness import cell
    from harness.lake import Lake
    cfg = dict(json.loads(
        (tinybench.BENCH / "configs" / "opendata_n256.json").read_text()),
        **tinybench.TINY)
    cfg["tables"] = 2 * cell.INGEST_CHUNK + 5
    loop, bulk = _ColumnByColumn(), _Bulk()
    cell.ingest(loop, Lake(cfg, 7))
    cell.ingest(bulk, Lake(cfg, 7))
    assert [name for name, _ in bulk.calls] == ["add_columns"] * 3
    assert [len(cols) for _, cols in bulk.calls] == \
        [cell.INGEST_CHUNK, cell.INGEST_CHUNK, 5]
    added = [col for _, cols in bulk.calls for col in cols]
    assert len(added) == len(loop.calls) == cfg["tables"]
    for col, (_, ref) in zip(added, loop.calls):
        assert col[0:3] == ref[0:3] and col[5] is ref[5]
        np.testing.assert_array_equal(col[3], ref[3])
        np.testing.assert_array_equal(col[4], ref[4])
    disc = Lake(cfg, 7).discrete
    assert [col[5] for col in added] == disc.tolist()
