"""A CPU rehearsal of each cell through the harness: the generator, the
traffic, the warm-up, the window and the comparison with the reference,
at a size a test run holds; and the control, which must fail."""

import json

import pytest

import tinybench


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinybench.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", ["lake256-screen", "lake1024-screen"])
def test_cell_rehearsal_is_correct(root, name):
    out = tinybench.run_cell(root, name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"screen_pairs_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["wrong"]["value"] == 0
    assert out["device"]["platform"] == "cpu"


def test_traced_rehearsal_reports_per_layer_metrics(root):
    out = tinybench.run_cell(root, "lake256-screen", trace=True)
    assert out["correct"], out["checks"]
    # The CPU has no device plane: only the host-side readers report.
    assert set(out["metrics"]) == {"ingest_us_per_column", "flush_s"}


def test_control_in_lower_precision_is_not_correct(root):
    import control
    bench = json.loads((root / "BENCHMARK.json").read_text())
    got = control.control(str(root), bench, "lake256-screen", 2**31 + 11)
    assert not got["correct"], got
    assert got["checks"]["mi_gap"]["value"] > got["checks"]["mi_gap"]["limit"]
