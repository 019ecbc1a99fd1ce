"""The harness finds a cell's pieces by name, and the command refuses to
run where there is no chip or no program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import tinybench
from harness import cell

# Appended to a copy of the table2 lake module: its reference leaves a
# mark beside it when it answers, so a test can see which module ran.
MARK = """

_table2_answer = answer


def answer(*args, **kw):
    import pathlib
    pathlib.Path(__file__).with_suffix(".answered").touch()
    return _table2_answer(*args, **kw)
"""


def test_added_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tinybench.make_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["tables"] = 512
    cfg["lake"] = "copied"
    (b / "configs" / "added.json").write_text(json.dumps(cfg))
    (b / "lakes" / "copied.py").write_text(
        (b / "lakes" / "table2.py").read_text() + MARK)
    mix = json.loads((b / "traffic" / "screen.json").read_text())
    mix["targets"] = ["numeric", "categorical"]
    (b / "traffic" / "added_mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "added_metric.screen.py").write_text(
        "def read(ctx):\n    return ctx['columns'] / 2\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "added", "source": "https://arxiv.org/abs/2403.15553",
                             "file": "bench/configs/added.json",
                             "reduced": [], "why": "added"})
    bench["workloads"].append({"name": "added-cell", "config": "added",
                               "traffic": "added_mix", "chips": 1,
                               "why": "added"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["screen_pairs_per_s"]["workloads"].append("added-cell")
    bench["per_layer"].append({
        "name": "added_metric.screen", "unit": "n", "better": "higher",
        "source": "program_counter", "layer": "index ingest",
        "moves": "screen_pairs_per_s", "workloads": ["added-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c, conf, spec = cell.cell_spec(str(root), bench, "added-cell")
    assert conf["tables"] == 512 and len(spec["targets"]) == 2
    names = [m["name"] for m in cell.cell_metrics(bench, c, trace=True)]
    assert "added_metric.screen" in names
    answers, factory = tinybench.recording()
    out = tinybench.run_cell(root, "added-cell", trace=True,
                             service_factory=factory)
    assert out["correct"], out["checks"]
    assert out["metrics"]["added_metric.screen"]["value"] == 256
    assert (b / "lakes" / "copied.answered").exists()

    # The same configuration on the table2 lake: the same answers.
    cfg["lake"] = "table2"
    (b / "configs" / "added.json").write_text(json.dumps(cfg))
    answers2, factory2 = tinybench.recording()
    out2 = tinybench.run_cell(root, "added-cell", service_factory=factory2)
    assert out2["correct"], out2["checks"]
    assert out2["checks"] == out["checks"]
    common = answers.keys() & answers2.keys()
    assert len(common) >= 2
    assert all(answers[key] == answers2[key] for key in common)


@pytest.mark.parametrize("value", [0.05, 0.5, 1.0])
def test_table2_lake_refuses_a_gate(tmp_path, value):
    root = tinybench.make_root(tmp_path)
    path = root / "bench" / "traffic" / "screen.json"
    mix = json.loads(path.read_text())
    mix["min_containment"] = value
    path.write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="table2 refuses min_containment="):
        tinybench.run_cell(root, "lake256-screen")


def test_a_gate_at_its_default_is_left_to_the_service():
    from harness import traffic
    mix = {"top_k": 10, "min_join": 100}
    assert traffic.serving_options(mix) == mix
    opts = traffic.serving_options(dict(mix, min_containment=0.0))
    assert opts == mix and traffic.gates(opts) == {}
    opts = traffic.serving_options(dict(mix, min_containment=0.25))
    assert traffic.gates(opts) == {"min_containment": 0.25}


# A lake module whose reference takes the containment gate: it leaves
# beside it, as JSON, each gate value its answers were asked for.
GATED_LAKE = """
import json
import pathlib

from harness import reference
from harness.lake import Lake
from harness.reference import LakeSketches, join, kind_of, ranked
from harness.traffic import requests

SEEN = pathlib.Path(__file__).with_suffix(".seen")


def check_options(gates):
    if set(gates) != {"min_containment"}:
        raise ValueError(f"gated lake takes min_containment: {gates}")


def answer(lake, ls, q, *, min_containment, **kw):
    seen = json.loads(SEEN.read_text()) if SEEN.exists() else []
    SEEN.write_text(json.dumps(sorted(set(seen) | {min_containment})))
    return reference.answer(lake, ls, q, **kw)
"""


def test_a_gate_reaches_submit_and_the_lake_reference(tmp_path):
    from repro.core.discovery import DiscoveryService
    root = tinybench.make_root(tmp_path)
    b = root / "bench"
    (b / "lakes" / "gated.py").write_text(GATED_LAKE)
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["lake"] = "gated"
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "screen.json").read_text())
    # A gate every column the mix's join size passes clears, each
    # request checked in full: the answers are the ungated reference's.
    mix.update(min_containment=0.001, complete_checks=64)
    (b / "traffic" / "screen.json").write_text(json.dumps(mix))
    submitted = []

    class Seen(DiscoveryService):
        def submit(self, queries, **kw):
            submitted.append(kw)
            return super().submit(queries, **kw)

    out = tinybench.run_cell(root, "lake256-screen", service_factory=Seen)
    assert out["correct"], out["checks"]
    assert submitted and all(kw["min_containment"] == 0.001
                             for kw in submitted)
    assert json.loads((b / "lakes" / "gated.seen").read_text()) == [0.001]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lake256-screen",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_non_zero_off_a_tpu(tmp_path):
    p = _run(tinybench.ROOT,
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert p.returncode == 2, p.stderr[-2000:]
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(tinybench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tinybench.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
