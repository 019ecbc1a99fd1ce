"""The harness finds a cell's pieces by name, and the command refuses to
run where there is no chip or no program."""

import json
import os
import shutil
import subprocess
import sys

import tinybench
from harness import cell


def test_added_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tinybench.make_root(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["tables"] = 512
    (b / "configs" / "added.json").write_text(json.dumps(cfg))
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
    out = tinybench.run_cell(root, "added-cell", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["added_metric.screen"]["value"] == 256


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
