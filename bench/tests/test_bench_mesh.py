"""A cell on four chips, rehearsed through the harness on four CPU
devices: its service runs on a ``("data",)`` mesh over them, its
answers pass the reference check, and they are the answers of the same
cell on one chip under the same seed.

The device count is fixed when JAX starts, so the rehearsal runs in a
child process with ``--xla_force_host_platform_device_count=4``."""

import json
import os
import subprocess
import sys
import textwrap

import tinybench

SCRIPT = textwrap.dedent("""
    import json, pathlib, sys
    sys.path.insert(0, sys.argv[1])
    import tinybench

    root = tinybench.make_root(pathlib.Path(sys.argv[2]))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mesh4 = dict(next(w for w in bench["workloads"]
                      if w["name"] == "lake256-screen"),
                 name="lake256-mesh4", chips=4)
    bench["workloads"].append(mesh4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lake256-screen" in m.get("workloads", []):
            m["workloads"].append("lake256-mesh4")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = {}
    for name in ("lake256-screen", "lake256-mesh4"):
        answers, factory = tinybench.recording()
        res = tinybench.run_cell(root, name, service_factory=factory)
        out[name] = {"result": res, "answers": answers,
                     "mesh": [None if m is None else
                              [list(m.axis_names), m.devices.size]
                              for m in factory.layouts]}
    print(json.dumps(out))
""")


def test_four_chip_cell_runs_on_a_mesh_and_answers_as_one_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(tinybench.ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tinybench.BENCH / "tests"),
         str(tmp_path)], env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    one, four = out["lake256-screen"], out["lake256-mesh4"]
    assert one["mesh"] == [None]
    assert four["mesh"] == [[["data"], 4]]
    for run in (one, four):
        res = run["result"]
        assert res["correct"], res["checks"]
        assert res["checks"]["wrong"]["value"] == 0
        assert res["device"]["count"] == 4
        assert res["attempted"] > 0
    common = one["answers"].keys() & four["answers"].keys()
    # Warm-up requests and at least the window's first.
    assert len(common) >= 2
    for key in common:
        assert four["answers"][key] == one["answers"][key]
