"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json`` at the root of the checkout.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the JAX profiler and the result carries the cell's
per-layer metrics, the device's busy time and a breakdown.  The last
lines on standard error are the numbers the correctness check compared,
each with its limit; the last line on standard output is the result.

Exits 2, with no result, when JAX finds no TPU or fewer chips than the
cell needs: a CPU timing is never reported.  Exits 3, with no result
and a ``phase=deadline`` line, when ingest projects its end past the
set-up deadline (``harness.cell.SETUP_DEADLINE_S``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))


def _environment() -> None:
    """Before JAX loads: the persistent compilation cache at one fixed
    path inside the checkout (or the one the environment names), then
    the program's own launch defaults."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    from repro.launch.env import apply_env
    apply_env()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        print(f"no BENCHMARK.json at {ROOT}", file=sys.stderr)
        return 2
    with open(bench_path) as f:
        bench = json.load(f)
    _environment()
    from harness.cell import DeadlineMissed, NoChip, log, run
    try:
        result = run(ROOT, bench, args.workload, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     t_start=T_START)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    except DeadlineMissed as e:
        log(f"phase=deadline {e}")
        return 3
    for key, c in result["checks"].items():
        log(f"check {key}={c['value']} limit={c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
