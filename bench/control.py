"""The control of the correctness check: the reference, computed in
bfloat16 (the step below the configurations' float32), put in the
program's place.

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's lake at the cell's own size and the
first ``complete_checks`` base tables of its mix (as many as a run
checks in full), answers them with the bfloat16 reference, and holds
those answers against the float32 reference with the benchmark's own
comparison.  It prints one
JSON line per seed with the numbers compared; the control has to come
out not correct.  The benchmark's runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def control(root: str, bench: dict, name: str, seed: int) -> dict:
    import numpy as np

    from harness import cell, compare, traffic

    _, cfg, spec = cell.cell_spec(root, bench, name)
    n, k = int(cfg["n"]), int(cfg["k"])
    opts = traffic.serving_options(spec)
    mj, top_k, gates = opts["min_join"], opts["top_k"], traffic.gates(opts)
    lake_mod = cell.load_lake(root, cfg)
    lake_mod.check_options(gates)
    lake = lake_mod.Lake(cfg, seed)
    lake.keep_rows()
    for a in range(0, lake.columns, cell.INGEST_CHUNK):
        lake.chunk(a, min(a + cell.INGEST_CHUNK, lake.columns))
    ls = lake_mod.LakeSketches(lake, n)
    reqs = lake_mod.requests(lake, spec)[:int(spec["complete_checks"])]
    joins: dict = {}
    gap, wrong, answers = 0.0, 0, 0
    for q in (q for r in reqs for q in r):
        ref = lake_mod.answer(lake, ls, q, n=n, k=k, min_join=mj,
                              joins=joins, **gates)
        low = lake_mod.answer(lake, ls, q, n=n, k=k, min_join=mj,
                              dtype="bfloat16", joins=joins, **gates)
        served = [(f"c{c}", mi, js) for c, mi, js in lake_mod.ranked(
            low, top_k=top_k, min_join=mj)]
        g, e = compare.check_answer(served, ref, top_k=top_k, min_join=mj)
        gap, wrong, answers = max(gap, g), wrong + e, answers + 1
    ok, checks = compare.verdict(gap, wrong)
    return {"workload": name, "seed": seed, "answers": answers,
            "correct": ok, "checks": checks,
            "mi_gap_over_limit": float(np.float32(gap)
                                       / compare.LIMITS["mi_gap"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for s in args.seeds.split(","):
        print(json.dumps(control(ROOT, bench, args.workload, int(s))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
