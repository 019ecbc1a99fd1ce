"""A copy of the benchmark at a size a CPU test run holds.

``make_root(tmp)`` copies ``bench/`` into ``tmp``, adds a tiny
configuration, shrinks the screen mix, and writes a ``BENCHMARK.json``
whose cells mirror the real ones on them.
``run_cell`` drives one run of a cell through the harness on the CPU
(the harness's look for a chip skipped); ``recording`` makes a service
factory whose services keep their answers.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"n": 32, "sig_width": 8, "tables": 768, "rows_per_table": 256,
        "n_keys_min": 20, "n_keys_max": 300}
TINY_MIX = {"distinct_requests": 8, "complete_checks": 2}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    root = pathlib.Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "opendata_n256.json").read_text())
    cfg.update(TINY, name="tiny")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "screen.json").read_text())
    mix.update(TINY_MIX, min_join=12)
    (root / "bench" / "traffic" / "screen.json").write_text(json.dumps(mix))
    bench["configs"] = [{"name": "tiny", "source": "https://arxiv.org/abs/2403.15553",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "a CPU test size"}]
    for w in bench["workloads"]:
        w["config"] = "tiny"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root: pathlib.Path, name: str, *, seed: int = 2**31 + 3,
             seconds: float = 1.0, trace: bool = False, **kw) -> dict:
    from harness import cell
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return cell.run(str(root), bench, name, seed=seed, seconds=seconds,
                    trace=trace, t_start=time.perf_counter(),
                    require_chip=False, **kw)


def request_key(sketches) -> str:
    """A request's fingerprint: its query sketches' keys and values."""
    h = hashlib.sha1()
    for sk in sketches:
        h.update(sk.key_hashes.tobytes())
        h.update(sk.values.tobytes())
    return h.hexdigest()


def recording():
    """(answers, factory): the factory's services keep in ``answers``,
    by ``request_key``, the first answer to each request as lists of
    (column, mi, join size); ``factory.layouts`` holds the mesh each
    service was given (None for one chip)."""
    from harness import compare
    from repro.core.discovery import DiscoveryService

    answers: dict = {}

    class Recording(DiscoveryService):
        def submit(self, queries, **kw):
            res = super().submit(queries, **kw)
            answers.setdefault(request_key(queries), [
                [(compare.column_of(t), float(mi), int(js))
                 for t, mi, js in r] for r in res])
            return res

    def factory(**kw):
        factory.layouts.append(kw.get("mesh"))
        return Recording(**kw)

    factory.layouts = []
    return answers, factory
