"""The set-up deadline: ingest that projects its end past
``cell.SETUP_DEADLINE_S`` stops the run with exit code 3 and no result,
ingest column by column projects inside a chunk too, and a slow first
chunk on each chip (the compiles) does not count against the rate."""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import tinybench
from harness import cell
from harness.lake import Lake

# A run through the command's own ``main``, its look for a chip skipped,
# on a lake of 100 chunks whose service takes 2 s a chunk: 200 s of
# ingest, past the deadline from the first chunk whose rate counts (the
# second) on.
SCRIPT = textwrap.dedent("""
    import functools, importlib.util, json, pathlib, sys, time
    sys.path.insert(0, sys.argv[1])
    import tinybench

    root = tinybench.make_root(pathlib.Path(sys.argv[2]))
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg["tables"] = 100 * 2048
    path.write_text(json.dumps(cfg))
    from harness import cell

    class Slow:
        def __init__(self, **kw):
            self.columns = 0

        def add_columns(self, tables, *arrays):
            time.sleep(2.0)
            self.columns += len(tables)

    spec = importlib.util.spec_from_file_location(
        "run", root / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cell.run = functools.partial(cell.run, require_chip=False,
                                 service_factory=Slow)
    sys.exit(run.main(["--workload", "lake256-screen", "--seed",
                       str(2**31 + 5), "--seconds", "1", "--trace", "0"]))
""")


def test_slow_ingest_stops_the_run_at_the_deadline(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(tinybench.ROOT / "src"))
    t = time.perf_counter()
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tinybench.BENCH / "tests"),
         str(tmp_path)], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 3, p.stderr[-4000:]
    assert time.perf_counter() - t < 90
    assert "{" not in p.stdout
    line = [x for x in p.stderr.splitlines() if "phase=deadline" in x]
    assert len(line) == 1 and "projected_ingest_end_s=" in line[0]
    assert "columns=4096/204800" in line[0]
    assert "phase=flush" not in p.stderr


def test_column_by_column_ingest_projects_inside_a_chunk(monkeypatch):
    monkeypatch.setattr(cell, "SETUP_DEADLINE_S", 5.0)
    monkeypatch.setattr(cell, "PROJECT_EVERY_S", 0.2)

    class Slow:
        added = 0

        def add(self, *column):
            time.sleep(0.005)
            self.added += 1

    cfg = dict(json.loads(
        (tinybench.BENCH / "configs" / "opendata_n256.json").read_text()),
        **tinybench.TINY)
    cfg["tables"] = cell.INGEST_CHUNK
    svc = Slow()
    t = time.perf_counter()
    with pytest.raises(cell.DeadlineMissed, match="projected_ingest_end_s="):
        cell.ingest(svc, Lake(cfg, 3))
    # About 10 s of adds in the one chunk; the projection stops it early.
    assert 0 < svc.added < cell.INGEST_CHUNK // 2
    assert time.perf_counter() - t < 5


class _Counted:
    """A lake of ``chunks`` empty chunks that ingest hands on as they are."""

    def __init__(self, chunks):
        self.columns = chunks * cell.INGEST_CHUNK
        self.discrete = np.zeros(self.columns, bool)
        self.made = []

    def chunk(self, c0, c1, device=None):
        self.made.append(device)
        return {"keys": None, "vals_f": None, "vals_c": None}


class _SlowStart:
    """A service whose first call on each of ``chips`` chips takes
    ``first`` seconds and every later one ``then``."""

    def __init__(self, chips, first, then):
        self.chips, self.first, self.then = chips, first, then
        self.seen, self.calls = set(), 0

    def add_columns(self, tables, key_column, value_column, keys, *rest):
        self.calls += 1
        chip = int(tables[0][1:]) // cell.INGEST_CHUNK % self.chips
        time.sleep(self.then if chip in self.seen else self.first)
        self.seen.add(chip)


@pytest.mark.parametrize("chips,first", [(1, 2.0), (4, 0.5)])
def test_a_slow_first_chunk_on_each_chip_does_not_trip(chips, first):
    # 4,000 chunks: a rate taken with the first calls in it projects
    # 4,000 x 2 s (1 chip) or 1,000 x 2 s (4 chips) of ingest; the
    # steady rate, 1 ms a chunk, projects about 4 s.
    lake, svc = _Counted(4000), _SlowStart(chips, first, 0.001)
    devices = [f"chip{i}" for i in range(chips)]
    seconds = cell.ingest(svc, lake, devices, time.perf_counter())
    assert svc.calls == 4000 and seconds < 60
    assert lake.made[:8] == [devices[i % chips] for i in range(8)]


def test_a_slow_first_chunk_still_counts_as_time_spent(monkeypatch):
    # Time already spent counts before the rate does: a first call that
    # ends past the deadline stops ingest at once.
    monkeypatch.setattr(cell, "SETUP_DEADLINE_S", 1.0)
    svc = _SlowStart(1, 1.5, 0.001)
    with pytest.raises(cell.DeadlineMissed, match="columns=2048/"):
        cell.ingest(svc, _Counted(4000))
    assert svc.calls == 1
