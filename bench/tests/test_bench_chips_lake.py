"""The ``table2_chips`` lake against ``table2`` at a CPU size, on one and
on four devices: the same chunks bit for bit, the same reference, each
chunk handed to ``add_columns`` whole on its chip, and the same index
built through the column-by-column fallback.

The device count is fixed when JAX starts, so each count runs in a
child process with ``--xla_force_host_platform_device_count``."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import tinybench

SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, sys.argv[1])
    import tinybench
    import jax
    import numpy as np
    from harness import cell, reference, traffic
    from repro.core.discovery import DiscoveryService

    cfg = json.loads((tinybench.BENCH / "configs" / "opendata_n256.json")
                     .read_text())
    cfg.update(tinybench.TINY, tables=2 * cell.INGEST_CHUNK + 100)
    seed = 2**31 + 21
    chips = cell.load_module(str(tinybench.ROOT), "lakes", "table2_chips")
    table2 = cell.load_module(str(tinybench.ROOT), "lakes", "table2")
    devices = jax.devices()
    host = table2.Lake(cfg, seed)
    host.keep_rows()
    lake = chips.Lake(cfg, seed)
    out = {"devices": len(devices), "chunks": []}
    for a in range(0, lake.columns, cell.INGEST_CHUNK):
        b = min(a + cell.INGEST_CHUNK, lake.columns)
        on = devices[a // cell.INGEST_CHUNK % len(devices)]
        h, d = host.chunk(a, b, on), lake.chunk(a, b, on)
        out["chunks"].append({
            "c0": a,
            "on": [str(next(iter(d[f].devices()))) for f in d],
            "committed": all(d[f].committed for f in d),
            "equal": {f: bool(np.array_equal(h[f], np.asarray(d[f])))
                      for f in h}})
    out["want_on"] = [str(devices[(c["c0"] // cell.INGEST_CHUNK)
                                 % len(devices)]) for c in out["chunks"]]

    # The reference: join sizes, ambiguity and MI of every column.
    n, k, mj = int(cfg["n"]), int(cfg["k"]), 12
    ls_h = reference.LakeSketches(host, n)
    ls_d = chips.LakeSketches(lake, n)
    spec = {"targets": ["numeric", "categorical"], "distinct_requests": 2}
    ref = []
    for q in (q for r in traffic.requests(host, spec) for q in r):
        a_h = table2.answer(host, ls_h, q, n=n, k=k, min_join=mj)
        a_d = chips.answer(lake, ls_d, q, n=n, k=k, min_join=mj)
        est = ~np.isnan(a_h.mi)
        ref.append({
            "js": bool(np.array_equal(a_h.js, a_d.js)),
            "ambiguous": bool(np.array_equal(a_h.ambiguous, a_d.ambiguous)),
            "estimated": int(est.sum()),
            "same_estimated": bool(np.array_equal(est, ~np.isnan(a_d.mi))),
            "mi_gap": float(np.max(np.abs(a_h.mi[est] - a_d.mi[est])))})
    out["reference"] = ref

    # Ingest: whole chunks to add_columns; the add loop where it is missing.
    class Bulk:
        calls = []

        def add_columns(self, tables, key_column, value_column, keys,
                        vals_f, vals_c, discrete):
            self.calls.append({
                "tables": [tables[0], tables[-1], len(tables)],
                "jax": all(isinstance(x, jax.Array)
                           for x in (keys, vals_f, vals_c)),
                "shapes": [list(x.shape) for x in (keys, vals_f, vals_c)],
                "on": [str(next(iter(x.devices())))
                       for x in (keys, vals_f, vals_c)],
                "discrete": discrete.tolist()
                == lake.discrete[int(tables[0][1:]):
                                 int(tables[-1][1:]) + 1].tolist()})

    cell.ingest(Bulk(), lake, devices)
    out["bulk"] = Bulk.calls

    def index_of(lk):
        svc = DiscoveryService(n=n, k=k, sig_width=int(cfg["sig_width"]),
                               agg=cfg["agg"], method=cfg["method"])
        cell.ingest(svc, lk, devices)
        ix = svc.index
        return ix.meta, [ix._keys, ix._vals_f, ix._vals_u, ix._masks,
                         ix._discrete]

    meta_h, bufs_h = index_of(host)
    meta_d, bufs_d = index_of(lake)
    out["index"] = {
        "columns": [len(meta_h), len(meta_d)],
        "meta": meta_h == meta_d,
        "buffers": all(len(x) == len(y) and all(
            np.array_equal(u, v) for u, v in zip(x, y))
            for x, y in zip(bufs_h, bufs_d))}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def rehearsal(request):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count="
               f"{request.param}",
               PYTHONPATH=str(tinybench.ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tinybench.BENCH / "tests")],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == request.param
    return out


def test_chunks_are_table2s_bit_for_bit_on_their_chips(rehearsal):
    chunks = rehearsal["chunks"]
    assert [c["c0"] for c in chunks] == [0, 2048, 4096]
    for c, want in zip(chunks, rehearsal["want_on"]):
        assert c["equal"] == {"keys": True, "vals_f": True, "vals_c": True}
        assert c["committed"] and c["on"] == [want] * 3


def test_reference_matches_table2s(rehearsal):
    ref = rehearsal["reference"]
    assert len(ref) == 4
    for r in ref:
        assert r["js"] and r["ambiguous"] and r["same_estimated"]
        assert r["estimated"] > 100
        assert r["mi_gap"] <= 1e-6


def test_a_device_chunk_reaches_add_columns_whole(rehearsal):
    calls = rehearsal["bulk"]
    assert [c["tables"] for c in calls] == [
        ["c0", "c2047", 2048], ["c2048", "c4095", 2048],
        ["c4096", "c4195", 100]]
    for c, want in zip(calls, rehearsal["want_on"]):
        assert c["jax"] and c["discrete"]
        assert c["shapes"] == [[c["tables"][2], tinybench.TINY[
            "rows_per_table"]]] * 3
        assert c["on"] == [want] * 3


def test_fallback_builds_the_same_index_from_device_chunks(rehearsal):
    ix = rehearsal["index"]
    assert ix["columns"] == [4196, 4196]
    assert ix["meta"] and ix["buffers"]


def test_rows_past_the_gathered_tail_fall_back_to_a_full_gather():
    import jax

    from harness import cell
    cfg = json.loads((tinybench.BENCH / "configs" / "opendata_n256.json")
                     .read_text())
    cfg.update(tinybench.TINY, tables=64)
    chips = cell.load_module(str(tinybench.ROOT), "lakes", "table2_chips")
    table2 = cell.load_module(str(tinybench.ROOT), "lakes", "table2")
    host = table2.Lake(cfg, 5)
    lake = chips.Lake(cfg, 5)
    assert lake.tail < cfg["rows_per_table"]
    lake.tail = 8       # far fewer than a row's draws past the head
    h, d = host.chunk(0, 64), lake.chunk(0, 64)
    for f in h:
        assert (h[f] == jax.device_get(d[f])).all(), f
