"""One run of one cell: set-up, warm-up, the measured window, the check.

``run`` drives the discovery service as a user would: it ingests the
seeded lake one generated chunk per call, flushes it to the device,
warms every shape the cell's traffic uses, measures for ``seconds``,
reads the device's peak memory, frees the service and then holds the
window's answers against the plain reference.

A configuration states its deployment in data: the cell's ``chips``
lay the service out (one chip, or a one-axis ``("data",)`` mesh over
the first ``chips`` devices), and its ``"lake"`` names the module under
``bench/lakes/`` that makes the lake, the mix's base tables and the
reference (``table2`` where it names none).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import compare, tracing, traffic
from harness.peaks import peaks

INGEST_CHUNK = 2048

# A run is stopped 360 s after it starts.  After ingest come the flush,
# the query sketches, warm-up, the window and the reference check, about
# as long again at today's cells (ingest ends about 80 s in, the result
# at about 150 s); so ingest has to end within half of the limit.  A
# service that ingests a lake too slowly for that (column by column
# where the cell's lake needs a bulk path) stops the run within seconds,
# with no result, instead of being killed at the limit.
SETUP_DEADLINE_S = 180.0
# Where ingest goes column by column, it projects its end at least this
# often, also inside a chunk.
PROJECT_EVERY_S = 30.0


class NoChip(RuntimeError):
    pass


class DeadlineMissed(RuntimeError):
    """Ingest projects its end past ``SETUP_DEADLINE_S``."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(root: str, bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of workload ``name``, found by
    name: the configuration's ``file`` and ``bench/traffic/<mix>.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, cfgs[cell["config"]]["file"]))
    spec = traffic.load(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, cfg, spec


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones with the
    trace off, per-layer ones with it on."""
    name = cell["name"]

    def listed(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}

    def reports(m):
        if "workloads" in m:
            return name in m["workloads"]
        return m["moves"] in moved

    return [m for m in bench["per_layer"] if reports(m)]


def load_module(root: str, folder: str, name: str):
    """``bench/<folder>/<name>.py``, loaded by its path."""
    path = os.path.join(root, "bench", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, metric: str):
    return load_module(root, "metrics", metric).read


def load_lake(root: str, cfg: dict):
    """The lake module the configuration names (``bench/lakes/``)."""
    return load_module(root, "lakes", cfg.get("lake", "table2"))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def ingest(svc, lake, devices=(), t_start: float | None = None) -> float:
    """Add every column of the lake, one chunk of ``INGEST_CHUNK``
    columns at a time; the next chunk is made on a second thread while
    this one is added.  Chunk ``i`` is made on the cell's chip
    ``devices[i % len(devices)]`` (``lake.chunk(c0, c1, device)``; a
    host lake ignores the chip, and with no ``devices`` it is None).

    A chunk goes whole to ``svc.add_columns`` where the service has it,
    else to ``svc.add`` column by column, in order, from a host copy.

    After each chunk (and, column by column, at least every
    ``PROJECT_EVERY_S``) ingest projects its end, and raises
    ``DeadlineMissed`` where that lies more than ``SETUP_DEADLINE_S``
    after ``t_start`` (the process start; ingest's own where None).
    The first chunk on each chip compiles the lake's programs and the
    service's, so the rate is taken from the first call to the service
    after those chunks on: until then the projection is the time spent.
    Returns ingest's seconds."""
    C = lake.columns
    t0 = last = time.perf_counter()
    t_start = t0 if t_start is None else t_start
    mark = None         # (time, columns) at the start of the steady rate
    logged = False

    def project(done: int, now: float) -> None:
        nonlocal last, logged
        last = now
        end = now - t_start
        if mark is not None and done > mark[1]:
            end += (C - done) * (now - mark[0]) / (done - mark[1])
        text = (f"projected_ingest_end_s={end:.1f} "
                f"limit_s={SETUP_DEADLINE_S:.0f} columns={done}/{C} "
                f"ingest_s={now - t0:.3f}")
        if end > SETUP_DEADLINE_S:
            raise DeadlineMissed(text)
        if mark is not None and done > mark[1] and not logged:
            logged = True
            log(f"ingest {text}")

    def chip(i: int):
        return devices[i % len(devices)] if len(devices) else None

    bulk = getattr(svc, "add_columns", None)
    bounds = [(a, min(a + INGEST_CHUNK, C)) for a in range(0, C, INGEST_CHUNK)]
    with ThreadPoolExecutor(1) as pool:
        nxt = pool.submit(lake.chunk, *bounds[0], chip(0))
        for i, (a, b) in enumerate(bounds):
            ch = nxt.result()
            if i + 1 < len(bounds):
                nxt = pool.submit(lake.chunk, *bounds[i + 1], chip(i + 1))
            warm = i + 1 >= max(1, len(devices))
            tables = [f"c{c}" for c in range(a, b)]
            if bulk is not None:
                bulk(tables, "k", "v", ch["keys"], ch["vals_f"],
                     ch["vals_c"], lake.discrete[a:b])
            else:
                keys, vf, vc = (np.asarray(ch[f])
                                for f in ("keys", "vals_f", "vals_c"))
                disc = lake.discrete[a:b].tolist()
                for j, table in enumerate(tables):
                    svc.add(table, "k", "v", keys[j],
                            vc[j] if disc[j] else vf[j], disc[j])
                    now = time.perf_counter()
                    if warm and mark is None:
                        mark = (now, a + j + 1)
                    elif now - last >= PROJECT_EVERY_S:
                        project(a + j + 1, now)
            now = time.perf_counter()
            if warm and mark is None:
                mark = (now, b)
            project(b, now)
    return time.perf_counter() - t0


def flush(svc) -> float:
    import jax
    t = time.perf_counter()
    for y_disc in (False, True):
        plan = svc.index.plan(y_disc)
        jax.block_until_ready([g.arrays for g in plan.groups])
    return time.perf_counter() - t


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Stage:
    """A service with the cell's lake ingested and its queries sketched."""


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no count)."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def set_up(root: str, bench: dict, name: str, *, seed: int, t_start: float,
           require_chip: bool = True, service_factory=None) -> Stage:
    """Everything before warm-up: device check, lake, the service on the
    cell's chips, ingest, flush, the query sketches the traffic will
    send."""
    import jax
    from repro.core.discovery import DiscoveryService
    from repro.core.sketch import build_sketch

    st = Stage()
    st.cell, st.cfg, st.spec = cell_spec(root, bench, name)
    cfg, spec = st.cfg, st.spec
    st.devices = jax.devices()
    dev = st.devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(st.devices) < int(st.cell["chips"])):
        raise NoChip(f"cell {name} needs {st.cell['chips']} TPU chip(s); "
                     f"JAX found {len(st.devices)} {dev.platform} device(s)")
    st.device = {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(st.devices)}
    log(f"phase=devices platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(st.devices)} jax={jax.__version__}")
    st.compiles = traffic.Compiles()
    st.seed = seed
    n = st.n = int(cfg["n"])
    st.opts = traffic.serving_options(spec)
    st.lake_mod = lake_mod = load_lake(root, cfg)
    lake_mod.check_options(traffic.gates(st.opts))

    chips = st.chips = int(st.cell["chips"])
    t = time.perf_counter()
    st.lake = lake_mod.Lake(cfg, seed)
    st.lake.keep_rows()
    log(f"phase=lake columns={st.lake.columns} "
        f"seconds={time.perf_counter() - t:.3f}")
    layout = {}
    if chips > 1:
        from repro.parallel.compat import make_mesh
        layout["mesh"] = make_mesh((chips,), ("data",),
                                   devices=st.devices[:chips])
        log(f"phase=mesh axis=data devices={chips}")
    make = service_factory or DiscoveryService
    st.svc = make(n=n, k=int(cfg["k"]), sig_width=int(cfg["sig_width"]),
                  agg=cfg["agg"], method=cfg["method"], **layout)
    st.ingest_s = ingest(st.svc, st.lake, st.devices[:chips], t_start)
    log(f"phase=ingest columns={len(st.svc)} seconds={st.ingest_s:.3f}")
    st.flush_s = flush(st.svc)
    log(f"phase=flush seconds={st.flush_s:.3f}")

    def sketch(q):
        return build_sketch(q.keys, q.y, n=n, method=cfg["method"],
                            side="train", value_is_discrete=q.discrete)

    t = time.perf_counter()
    st.requests = lake_mod.requests(st.lake, spec)
    st.req_sk = [[sketch(q) for q in r] for r in st.requests]
    st.warm_sk = [[sketch(q) for q in r]
                  for r in lake_mod.requests(st.lake, spec, warm=True)[:4]]
    log(f"phase=sketches seconds={time.perf_counter() - t:.3f}")
    return st


def warm_up(st: Stage) -> int:
    """Run the cell's own traffic until it compiles nothing more."""
    return traffic.warm("submit", lambda i: st.svc.submit(
        st.warm_sk[i % len(st.warm_sk)], **st.opts), st.compiles,
        min_calls=4, log=log)


def run(root: str, bench: dict, name: str, *, seed: int, seconds: float,
        trace: bool, t_start: float, require_chip: bool = True,
        service_factory=None) -> dict:
    import jax

    st = set_up(root, bench, name, seed=seed, t_start=t_start,
                require_chip=require_chip, service_factory=service_factory)
    cell, cfg, spec, lake = st.cell, st.cfg, st.spec, st.lake
    svc, compiles, device, n = st.svc, st.compiles, st.device, st.n
    t = time.perf_counter()
    calls = warm_up(st)
    t_window = time.perf_counter()
    log(f"phase=warmup calls={calls} seconds={t_window - t:.3f}")
    setup_s = t_window - t_start
    log(f"phase=setup seconds={setup_s:.3f}")

    # -- the measured window --------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    before = compiles.n
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_w = time.perf_counter()
    out = traffic.run_closed(lambda sk: svc.submit(sk, **st.opts),
                             st.req_sk, seconds)
    window_s = time.perf_counter() - t_w
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.n - before
    print(f"window_compiles={window_compiles} window_s={window_s:.3f}",
          flush=True)
    log(f"phase=window compiles={window_compiles} seconds={window_s:.3f}")

    device["memory_peak_bytes"] = memory_peak(st.devices[:st.chips])
    del svc, st.svc
    gc.collect()

    # -- end-to-end metrics ---------------------------------------------
    done = out["done"]
    T = len(spec["targets"])
    attempted = len(done) * T
    pairs = attempted * lake.columns
    metrics = {"setup_s": {"value": setup_s, "unit": "s"},
               "screen_pairs_per_s": {
                   "value": pairs / (out["t_end"] - out["t0"]),
                   "unit": "pairs/s"}}
    req_s = np.asarray(out["request_s"])
    log(f"phase=screen requests={len(done)} pairs={pairs} "
        f"request_s_min={req_s.min():.4f} "
        f"request_s_median={np.median(req_s):.4f} "
        f"request_s_max={req_s.max():.4f}")

    # -- the check --------------------------------------------------------
    ok, checks, lanes = check(st, done, trace)

    result = {"correct": ok, "attempted": int(attempted), "failed": 0}
    wanted = cell_metrics(bench, cell, trace)
    if trace:
        events = tracing.read_events(tracing.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tracing.reduce(events) if events["devices"] else None
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = window_s
        ctx = {"cell": cell, "config": cfg, "traffic": spec,
               "ingest_s": st.ingest_s, "columns": lake.columns,
               "flush_s": st.flush_s, "events": events, "trace": red,
               "window_s": window_s, "queries": attempted,
               "knn_lanes": lanes, "P": n,
               "peaks": peaks(device["kind"])
               if device["platform"] == "tpu" else None}
        metrics = {}
        for m in wanted:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        names = {m["name"] for m in wanted}
        metrics = {k: v for k, v in metrics.items() if k in names}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def complete_sample(seed: int, columns: int, size: int) -> np.ndarray:
    """The columns, drawn from the seed, that a mix's complete checks
    cover where it sets ``complete_columns`` (sorted)."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 11])
    return np.sort(rng.choice(columns, size=min(columns, size),
                              replace=False))


def check(st: Stage, done: list, count_lanes: bool):
    """Hold the window's answers against the reference: every served
    entry of every completed request (its join size, its MI, the
    order and length of its list), and, on ``complete_checks`` requests
    drawn from the seed, every passing column the list may have missed:
    among all columns, or among the mix's ``complete_columns`` drawn
    from the seed.  Returns (correct, checks, kNN lanes by estimator
    kind or None)."""
    lake, spec, n, k = st.lake, st.spec, st.n, int(st.cfg["k"])
    mj, top_k = st.opts["min_join"], st.opts["top_k"]
    lake_mod, gates = st.lake_mod, traffic.gates(st.opts)
    t = time.perf_counter()
    ls = lake_mod.LakeSketches(lake, n)
    log(f"phase=reference_build seconds={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    joins: dict = {}
    R = len(st.requests)
    rng = np.random.default_rng([int(st.seed) % (1 << 63), 7])
    full = set(rng.choice(len(done), size=min(len(done),
                          int(spec["complete_checks"])), replace=False)
               .tolist())
    sample = None
    if "complete_columns" in spec:
        sample = complete_sample(st.seed, lake.columns,
                                 int(spec["complete_columns"]))
    gap, wrong, entries = 0.0, 0, 0
    for i, (req, res) in enumerate(done):
        for q, served in zip(st.requests[req % R], res):
            only = [compare.column_of(meta) for meta, _, _ in served]
            if i in full:
                only = None if sample is None else np.union1d(sample, only)
            ans = lake_mod.answer(lake, ls, q, n=n, k=k, min_join=mj,
                                  only=only, joins=joins, **gates)
            if ans.skipped:
                continue
            g, w = compare.check_answer(served, ans, top_k=top_k,
                                        min_join=mj)
            gap, wrong, entries = max(gap, g), wrong + w, entries + len(served)
    log(f"phase=checks requests={len(done)} complete={len(full)} "
        f"complete_columns={lake.columns if sample is None else len(sample)} "
        f"served_entries={entries} seconds={time.perf_counter() - t:.3f}")
    lanes = None
    if count_lanes:
        t = time.perf_counter()
        lanes = {}
        for req, _ in done:
            for q in st.requests[req % R]:
                js = lake_mod.join(ls, q, n, joins)[-1]
                for disc in (False, True):
                    kind = lake_mod.kind_of(disc, q.discrete)
                    cnt = int(((js >= mj) & (lake.discrete == disc)).sum())
                    lanes[kind] = lanes.get(kind, 0) + cnt
        log(f"phase=lanes seconds={time.perf_counter() - t:.3f}")
    ok, checks = compare.verdict(gap, wrong)
    return ok, checks, lanes
