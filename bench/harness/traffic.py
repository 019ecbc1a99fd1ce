"""The general traffic generator: reads a mix from ``bench/traffic/<name>.json``.

A mix is a batch augmentation job (FeatNavigator, arXiv:2406.09534;
Hippasus, arXiv:2602.02025): one caller screens base tables against the
whole lake, one ``submit`` per base table with one query per target
column, and sends the next table when the last one returns (a closed
loop).  A base table is the train side of the configuration's table
pairs: ``rows_per_table`` rows whose keys are Zipf draws modulo its own
key count, that count uniform between the configuration's
``n_keys_min`` and ``n_keys_max``.  Its ``targets`` are numeric or
categorical as the mix lists them, each related to the lake's latent
by its own strength, uniform in (0, 1).  ``distinct_requests`` base
tables are built at set-up and sent in turn; warm-up sends others.
A mix may also set the service's gates (``GATES``); one that departs
from the service's default the harness passes to ``submit`` and to the
lake's reference alike.  ``complete_checks`` base tables drawn from the
seed are checked for passing columns their lists miss: among every
column of the lake, or, where the mix sets ``complete_columns``, among
that many drawn from the seed.

Per seed the work is one multiset in another order: the key counts and
strengths are evenly spaced quantiles, placed by the seed.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

from harness.lake import Lake, Query, permutation, quantiles

_WARM_TABLE = 1 << 20      # warm-up base tables

# Serving options of ``DiscoveryService.submit`` that change its answers,
# which a mix may set besides ``top_k`` and ``min_join``, with the
# service's defaults.
GATES = {"min_containment": 0.0}


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec.get("kind") != "closed":
        raise ValueError(f"{path}: kind must be 'closed'")
    if not set(spec["targets"]) <= {"numeric", "categorical"}:
        raise ValueError(f"{path}: targets are 'numeric' or 'categorical'")
    if not 0.0 <= float(spec.get("min_containment", 0.0)) <= 1.0:
        raise ValueError(f"{path}: min_containment lies in [0, 1]")
    if int(spec.get("complete_columns", 1)) < 1:
        raise ValueError(f"{path}: complete_columns is at least 1")
    return spec


def serving_options(spec: dict) -> dict:
    opts = {"top_k": int(spec["top_k"]), "min_join": int(spec["min_join"])}
    opts.update((g, spec[g]) for g, default in GATES.items()
                if spec.get(g, default) != default)
    return opts


def gates(opts: dict) -> dict:
    """The gates among serving options ``opts``: those that depart from
    the service's defaults."""
    return {g: opts[g] for g in GATES if g in opts}


def requests(lake: Lake, spec: dict, warm: bool = False) -> list[list[Query]]:
    """The mix's base tables, each as its list of target queries."""
    cfg = lake.cfg
    R = int(spec["distinct_requests"])
    T = len(spec["targets"])
    s = lake.seed
    n_keys = np.floor(quantiles(R, cfg["n_keys_min"], cfg["n_keys_max"])
                      ).astype(np.int64)[permutation(s, 10 + warm, R)]
    alpha = quantiles(R * T, 0.0, 1.0)[permutation(s, 12 + warm, R * T)]
    base = _WARM_TABLE if warm else 0
    return [[Query(lake, base + i, n_keys[i], j, alpha[i * T + j],
                   kind == "categorical")
             for j, kind in enumerate(spec["targets"])] for i in range(R)]


def run_closed(submit, sketches: list, seconds: float) -> dict:
    """Closed loop: ``submit(sketches) -> results`` until ``seconds``
    have passed; the window ends at the first completion after that.
    ``request_s`` holds each request's time, submit to completion."""
    done, request_s = [], []
    t0 = t_end = time.perf_counter()
    i = 0
    while True:
        res = submit(sketches[i % len(sketches)])
        done.append((i, res))
        i += 1
        t_prev, t_end = t_end, time.perf_counter()
        request_s.append(t_end - t_prev)
        if t_end - t0 >= seconds:
            break
    return {"t0": t0, "t_end": t_end, "done": done, "request_s": request_s}


class Compiles:
    """Counts programs lowered in this process (each new shape of each
    jitted function, whether compiled or loaded from the cache)."""

    def __init__(self):
        import jax
        self.n = 0
        self._lock = threading.Lock()

        def on(event, duration, **_):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                with self._lock:
                    self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on)


def warm(name: str, fn, compiles: Compiles, *, min_calls: int = 2,
         max_calls: int = 12, log=print) -> int:
    """Call ``fn(i)`` until ``min_calls`` calls in a row compile nothing
    (the program's shortlist widths settle over its first calls, and
    each new width compiles), at most ``max_calls``.  Returns the calls
    made; the window's own count of compiles shows whether it settled."""
    quiet = 0
    for i in range(max_calls):
        before = compiles.n
        t = time.perf_counter()
        fn(i)
        new = compiles.n - before
        log(f"warmup {name} call={i} compiled={new} "
            f"seconds={time.perf_counter() - t:.3f}")
        quiet = quiet + 1 if new == 0 else 0
        if quiet >= min_calls:
            return i + 1
    log(f"warmup {name}: still compiling after {max_calls} calls")
    return max_calls
