"""Per-layer time from the profiler trace, per query served.

The program names some of its work on the profiler's clock: the
estimator kernel's device ops carry its ``pallas_call`` name as their
own name, and the service's host spans are ``TraceAnnotation`` events
(``discovery.dispatch``, ``discovery.rank``, ...).  Device ops nest (a
``while`` op spans the ops of its body), so a layer's device time is the
union of its matching intervals on each chip, averaged over the chips; a
host span's time is the union of its events.  Where the trace holds
nothing that matches (a program without these names), the reading is
None, not 0.
"""

from __future__ import annotations

import re

import numpy as np

from harness import tracing


def _span_s(iv) -> float:
    u = tracing._union(np.asarray(iv, np.int64).reshape(-1, 2))
    return float((u[:, 1] - u[:, 0]).sum()) * 1e-9


def device_ms_per_query(ctx: dict, pattern: re.Pattern) -> float | None:
    """Milliseconds per query of the device ops whose text (name, then
    naming stats) ``pattern`` matches: the union on each chip, averaged
    over the chips."""
    events = ctx.get("events")
    if not events or not events["devices"] or not ctx["queries"]:
        return None
    per_chip = [[(s, e) for _, s, e, text in evs if pattern.search(text)]
                for evs in events["devices"].values()]
    if not any(per_chip):
        return None
    return float(np.mean([_span_s(iv) for iv in per_chip])) * 1e3 \
        / ctx["queries"]


def host_ms_per_query(ctx: dict, name: str) -> float | None:
    """Milliseconds per query of the host events named ``name``: read
    only from a trace that holds a device plane, as a run without a chip
    reports no device-trace metric."""
    events = ctx.get("events")
    if not events or not events["devices"] or not ctx["queries"]:
        return None
    iv = [(s, e) for n, s, e in events["host"] if n == name]
    if not iv:
        return None
    return _span_s(iv) * 1e3 / ctx["queries"]
