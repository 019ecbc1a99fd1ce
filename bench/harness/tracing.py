"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

From the ``.xplane.pb`` a ``jax.profiler`` session writes:

* device planes (``/device:TPU:<i>``): the events of their ``XLA Ops``
  line are the operations that ran on that chip.  Busy time is the
  union of their intervals (overlapping events count once), averaged
  over the chips;
* kernel time (:func:`kernel_seconds`): the summed durations of device
  operations whose name contains one of a kernel's name patterns;
* the breakdown: the device operations that took the most time, and
  the longest idle gaps of the first chip, each named by the innermost
  host event that spans half of it or more (what the host was doing
  meanwhile).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) intervals; returns the disjoint union, sorted."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


# Event stats whose text a kernel's name patterns are also matched in
# (the HLO op, its long form and the framework op that made it).
_NAME_STATS = ("long_name", "hlo_op", "tf_op", "kernel_details")


def _text(e) -> str:
    parts = [e.name]
    for key, val in e.stats:
        if key in _NAME_STATS and isinstance(val, str):
            parts.append(val)
    return " | ".join(parts)


def read_events(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns, text)]},
    "host": [(name, start_ns, end_ns)]}; ``text`` is the name with its
    naming stats, what kernel patterns are matched in."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.end_ns, _text(e))
                               for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def kernel_seconds(events: dict, patterns) -> float:
    """Device seconds of the operations whose name contains a pattern,
    summed over the chips."""
    return sum((e - s) * 1e-9 for evs in events["devices"].values()
               for _, s, e, text in evs if any(p in text for p in patterns))


def reduce(events: dict, top: int = 10) -> dict:
    """Busy seconds (mean over chips) and the breakdown lists."""
    devs = events["devices"]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = []
    per_op = defaultdict(float)
    first = None
    for plane in sorted(devs):
        evs = devs[plane]
        iv = np.asarray([(s, e) for _, s, e, _ in evs],
                        np.int64).reshape(-1, 2)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        if first is None:
            first = u
        for name, s, e, _ in evs:
            per_op[name] += (e - s) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if first is not None and len(first) > 1:
        g = np.stack([first[:-1, 1], first[1:, 0]], axis=1)
        g = g[np.argsort(-(g[:, 1] - g[:, 0]), kind="stable")[:top]]
        names = [n for n, _, _ in events["host"]]
        hs = np.asarray([s for _, s, _ in events["host"]], np.int64)
        he = np.asarray([e for _, _, e in events["host"]], np.int64)
        for s, e in g:
            ov = np.minimum(e, he) - np.maximum(s, hs)
            name = "host: no event"
            if len(ov) and ov.max() > 0:
                # The innermost (shortest) event over half the gap or
                # more; else the one that overlaps it most.
                half = np.flatnonzero(2 * ov >= e - s)
                i = half[np.argmin((he - hs)[half])] if len(half) \
                    else int(np.argmax(ov))
                name = names[i]
            gaps.append([name, float(e - s) * 1e-9])
    return {"busy_s": float(np.mean(busy)), "chips": len(devs), "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": gaps}
