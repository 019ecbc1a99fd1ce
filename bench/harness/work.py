"""Work that the traffic requires of the kNN estimator kernel.

Counted from the shapes, whatever implements the estimate: for each
(query, candidate) pair whose join reaches ``min_join`` and goes to a
kNN estimator, an exact estimate over the sample width ``P`` the
scorers receive (the sketch size) forms every ordered pair (i, j) of
the sample once.  Selection of the k smallest distances is not counted
(it depends on the implementation).

Per ordered pair:

* mixed KSG (numeric candidate, numeric target): |dx| and |dy| (a
  subtraction and an absolute value each), their maximum, and five
  ball and tie counts (a comparison and an addition each): 15 ops;
* DC-KSG (one side categorical): |dy| (2), the class comparison (1),
  the class-size count (1) and the ball count (a comparison and an
  addition): 6 ops.

Bytes per pair of columns: the sample's values and mask (3 x 4 x P) in,
and per row the radius and the counts out (mixed: 6 x 4 x P; DC-KSG:
3 x 4 x P).  Categorical pairs use the plug-in estimator and no kNN
work; they are not counted.
"""

from __future__ import annotations

OPS_PER_PAIR = {"mixed": 15, "dc_x": 6, "dc_y": 6}
OUT_WORDS = {"mixed": 6, "dc_x": 3, "dc_y": 3}


def lane_work(kind: str, P: int) -> tuple[float, float]:
    """(operations, bytes) of one estimate of ``kind`` at width P."""
    if kind not in OPS_PER_PAIR:
        return 0.0, 0.0
    return float(OPS_PER_PAIR[kind] * P * P), float((3 + OUT_WORDS[kind]) * 4 * P)


def roofline_pct(lanes: dict, P: int, kernel_s: float, peaks: dict):
    """Share of the chip's roofline the kernel reached, in percent:
    the least time the required work could take (operations over the
    peak FLOP/s or bytes over the peak bandwidth, whichever is larger)
    over the kernel's measured time.  None when there is nothing to
    read."""
    flops = sum(lane_work(k, P)[0] * n for k, n in lanes.items())
    nbytes = sum(lane_work(k, P)[1] * n for k, n in lanes.items())
    if kernel_s <= 0 or flops <= 0:
        return None
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
