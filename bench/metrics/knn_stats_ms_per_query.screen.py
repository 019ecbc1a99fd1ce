"""Device milliseconds per query of the kNN estimator kernel, per chip
(the union on each chip, averaged over the cell's chips): the ops
whose own name is one of its ``pallas_call`` names
(``kernels/knn_stats/kernel.py``: ``knn_stats_radius_counts`` and its
suffixed variants).  The name is matched at the start of the op's text
only: on a TPU an op's name is its HLO instruction, which cites its
operands, so an op that reads the kernel's output names the kernel too
and is not the kernel."""

import re

from harness import stages

KERNEL = re.compile(r"^%?knn_stats_radius_counts")


def read(ctx):
    return stages.device_ms_per_query(ctx, KERNEL)
