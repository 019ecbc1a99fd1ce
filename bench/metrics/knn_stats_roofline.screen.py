"""Share of the chip's roofline reached by the kNN estimator kernel
(``kernels/knn_stats``), per chip: the least time the traffic's required
kNN work could take at one chip's published peaks (``harness.work``,
counted from the lake the benchmark made), over the kernel's device
time in the trace summed over the cell's chips."""

from harness import tracing, work

# What the kernel's device operations carry in their names in the trace:
# its Pallas kernel function, or the Mosaic custom call (the scoring
# programs run no other Pallas kernel).
KERNEL_NAMES = ("radius_counts", "tpu_custom_call", "pallas")


def read(ctx):
    lanes = ctx.get("knn_lanes")
    if not lanes or not ctx.get("events") or not ctx.get("peaks"):
        return None
    kernel_s = tracing.kernel_seconds(ctx["events"], KERNEL_NAMES)
    return work.roofline_pct(lanes, ctx["P"], kernel_s, ctx["peaks"])
