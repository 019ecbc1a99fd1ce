"""Share of the traced window in which no operation ran on a chip, per
chip: one minus the busy time averaged over the cell's chips
(``tracing.reduce``) over the window."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / ctx["window_s"])
