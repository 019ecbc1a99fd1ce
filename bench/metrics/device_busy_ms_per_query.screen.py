"""Device milliseconds per query served in the traced window, per chip:
on each chip the union of the intervals in which an operation ran,
averaged over the cell's chips (``tracing.reduce``), over the queries
served (no program names, so a refactor cannot silence it)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx["queries"]:
        return None
    return trace["busy_s"] * 1e3 / ctx["queries"]
