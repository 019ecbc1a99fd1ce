"""Host milliseconds per query of ranking a delivered bucket: the
service's ``discovery.rank`` spans (fencing, ``_rank``, the stat
commit), on the profiler's clock."""

from harness import stages


def read(ctx):
    return stages.host_ms_per_query(ctx, "discovery.rank")
