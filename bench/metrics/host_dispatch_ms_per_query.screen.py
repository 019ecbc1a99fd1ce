"""Host milliseconds per query of dispatching a bucket: the service's
``discovery.dispatch`` spans (plan-cache lookup, train staging, the
enqueue of the bucket's programs), on the profiler's clock."""

from harness import stages


def read(ctx):
    return stages.host_ms_per_query(ctx, "discovery.dispatch")
