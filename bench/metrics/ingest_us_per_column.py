"""Host microseconds per candidate column added through
``DiscoveryService.add`` (``SketchIndex.add`` and ``build_sketch``),
from the benchmark's clock around its ingest loop."""


def read(ctx):
    return ctx["ingest_s"] / ctx["columns"] * 1e6
