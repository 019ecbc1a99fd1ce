"""Seconds to upload the lake into the device store (``index._DeviceStore``):
the first ``plan()`` of each target type, until its arrays are ready."""


def read(ctx):
    return ctx["flush_s"]
