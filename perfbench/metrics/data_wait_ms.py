"""Data layer: host milliseconds per step that the window spent in the
Prefetcher's ``next()`` (the batch hand-off and its device placement),
from the benchmark's own span around each call."""


def read(ctx):
    waits = ctx["data_wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
