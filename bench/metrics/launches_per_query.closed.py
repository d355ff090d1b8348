"""Persistent-driver launches per completed request, from dispatch_counters()["bodies"] over the window."""
from bench.lib import layer


def read(ctx):
    return layer.launches_per_query(ctx)
