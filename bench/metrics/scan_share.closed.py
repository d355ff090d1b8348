"""Share of completed requests the planner sent to the pre-filter scan, in %."""
from bench.lib import layer


def read(ctx):
    return layer.scan_share(ctx)
