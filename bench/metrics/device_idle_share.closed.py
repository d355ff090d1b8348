"""Share of the traced window in which no operation ran on the device, in % (closed loop)."""
from bench.lib import layer


def read(ctx):
    return layer.idle_share(ctx)
