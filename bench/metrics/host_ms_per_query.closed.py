"""Host milliseconds per completed request that the serving layer spends while the device is idle (closed loop)."""
from bench.lib import layer


def read(ctx):
    return layer.host_ms_per_query(ctx)
