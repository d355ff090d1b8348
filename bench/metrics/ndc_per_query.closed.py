"""Mean distance computations (NDC) per completed request, from the program's per-request counter (closed loop)."""
from bench.lib import layer


def read(ctx):
    return layer.ndc_per_query(ctx)
