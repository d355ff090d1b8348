"""The persistent traversal kernel's share of its roofline, in % (closed loop)."""
from bench.lib import layer


def read(ctx):
    return layer.traverse_roofline(ctx)
