"""Kernels: device time of one ``_waterlevel_kernel`` launch (one group's
water level over every server lane, padded launches included)."""

from bench.trace import find


def read(ctx):
    if not ctx.trace:
        return None
    n, total = find(ctx.trace["ops"], "_waterlevel_call", "custom-call")
    return total / n * 1e6 if n else None
