"""Kernels: device time of one ``_rd_strip_kernel`` launch (the bitonic
sort and prefix walk of one strip)."""

from bench.trace import find


def read(ctx):
    if not ctx.trace:
        return None
    n, total = find(ctx.trace["ops"], "_rd_strip_call", "custom-call")
    return total / n * 1e6 if n else None
