"""Median submit→placement latency of the jobs due in an open-loop window."""

import numpy as np


def read(ctx):
    if ctx.arrivals != "open" or not ctx.latencies_s:
        return None
    return float(np.percentile(ctx.latencies_s, 50)) * 1e3
