"""95th percentile of the submit→placement latency over every job due in
an open-loop window (one never placed enters with its wait so far)."""

import numpy as np


def read(ctx):
    if ctx.arrivals != "open" or not ctx.latencies_s:
        return None
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3
