"""Load generator: 95th percentile of how late each step was submitted
after its due time (open loop).  A growing lag means the driver, not the
offered rate, set the load."""

import numpy as np


def read(ctx):
    if ctx.arrivals != "open" or not ctx.gen_lags_s:
        return None
    return float(np.percentile(ctx.gen_lags_s, 95)) * 1e3
