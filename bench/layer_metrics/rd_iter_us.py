"""Kernels: device time of the RD program (``_rd_device``) in the trace
over the window's loop iterations (obs counter ``rd.iters``): one
iteration of the deletion or dedup loop, strip or not."""

from bench.trace import find


def read(ctx):
    if not ctx.trace or not ctx.obs:
        return None
    _, iters = ctx.obs.get("rd.iters", (0, 0))
    _, prog_s = find(ctx.trace["programs"], "_rd_device")
    return prog_s / iters * 1e6 if iters and prog_s > 0 else None
