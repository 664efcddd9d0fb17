"""Device adapters: device time of the RD program (``_rd_device``) in the
trace over the strip-kernel launches, which equal the strips: one strip
of the while loop, the kernel and the scatters and gathers around it."""

from bench.trace import find


def read(ctx):
    if not ctx.trace:
        return None
    _, prog_s = find(ctx.trace["programs"], "_rd_device")
    strips, _ = find(ctx.trace["ops"], "_rd_strip_call", "custom-call")
    return prog_s / strips * 1e6 if strips and prog_s > 0 else None
