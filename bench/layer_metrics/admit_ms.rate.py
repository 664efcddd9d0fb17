"""Admission + policy: mean wall time of placing one job (obs
``sched.overhead_us``, a burst's time shared by its jobs), in
open cells."""


def read(ctx):
    if ctx.arrivals != "open" or not ctx.obs:
        return None
    count, total = ctx.obs.get("sched.overhead_us", (0, 0))
    return total / count / 1e3 if count else None
