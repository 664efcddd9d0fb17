"""Device adapters: classes the RD device program's strips moved per loop
iteration (obs counters ``rd.moved`` over ``rd.iters``, both carried out of
the device loops and observed once per job)."""


def read(ctx):
    if not ctx.obs:
        return None
    jobs, moved = ctx.obs.get("rd.moved", (0, 0))
    _, iters = ctx.obs.get("rd.iters", (0, 0))
    return moved / iters if jobs and iters else None
