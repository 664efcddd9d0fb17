"""Control plane: mean wall time of one service tick (obs
``tick.service.us``) in the window, in open cells."""


def read(ctx):
    if ctx.arrivals != "open" or not ctx.obs:
        return None
    count, total = ctx.obs.get("tick.service.us", (0, 0))
    return total / count / 1e3 if count else None
