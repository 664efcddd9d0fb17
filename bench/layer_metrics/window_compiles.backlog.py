"""Device adapters: programs lowered (jax.monitoring) while the window
was open, in backlog cells; the warm-up should leave none."""


def read(ctx):
    if ctx.arrivals != "backlog":
        return None
    return ctx.window_compiles
