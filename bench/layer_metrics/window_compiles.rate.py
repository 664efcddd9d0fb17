"""Device adapters: programs lowered (jax.monitoring) while the window
was open, in open cells; the warm-up should leave none."""


def read(ctx):
    if ctx.arrivals != "open":
        return None
    return ctx.window_compiles
