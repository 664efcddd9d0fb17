"""Seconds from process start to the window's open: imports, traffic,
control plane and warm-up of every program the window runs."""


def read(ctx):
    return ctx.setup_s
