"""Tasks placed in a backlog window over its whole span (to the return
of the step in flight when it closed)."""


def read(ctx):
    if ctx.arrivals != "backlog" or ctx.span_s <= 0:
        return None
    return ctx.tasks_placed / ctx.span_s
