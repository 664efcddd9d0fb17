"""Device adapters: mean wall time of one RD dispatch's ``rd.wait`` phase
(obs span ``rd.wait``: the dispatch up to ``jax.block_until_ready`` on every
output, the host blocked on the device program)."""


def read(ctx):
    if not ctx.obs:
        return None
    count, total = ctx.obs.get("rd.wait.us", (0, 0))
    return total / count / 1e3 if count else None
