"""Device adapters: mean wall time of one RD dispatch's ``rd.readback`` phase
(obs span ``rd.readback``: one ``jax.device_get`` of every output together)."""


def read(ctx):
    if not ctx.obs:
        return None
    count, total = ctx.obs.get("rd.readback.us", (0, 0))
    return total / count / 1e3 if count else None
