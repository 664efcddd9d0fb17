"""Device adapters: mean wall time of one RD dispatch's ``rd.decode`` phase
(obs span ``rd.decode``: the decode of the outputs into an assignment,
``validate`` and ``realized_phi`` included)."""


def read(ctx):
    if not ctx.obs:
        return None
    count, total = ctx.obs.get("rd.decode.us", (0, 0))
    return total / count / 1e3 if count else None
