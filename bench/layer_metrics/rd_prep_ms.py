"""Device adapters: mean wall time of one RD dispatch's ``rd.prep`` phase
(obs span ``rd.prep``: the host's dense instance and the uploads)."""


def read(ctx):
    if not ctx.obs:
        return None
    count, total = ctx.obs.get("rd.prep.us", (0, 0))
    return total / count / 1e3 if count else None
