"""Device adapters: mean iterations of the RD device program's two loops
per job (obs counter ``rd.iters``: deletion iterations, strip or not, plus
dedup strips), read back beside the job's outputs."""


def read(ctx):
    if not ctx.obs:
        return None
    count, total = ctx.obs.get("rd.iters", (0, 0))
    return total / count if count else None
