"""Device adapters: mean host-observed time of one water-level dispatch
(obs ``device.wf-*.exec_us``: input prep, transfer, run and sync)."""


def read(ctx):
    if not ctx.obs:
        return None
    rows = [v for k, v in ctx.obs.items() if k.startswith("device.wf-") and k.endswith(".exec_us")]
    count = sum(c for c, _ in rows)
    return sum(t for _, t in rows) / count / 1e3 if count else None
