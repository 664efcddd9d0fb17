"""Kernels: share of the HBM roofline reached by ``_waterlevel_kernel``.

The least time is the bytes the window's real water levels must move
(``bench.roofline.wf_level_bytes`` per (job, group), over the real
servers) at the chip's peak HBM bandwidth; it is divided by the device
time of every kernel launch in the window, padded launches included."""

from bench.roofline import wf_level_bytes
from bench.trace import find


def read(ctx):
    if not ctx.trace or not ctx.peaks or not ctx.wf_groups:
        return None
    _, total = find(ctx.trace["ops"], "_waterlevel_call", "custom-call")
    if total <= 0:
        return None
    least = ctx.wf_groups * wf_level_bytes(ctx.n_servers) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / total
