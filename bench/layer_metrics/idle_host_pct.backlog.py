"""Device: share of the traced window in which the chip was idle and the
host was not blocked on it in ``rd.wait``, in backlog cells.

The window's idle time is what ``device_idle_pct.backlog`` reads (the
window less the union of the chip's ops).  From it goes the idle time put
down to ``rd.wait`` (the host blocked in ``jax.block_until_ready`` on the
RD program) or to an event of the dispatch, which the chip's trace shows
nested in ``rd.wait`` (``WAIT_LABELS``: jax's ``PjitFunction`` of the RD
program, its ``ParseArguments`` and PJRT's execute); what is left, the chip
idle while the host preps, reads back, decodes or runs the service tick,
is this share.  The idle time is read from every label (``idle_by_label``),
not from the largest ten: when the chip is busy while the host waits,
``rd.wait`` holds little idle and ranks low.  A window with no ``rd.wait``
host span reads nothing: a program without the span, or one that never
dispatched RD to the device."""

WAIT_LABELS = (
    "rd.wait",
    "PjitFunction(_rd_device)",
    "PjitFunction(_rd_device_chain)",
    "ParseArguments",
    "PJRT_LoadedExecutable_Execute linkage",
)


def read(ctx):
    if ctx.arrivals != "backlog" or not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    if not ctx.trace["host_spans"].get("rd.wait"):
        return None
    idle = ctx.trace["window_s"] - ctx.trace["busy_s"]
    wait = sum(ctx.trace["idle_by_label"].get(label, 0.0) for label in WAIT_LABELS)
    return 100.0 * (idle - wait) / ctx.trace["window_s"]
