"""One device dispatch in named phases, on the profiler's clock.

The ``wf_jax``/``rd_jax`` adapters run each dispatch through
:func:`phased_call`, so that a dispatch reads the same on the host line
of a profiler trace and in the obs histograms (``<phase>.<part>.us``):

- ``<phase>.prep``: the host's dense instance (``build()``) and the
  uploads;
- ``<phase>.wait``: the dispatch up to ``jax.block_until_ready`` on
  every output — the host blocked on the device program;
- ``<phase>.readback``: one ``jax.device_get`` of every output together;
- ``<phase>.decode``: the caller's, around its decode of the outputs.

``device.<kind>`` (``device.<kind>.exec_us``) runs, as it always has,
from the uploads to the end of the readback, so it opens inside
``<phase>.prep``.  With observability off every span is the shared
no-op and the dispatch syncs once (``block_until_ready``) and reads back
once.
"""

from __future__ import annotations

import contextlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.session import device_span, span

__all__ = ["phased_call"]


def phased_call(
    phase: str,
    kind: str,
    sig: tuple,
    fn: Callable,
    build: Callable[[], tuple[np.ndarray, ...]],
    *,
    downgrade: bool = False,
    fallback: Callable[[tuple], bool] | None = None,
) -> tuple:
    """``fn`` on the uploads of the arrays ``build()`` returns; its
    outputs as numpy arrays.  ``sig`` is the kernelcheck signature the
    dispatch is profiled under; ``fallback(outputs)`` says whether the
    caller will discard the result for a host re-run."""
    with contextlib.ExitStack() as device:
        with span(f"{phase}.prep"):
            host = build()
            dev = device.enter_context(
                device_span(kind, sig, downgrade=downgrade)
            )
            args = [jnp.asarray(a) for a in host]
        with span(f"{phase}.wait"):
            outs = jax.block_until_ready(fn(*args))
        with span(f"{phase}.readback"):
            outs = jax.device_get(outs)
        if dev is not None and fallback is not None:
            dev.fallback = fallback(outs)
    return outs
