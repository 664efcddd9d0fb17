"""Mesh construction.

Functions — not module-level constants — so importing this module never
touches jax device state (the dry-run sets the host-device-count flag
before its first jax import; tests and benches must keep seeing 1 CPU).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes.

    The model code places activations with ``with_sharding_constraint``
    hints (:mod:`repro.parallel.constrain`) and lets GSPMD propagate the
    rest; under ``Explicit`` axes (``jax.make_mesh``'s default) such a
    hint is an assertion about the operand's sharding and raises."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    pure data parallelism across the pod-interconnect (DCN), scaling to N
    pods by changing the leading extent."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
