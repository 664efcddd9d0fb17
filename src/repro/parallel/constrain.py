"""Ambient-mesh activation sharding constraints.

Model code calls ``shard(x, "dp", None, "model")`` with *logical* axis
tags; under ``jax.set_mesh(mesh)`` (set by the launchers) the
tags resolve to whichever of the mesh axes exist — "dp" → ("pod","data")
on the multi-pod mesh, ("data",) on a single pod — and a
``with_sharding_constraint`` is emitted.  With no ambient mesh (unit
tests, single-device smoke runs) it is a no-op, so the model stays
mesh-agnostic.

Pinning the carry/activation layout at block boundaries is what keeps
GSPMD's propagation from flipping activations to replicated inside
``lax.scan`` bodies (observed: un-pinned unembed logits replicated to
40 GiB/device on the 256-chip mesh — see EXPERIMENTS.md §Dry-run).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["shard", "logical_spec", "ambient_mesh"]


def ambient_mesh():
    """The ambient abstract mesh (``jax.set_mesh``), or None when unset —
    model code then skips its sharding hints (single-device tests and
    smoke runs)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def logical_spec(mesh, *tags) -> P:
    """Resolve logical tags ("dp" | "model" | None) against a mesh."""
    axes = []
    for t in tags:
        if t == "dp":
            dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            axes.append(dp if dp else None)
        elif t == "model":
            axes.append("model" if "model" in mesh.axis_names else None)
        elif t is None:
            axes.append(None)
        else:  # explicit mesh axis name
            axes.append(t if t in mesh.axis_names else None)
    return P(*axes)


def shard(x: jax.Array, *tags) -> jax.Array:
    """Constrain ``x`` to the logical spec if an ambient mesh is set."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    spec = logical_spec(mesh, *tags)
    # divisibility guard: replicate any axis that does not divide
    fixed = []
    for dim, axes in zip(x.shape, spec):
        if axes is None:
            fixed.append(None)
            continue
        names = (axes,) if isinstance(axes, str) else tuple(axes)
        extent = 1
        for n in names:
            extent *= mesh.shape[n]
        fixed.append(axes if dim % extent == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))
