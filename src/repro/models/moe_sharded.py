"""shard_map MoE dispatch — the §Perf optimization for collective-bound
MoE training (EXPERIMENTS.md §Perf, hillclimb #1/#2).

The baseline GSPMD dispatch scatters a *global* (E, C, d) buffer: the
sharding propagator materializes replicated (N·K, d) intermediates and
re-shards the scatter across both mesh axes (measured ~11 TB/device wire
traffic on qwen3-moe train_4k — 40× the compute term).

The structural insight: with experts sharded on `model` and activations
replicated over `model` within each data shard, **dispatch needs no
communication at all** — every device already holds the tokens of its
data shard and the weights of its experts.  Each device:

  1. routes its local tokens (router weights are replicated);
  2. keeps only assignments to its *own* experts (`axis_index("model")`);
  3. builds a local (E/TP, C_local, d) buffer and runs its experts;
  4. scatters outputs back to local token positions;
  5. one ``psum`` over `model` merges the k expert contributions —
     exactly the all-reduce a dense TP FFN would do anyway.

Expert weights stay FSDP-sharded on the d_model axis between steps and
are all-gathered over the data axes on use (same traffic as GSPMD FSDP).
Capacity becomes per-data-shard (N_local·k/E·cf) — standard "local
capacity"; drop behavior differs from the global baseline only when
token→expert skew differs across data shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .ffn import _positions_in_expert, swiglu

__all__ = ["moe_apply_sharded"]


def moe_apply_sharded(
    p: dict, cfg: ModelConfig, x: jax.Array, mesh
) -> tuple[jax.Array, jax.Array]:
    """Drop-in for ``moe_apply`` under an ambient mesh with a `model` axis."""
    m = cfg.moe
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]
    e, k = m.n_experts, m.top_k
    assert e % tp == 0, "expert count must divide the model axis"
    e_loc = e // tp
    b, s, d = x.shape

    # param specs mirror repro.parallel.sharding rules
    wg_spec = P("model", dp, None)
    wo_spec = P("model", None, dp)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),  # x: tokens on dp, replicated on model
            P(),  # router (fp32, replicated)
            wg_spec,
            wg_spec,
            wo_spec,
        ),
        out_specs=(P(dp, None, None), P()),
    )
    def run(x_loc, rw, wg, wu, wo):
        # FSDP gather of this shard's expert weights over the data axes
        for ax in dp:
            wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, ax, axis=2, tiled=True)
        b_loc, s_loc, _ = x_loc.shape
        n_loc = b_loc * s_loc
        cap = max(1, int(n_loc * k / e * m.capacity_factor))

        x_flat = x_loc.reshape(n_loc, d)
        logits = x_flat.astype(jnp.float32) @ rw
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        flat_e = top_i.reshape(n_loc * k)
        flat_w = top_w.reshape(n_loc * k)

        pos = _positions_in_expert(flat_e, e)
        my_first = jax.lax.axis_index("model") * e_loc
        local_e = flat_e - my_first
        mine = (local_e >= 0) & (local_e < e_loc)
        keep = mine & (pos < cap)
        slot = jnp.where(keep, pos, 0)
        dest = jnp.where(keep, local_e, 0)

        x_rep = jnp.repeat(x_flat, k, axis=0)
        contrib = x_rep * keep[:, None].astype(x_loc.dtype)
        buf = jnp.zeros((e_loc, cap, d), x_loc.dtype).at[dest, slot].add(contrib)

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo)

        gathered = out_buf[dest, slot] * (flat_w * keep).astype(x_loc.dtype)[:, None]
        y_partial = gathered.reshape(n_loc, k, d).sum(axis=1)
        y = jax.lax.psum(y_partial, "model")  # merge the k expert owners

        # aux loss: local estimate, averaged over data shards (identical
        # across model shards — routing is replicated within a data shard)
        ones = jnp.ones_like(flat_e, dtype=jnp.float32)
        frac = jax.ops.segment_sum(ones, flat_e, num_segments=e) / (n_loc * k)
        aux = e * jnp.sum(frac * probs.mean(axis=0)) * m.router_aux_coef
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(b_loc, s_loc, d), aux

    ex = p["experts"]
    y, aux = run(x, p["router"]["w"], ex["wi_gate"], ex["wi_up"], ex["wo"])
    if m.n_shared:
        y = y + swiglu(p["shared"], x)
    return y, aux
