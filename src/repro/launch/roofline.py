import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # reprolint: disable=R002 XLA device-count override must precede the first jax import

# --- everything below may import jax (device count is now locked) --------
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.configs.shapes import SHAPES, applicable, get_shape  # noqa: E402
from repro.launch.hlo import collective_stats  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import ModelConfig  # noqa: E402
from repro.train import AdamWConfig  # noqa: E402

"""Roofline probes: exact per-device FLOPs / bytes / collective traffic.

XLA's cost analysis counts ``while`` bodies once regardless of trip
count, so scanned-layer lowering (the production path) underreports by
~L×.  The probes lower *unrolled* variants of the same architecture at
two depths and extrapolate linearly:

    per_layer = (X(L=4) - X(L=2)) / 2
    X(L_full) = X(2) + (L_full - 2) · per_layer

FLOPs additionally force the direct (non-scanned) attention path so the
S² attention math is fully visible; bytes keep the chunked path (the one
that executes) and add the analytic KV re-stream term the chunk loop
hides.  Collective bytes come from the partitioned HLO of the unrolled
probes (per-layer collectives visible).  Memory-fit numbers come from the
full-depth scanned artifacts in results/dryrun (see EXPERIMENTS.md).

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Run:  PYTHONPATH=src python -m repro.launch.roofline [--arch A] [--shape S]
"""

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
CHIPS = 256  # roofline table is single-pod

RESULTS_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "results", "roofline"
)


def _probe_depths(cfg: ModelConfig) -> tuple[ModelConfig, ModelConfig, int, int]:
    """(shallow cfg, deeper cfg, shallow units, full units)."""
    if cfg.block_pattern == "zamba2":
        p = cfg.hybrid_period
        return (
            cfg.scaled(n_layers=2 * p),
            cfg.scaled(n_layers=4 * p),
            2,
            cfg.n_layers // p,
        )
    if cfg.block_pattern == "encdec":
        return (
            cfg.scaled(n_layers=2, n_encoder_layers=2),
            cfg.scaled(n_layers=4, n_encoder_layers=4),
            2,
            cfg.n_layers,  # enc and dec scale together (equal depths)
        )
    return cfg.scaled(n_layers=2), cfg.scaled(n_layers=4), 2, cfg.n_layers


def _lower_cell(cfg, shape, *, force_direct: bool, unroll: bool = True):
    """Lower+compile one unrolled probe; returns (flops, bytes, wire_bytes)."""
    from repro.launch.dryrun import shardings_for
    from repro.launch.specs import cache_specs, input_specs, param_specs, state_specs
    from repro.models import attention as attn_mod
    from repro.models import decode_step, prefill
    from repro.train import make_train_step

    opt_cfg = AdamWConfig()
    mesh = make_production_mesh(multi_pod=False)
    in_sh, logits_sh = shardings_for(mesh, cfg, shape, opt_cfg)
    if shape.kind == "train":
        fn = make_train_step(cfg, opt_cfg, logits_sharding=logits_sh)
        # thread unroll through the loss by rebuilding with a wrapper
        from repro.train.step import loss_fn as _loss
        from repro.train.optim import adamw_update

        def fn(state, batch):  # noqa: F811 — unrolled variant of train_step
            grad_fn = jax.value_and_grad(
                lambda p, b: _loss(
                    p, cfg, b, remat=True, logits_sharding=logits_sh
                ),
                has_aux=True,
            )
            (_, metrics), grads = grad_fn(state["params"], batch)
            new_params, new_opt, om = adamw_update(
                opt_cfg, grads, state["opt"], state["params"]
            )
            metrics.update(om)
            return {"params": new_params, "opt": new_opt}, metrics

        args = (state_specs(cfg, opt_cfg), input_specs(cfg, shape))
    elif shape.kind == "prefill":
        fn = lambda params, batch: prefill(params, cfg, batch, unroll=unroll)
        args = (param_specs(cfg), input_specs(cfg, shape))
    else:
        fn = lambda params, tokens, cache: decode_step(
            params, cfg, tokens, cache, unroll=unroll
        )
        args = (
            param_specs(cfg),
            input_specs(cfg, shape)["tokens"],
            cache_specs(cfg, shape.global_batch, shape.seq_len),
        )

    # train path: unroll via a monkeypatched forward (loss_fn has no knob).
    # NB: repro.train.step binds `forward_train` by value at import, so the
    # patch must land on that module's attribute, not on repro.models.model.
    import repro.models.model as model_mod
    import repro.train.step as step_mod

    prev_force = attn_mod.FORCE_DIRECT
    attn_mod.FORCE_DIRECT = force_direct
    orig_fwd = model_mod.forward_train
    if shape.kind == "train" and unroll:
        patched = lambda p, c, b, remat=True, **kw: orig_fwd(
            p, c, b, remat=remat, unroll=True
        )
        step_mod.forward_train = patched
    try:
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
        return (
            float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll.wire_bytes),
        )
    finally:
        attn_mod.FORCE_DIRECT = prev_force
        step_mod.forward_train = orig_fwd


def _attn_stream_correction(cfg, shape) -> float:
    """Per-device KV re-stream bytes hidden by the chunked-attention scan.

    Each of the nq query chunks re-reads the full K and V rows:
    per layer ≈ (nq - 1) · S·Hkv·hd · 2 tensors · 2 B (one read is already
    counted).  Sharded over the model axis (heads or sequence)."""
    if cfg.block_pattern in ("mamba2",) or shape.kind == "decode":
        return 0.0
    s = shape.seq_len
    if s < 4096:
        return 0.0
    nq = max(1, s // 1024)
    b_local = max(1, shape.global_batch // 16)  # data axis
    per_layer = (nq - 1) * b_local * s * cfg.n_kv_heads * cfg.head_dim_ * 2 * 2
    return per_layer * cfg.n_layers / 16  # model axis shards heads/seq


def probe(arch: str, shape_name: str, out_dir: str) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    cell = {"arch": arch, "shape": shape_name, "chips": CHIPS}
    ok, reason = applicable(cfg, shape)
    if not ok:
        cell.update(status="skipped", reason=reason)
        return cell
    t0 = time.time()
    lo_cfg, hi_cfg, lo_n, full_n = _probe_depths(cfg)

    def extrapolate(lo_vals, hi_vals):
        per = [(h - l) / lo_n for l, h in zip(lo_vals, hi_vals)]
        return [l + (full_n - lo_n) * p for l, p in zip(lo_vals, per)]

    # FLOPs: direct attention (full math visible)
    f_lo = _lower_cell(lo_cfg, shape, force_direct=True)
    f_hi = _lower_cell(hi_cfg, shape, force_direct=True)
    flops, _, _ = extrapolate(f_lo, f_hi)
    # bytes + collectives: executed (chunked) path
    b_lo = _lower_cell(lo_cfg, shape, force_direct=False)
    b_hi = _lower_cell(hi_cfg, shape, force_direct=False)
    _, bytes_acc, wire = extrapolate(b_lo, b_hi)
    bytes_acc += _attn_stream_correction(cfg, shape)

    n_eff = cfg.param_count() - cfg.vocab * cfg.d_model  # embed lookup free
    n_active = cfg.active_param_count() - cfg.vocab * cfg.d_model
    tokens = (
        shape.global_batch * shape.seq_len
        if shape.kind != "decode"
        else shape.global_batch
    )
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens

    compute_t = flops / PEAK_FLOPS  # per-device seconds
    memory_t = bytes_acc / HBM_BW
    collective_t = wire / ICI_BW
    bound = max(compute_t, memory_t, collective_t)
    dominant = (
        "compute"
        if bound == compute_t
        else "memory" if bound == memory_t else "collective"
    )
    cell.update(
        status="ok",
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collective_wire_bytes=wire,
        compute_term_s=compute_t,
        memory_term_s=memory_t,
        collective_term_s=collective_t,
        dominant=dominant,
        model_flops=model_flops,
        model_flops_per_device=model_flops / CHIPS,
        useful_compute_ratio=(model_flops / CHIPS) / max(flops, 1.0),
        roofline_fraction=(model_flops / CHIPS / PEAK_FLOPS) / max(bound, 1e-12),
        probe_wall_s=round(time.time() - t0, 1),
    )
    return cell


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default=None)
    parser.add_argument("--shape", default=None)
    parser.add_argument("--out", default=os.path.abspath(RESULTS_DEFAULT))
    args = parser.parse_args(argv)
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape_name in shapes:
            cell = probe(arch, shape_name, args.out)
            with open(
                os.path.join(args.out, f"{arch}__{shape_name}.json"), "w"
            ) as f:
                json.dump(cell, f, indent=1)
            if cell["status"] == "ok":
                print(
                    f"[ok] {arch} × {shape_name}: "
                    f"C={cell['compute_term_s']*1e3:.2f}ms "
                    f"M={cell['memory_term_s']*1e3:.2f}ms "
                    f"X={cell['collective_term_s']*1e3:.2f}ms "
                    f"dom={cell['dominant']} "
                    f"useful={cell['useful_compute_ratio']:.2f} "
                    f"roofline={cell['roofline_fraction']:.3f} "
                    f"({cell['probe_wall_s']}s)",
                    flush=True,
                )
            else:
                print(f"[skip] {arch} × {shape_name}: {cell['reason']}", flush=True)


if __name__ == "__main__":
    main()
