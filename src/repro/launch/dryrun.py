import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # reprolint: disable=R002 XLA device-count override must precede the first jax import

# --- everything below may import jax (device count is now locked) --------
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.configs.shapes import SHAPES, applicable, get_shape  # noqa: E402
from repro.launch.hlo import collective_stats  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import cache_specs, input_specs, step_fn_for  # noqa: E402
from repro.parallel import (  # noqa: E402
    batch_sharding,
    cache_sharding,
    fsdp_axes,
    param_sharding,
)
from repro.train import AdamWConfig  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent (shardings
propagate, collectives legal, memory accounted) and extracts the roofline
terms (FLOPs / bytes from ``cost_analysis``; collective bytes from the
partitioned HLO).  Artifacts land in ``results/dryrun/*.json`` and feed
``benchmarks/roofline.py`` and EXPERIMENTS.md.

Run:  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--out results/dryrun]
"""

RESULTS_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "results", "dryrun"
)


def shardings_for(mesh, cfg, shape, opt_cfg, *, serve_params: bool = False):
    """(in_shardings, logits_sharding) for the cell's step function.

    ``serve_params=True`` uses the decode-optimized resident weights
    (TP-only + 2-D EP; see parallel.serve_param_sharding, §Perf #3).
    """
    from repro.launch.specs import param_specs, state_specs
    from repro.parallel import serve_param_sharding

    dp = fsdp_axes(mesh)
    logits_sh = NamedSharding(mesh, P(dp, None, "model"))
    if shape.kind == "train":
        st = state_specs(cfg, opt_cfg)
        state_sh = {
            "params": param_sharding(mesh, st["params"]),
            "opt": {
                "m": param_sharding(mesh, st["opt"]["m"]),
                "v": param_sharding(mesh, st["opt"]["v"]),
                "step": NamedSharding(mesh, P()),
            },
        }
        batch_sh = batch_sharding(mesh, input_specs(cfg, shape))
        return (state_sh, batch_sh), logits_sh
    if shape.kind == "prefill":
        from repro.launch.specs import param_specs

        p_sh = param_sharding(mesh, param_specs(cfg))
        batch_sh = batch_sharding(mesh, input_specs(cfg, shape))
        return (p_sh, batch_sh), logits_sh
    # decode
    if serve_params:
        p_sh = serve_param_sharding(mesh, param_specs(cfg))
    else:
        p_sh = param_sharding(mesh, param_specs(cfg))
    tok_sh = batch_sharding(mesh, input_specs(cfg, shape))["tokens"]
    c_sh = cache_sharding(
        mesh, cache_specs(cfg, shape.global_batch, shape.seq_len)
    )
    return (p_sh, tok_sh, c_sh), logits_sh


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": 512 if multi_pod else 256,
    }
    ok, reason = applicable(cfg, shape)
    if not ok:
        cell["status"] = "skipped"
        cell["reason"] = reason
        return cell

    opt_cfg = AdamWConfig()
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        in_sh, logits_sh = shardings_for(mesh, cfg, shape, opt_cfg)
        fn, args = step_fn_for(cfg, shape, opt_cfg, logits_sharding=logits_sh)
        # donate the mutable aggregate (train state / decode cache) so the
        # functional update aliases instead of copying
        donate = {"train": (0,), "prefill": (), "decode": (2,)}[shape.kind]
        with jax.set_mesh(mesh):  # ambient mesh: activation constraints apply
            lowered = jax.jit(
                fn, in_shardings=in_sh, donate_argnums=donate
            ).lower(*args)
            cell["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
        cell["compile_s"] = round(time.time() - t0, 2)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
        cell.update(
            status="ok",
            flops_per_device=float(cost.get("flops", 0.0)),
            bytes_per_device=float(cost.get("bytes accessed", 0.0)),
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            collective_ops=coll.ops,
            collective_operand_bytes=coll.operand_bytes,
            collective_wire_bytes=float(coll.wire_bytes),
        )
        print(
            f"[ok] {arch} × {shape_name} × {mesh_name}: "
            f"lower {cell['lower_s']}s compile {cell['compile_s']}s  "
            f"flops/dev {cell['flops_per_device']:.3e}  "
            f"args {cell['argument_bytes'] / 2**30:.2f}GiB  "
            f"temp {cell['temp_bytes'] / 2**30:.2f}GiB  "
            f"coll {cell['collective_wire_bytes'] / 2**20:.1f}MiB",
            flush=True,
        )
        print(f"     memory_analysis: {mem}", flush=True)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        cell["status"] = "error"
        cell["error"] = f"{type(e).__name__}: {e}"
        traceback.print_exc()
    finally:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir, f"{arch}__{shape_name}__{mesh_name}.json"
        )
        with open(path, "w") as f:
            json.dump(cell, f, indent=1)
    return cell


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default=None, help="one arch (default: all)")
    parser.add_argument("--shape", default=None, help="one shape (default: all)")
    parser.add_argument("--mesh", default="both", choices=("single", "multi", "both"))
    parser.add_argument("--out", default=os.path.abspath(RESULTS_DEFAULT))
    args = parser.parse_args(argv)

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    summary = {"ok": 0, "skipped": 0, "error": 0}
    t0 = time.time()
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                cell = run_cell(arch, shape_name, multi, args.out)
                summary[cell["status"]] += 1
                if cell["status"] == "skipped":
                    print(f"[skip] {arch} × {shape_name}: {cell['reason']}")
                elif cell["status"] == "error":
                    print(f"[ERR] {arch} × {shape_name}: {cell['error']}")
    print(f"\nsummary: {summary}  wall={time.time() - t0:.0f}s")
    if summary["error"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
