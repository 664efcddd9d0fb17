"""Production train driver: ``python -m repro.launch.train --arch <id>``.

On real hardware this runs under the TPU runtime (jax.distributed
initializes from the pod metadata); on CPU it runs reduced configs for
validation.  Wires together: config → mesh → shardings → locality-aware
data pipeline → train step → checkpoint manager (auto-resume).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import LocalityAwareLoader, ShardStore
from repro.launch.mesh import make_production_mesh
from repro.parallel import fsdp_axes, param_sharding
from repro.train import AdamWConfig, make_train_step, train_state_init


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", choices=ARCHS, default="qwen1.5-4b")
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--microbatches", type=int, default=1)
    parser.add_argument("--ckpt-dir", default="/tmp/repro_train")
    parser.add_argument("--smoke", action="store_true",
                        help="reduced config (CPU validation)")
    parser.add_argument("--production-mesh", action="store_true",
                        help="build the (data, model) pod mesh (needs ≥256 devices)")
    args = parser.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt_cfg = AdamWConfig(total_steps=args.steps)
    state = train_state_init(jax.random.PRNGKey(0), cfg, opt_cfg).as_dict()

    if args.production_mesh:
        mesh = make_production_mesh()
        state_sh = {
            "params": param_sharding(mesh, state["params"]),
            "opt": {
                "m": param_sharding(mesh, state["opt"]["m"]),
                "v": param_sharding(mesh, state["opt"]["v"]),
                "step": NamedSharding(mesh, P()),
            },
        }
        logits_sh = NamedSharding(mesh, P(fsdp_axes(mesh), None, "model"))
        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg, microbatches=args.microbatches,
                            logits_sharding=logits_sh),
            in_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        ctx = jax.set_mesh(mesh)
    else:
        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg, microbatches=args.microbatches),
            donate_argnums=(0,),
        )
        import contextlib

        ctx = contextlib.nullcontext()

    store = ShardStore(
        n_shards=128, n_hosts=8, replicas=3,
        tokens_per_shard=(args.seq_len + 1) * 8, vocab=cfg.vocab,
    )
    loader = LocalityAwareLoader(
        store, batch_tokens=args.batch * (args.seq_len + 1),
        seq_len=args.seq_len + 1,
    )
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start, restored = mgr.restore_latest(state)
    if restored is not None:
        state = restored
        print(f"resumed from step {start}")
    step = start or 0

    with ctx:
        epoch = 0
        while step < args.steps:
            for tokens in loader.batches(epoch):
                if step >= args.steps:
                    break
                batch = {
                    "tokens": jnp.asarray(tokens[:, :-1]),
                    "targets": jnp.asarray(tokens[:, 1:]),
                }
                state, metrics = step_fn(state, batch)
                if step % 10 == 0:
                    print(f"step {step:5d} loss={float(metrics['loss']):.4f}")
                if step and step % 50 == 0:
                    mgr.save_async(step, state)
                step += 1
            epoch += 1
    mgr.wait()
    mgr.save(step, state)
    print(f"finished at step {step}")


if __name__ == "__main__":
    main()
