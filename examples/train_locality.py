"""End-to-end training driver: ~100M-param model, locality-aware data.

    PYTHONPATH=src python examples/train_locality.py [--steps 300]
        [--arch mamba2-130m] [--compress] [--fail-host 3]

Demonstrates the full stack working together on CPU:

- data shards replicated over hosts; every epoch's reads scheduled by the
  paper's water-filling (``LocalityAwareLoader``);
- a real model from the zoo (default: mamba2-130m ≈ 100M params at
  reduced width for CPU speed) trained with AdamW + remat;
- checkpoint/restart: saves every 50 steps, auto-resumes if restarted;
- optional host failure mid-run — reads reroute to surviving replicas
  and training continues without data-order drift;
- optional int8 gradient compression demo on a toy mesh.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import LocalityAwareLoader, ShardStore
from repro.train import AdamWConfig, make_train_step, train_state_init


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--arch", default="mamba2-130m")
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--fail-host", type=int, default=None)
    parser.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    parser.add_argument("--compress", action="store_true")
    args = parser.parse_args()

    # reduced width so a few hundred steps run in minutes on CPU
    cfg = get_config(args.arch).scaled(
        d_model=256,
        n_layers=4,
        vocab=8192,
        dtype="float32",
    )
    if cfg.ssm is not None:
        import dataclasses

        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, state_dim=32, chunk=64)
        )
    opt_cfg = AdamWConfig(
        lr=3e-4, warmup_steps=20, total_steps=args.steps, moment_dtype="float32"
    )

    store = ShardStore(
        n_shards=256, n_hosts=16, replicas=3,
        tokens_per_shard=args.seq_len * 8, vocab=cfg.vocab,
    )
    loader = LocalityAwareLoader(
        store, batch_tokens=args.batch * args.seq_len, seq_len=args.seq_len + 1
    )

    state = train_state_init(jax.random.PRNGKey(0), cfg, opt_cfg).as_dict()
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start, restored = mgr.restore_latest(state)
    if restored is not None:
        state = restored
        print(f"resumed from step {start}")
    start = start or 0

    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0,))
    step = start
    epoch = 0
    while step < args.steps:
        for tokens in loader.batches(epoch):
            if step >= args.steps:
                break
            if args.fail_host is not None and step == args.steps // 2:
                print(f"!! failing data host {args.fail_host}")
                store.fail_host(args.fail_host)
            batch = {
                "tokens": jnp.asarray(tokens[:, :-1]),
                "targets": jnp.asarray(tokens[:, 1:]),
            }
            state, metrics = step_fn(state, batch)
            if step % 25 == 0:
                print(
                    f"step {step:4d}  loss={float(metrics['loss']):.4f}  "
                    f"gnorm={float(metrics['grad_norm']):.3f}  "
                    f"lr={float(metrics['lr']):.2e}"
                )
            if step and step % 50 == 0:
                mgr.save_async(step, state)
            step += 1
        epoch += 1
    mgr.wait()
    mgr.save(step, state)
    print(f"done at step {step}; checkpoints in {args.ckpt_dir}")

    if args.compress:
        _compression_demo()


def _compression_demo() -> None:
    """int8 EF gradient reduction on a toy problem (single host demo)."""
    from repro.launch.mesh import make_mesh
    from repro.train.compress import init_error_state, make_compressed_grad_fn

    mesh = make_mesh((1,), ("data",))
    w = jnp.zeros((8,))
    xs = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
    ys = xs @ np.arange(8, dtype=np.float32)

    def grad_fn(params, batch):
        x, y = batch
        return jax.grad(lambda p: jnp.mean((x @ p - y) ** 2))(params)

    fn = make_compressed_grad_fn(grad_fn, mesh)
    err = init_error_state(w, 1)
    for i in range(200):
        g, err = fn(w, (jnp.asarray(xs), jnp.asarray(ys)), err)
        w = w - 0.01 * g
    print("compressed-grad solution ≈", np.round(np.asarray(w), 2))


if __name__ == "__main__":
    main()
