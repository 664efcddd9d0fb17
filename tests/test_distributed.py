"""Multi-device semantics on an 8-way CPU mesh (subprocess — the main
test process must keep seeing exactly 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # 8-device CPU-mesh subprocess runs


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    script = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"\n'
        + textwrap.dedent(body)
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=420,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},  # reprolint: disable=R002 passthrough to a subprocess, no backend choice read
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_main_process_sees_one_device():
    import jax

    assert len(jax.devices()) == 1


def test_sharded_train_step_matches_single_device():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.train import AdamWConfig, make_train_step, train_state_init
        from repro.launch.mesh import make_mesh
        from repro.parallel import param_sharding, batch_sharding

        cfg = get_smoke_config("qwen1.5-4b")
        opt = AdamWConfig(moment_dtype="float32")
        state = train_state_init(jax.random.PRNGKey(0), cfg, opt).as_dict()
        toks = np.random.default_rng(0).integers(0, cfg.vocab, (8, 17))
        batch = {"tokens": jnp.asarray(toks[:, :-1]),
                 "targets": jnp.asarray(toks[:, 1:])}

        # single-device reference
        s_ref, m_ref = jax.jit(make_train_step(cfg, opt))(state, batch)

        mesh = make_mesh((4, 2), ("data", "model"))
        st_sh = {"params": param_sharding(mesh, state["params"]),
                 "opt": {"m": param_sharding(mesh, state["opt"]["m"]),
                          "v": param_sharding(mesh, state["opt"]["v"]),
                          "step": NamedSharding(mesh, P())}}
        b_sh = batch_sharding(mesh, batch)
        with jax.set_mesh(mesh):
            s_dist, m_dist = jax.jit(
                make_train_step(cfg, opt), in_shardings=(st_sh, b_sh)
            )(state, batch)
        # loss and updated params must agree across partitionings
        assert abs(float(m_ref["loss"]) - float(m_dist["loss"])) < 1e-4
        errs = [float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(s_ref["params"]),
                    jax.tree.leaves(s_dist["params"]))]
        assert max(errs) < 5e-4, max(errs)
        print("DIST_OK")
        """
    )
    assert "DIST_OK" in out


def test_elastic_checkpoint_restore_across_meshes():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import make_mesh
        from repro.parallel import param_sharding
        from repro.configs import get_smoke_config
        from repro.models import init_params

        cfg = get_smoke_config("qwen1.5-4b")
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = param_sharding(mesh_a, params)
        placed = jax.tree.map(jax.device_put, params, sh_a)
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, placed)
            # restore onto a *different* mesh shape (elastic restart)
            mesh_b = make_mesh((2, 4), ("data", "model"))
            sh_b = param_sharding(mesh_b, params)
            restored = restore_checkpoint(d, 1, params, sh_b)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC_OK")
        """
    )
    assert "ELASTIC_OK" in out


def test_compressed_grads_match_exact_mean():
    out = _run(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.train.compress import (init_error_state,
                                          make_compressed_grad_fn)

        mesh = make_mesh((8,), ("data",))
        w = jnp.zeros((16,))
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
        ys = jnp.asarray(xs @ np.arange(16, dtype=np.float32))

        def grad_fn(params, batch):
            x, y = batch
            return jax.grad(lambda p: jnp.mean((x @ p - y) ** 2))(params)

        exact = grad_fn(w, (xs, ys))
        fn = jax.jit(make_compressed_grad_fn(grad_fn, mesh))
        err = init_error_state(w, 8)
        g, err = fn(w, (xs, ys), err)
        # one step: int8 error ≤ scale; with EF, descent still converges
        rel = float(jnp.abs(g - exact).max() / jnp.abs(exact).max())
        assert rel < 0.02, rel

        @jax.jit
        def steps(w, err):
            def body(carry, _):
                w, err = carry
                g, err = fn(w, (xs, ys), err)
                return (w - 0.1 * g, err), None

            (w, err), _ = jax.lax.scan(body, (w, err), None, length=300)
            return w, err

        w, err = steps(w, err)
        final = float(jnp.abs(w - jnp.arange(16.0)).max())
        assert final < 0.05, final
        print("COMPRESS_OK")
        """
    )
    assert "COMPRESS_OK" in out
