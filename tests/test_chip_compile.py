"""The scheduler's device programs, compiled for a described TPU v5e.

Nothing runs: the TPU compiler is handed a chip that is described, not
attached, and refuses what interpret mode accepts (lane rotations by a
traced shift, block shapes off the (8, 128) tiling, scoped-VMEM
overruns).  Each test compiles at a real width and asserts the Pallas
kernel is in the compiled program (``tpu_custom_call``).

The ceiling tests compile each dispatcher's widest Pallas geometry with
the scoped VMEM set to kernelcheck's model of that kernel: a pass shows
the model bounds what the compiler needs, so kernelcheck's memory proof
(model ≤ budget = the kernels' limit) is a claim about this compile.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.kernelcheck import DEFAULT_BUDGET_BYTES, _block_bytes
from repro.kernels import rd as rd_kernel
from repro.kernels import waterlevel

i32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # reprolint: disable=R002 keeps the TPU compiler's logs out of /tmp, no backend choice read
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache entry written for a described chip cannot be
    # read back without one: keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _hlo(fn, sharding, *shapes) -> str:
    args = [
        jax.ShapeDtypeStruct(s, i32, sharding=sharding) for s in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _wl(b, w, d):
    return waterlevel._waterlevel_call_padded(b, w, d, interpret=False)


def _strip(keys, size, quota):
    return rd_kernel._rd_strip_call(keys, size, quota, interpret=False)


@pytest.mark.parametrize("lanes", [4096, 16384])
def test_waterlevel_kernel_compiles(one_chip, lanes):
    hlo = _hlo(_wl, one_chip, (1, lanes), (1, lanes), (1,))
    assert "tpu_custom_call" in hlo


def test_waterlevel_batched_grid_compiles(one_chip):
    hlo = _hlo(_wl, one_chip, (8, 1024), (8, 1024), (8,))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("lanes", [4096, 16384])
def test_rd_strip_compiles(one_chip, lanes):
    hlo = _hlo(_strip, one_chip, (11, lanes), (lanes,), ())
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["waterlevel", "rd-strip"])
def test_dispatcher_ceiling_compiles_within_kernelcheck_model(
    one_chip, monkeypatch, kernel
):
    if kernel == "waterlevel":
        m = waterlevel.PALLAS_MAX_M
        assert waterlevel.resolve_use_pallas(True, m)
        model = waterlevel.wl_vmem_blocks({"m": m})
        fn, shapes = _wl, ((1, m), (1, m), (1,))
    else:
        c, rows = rd_kernel.RD_PALLAS_MAX_C, rd_kernel.RD_PALLAS_MAX_KEY_ROWS
        assert rd_kernel.rd_pallas_fits(c, rows)
        model = rd_kernel._rd_strip_vmem({"c": c, "rows": rows})
        fn, shapes = _strip, ((rows, c), (c,), ())
    model_bytes, _ = _block_bytes(model)
    assert model_bytes <= DEFAULT_BUDGET_BYTES == waterlevel.VMEM_LIMIT_BYTES
    # the kernels read the limit at trace time: retrace under the model's
    # bytes, and drop that trace afterwards
    monkeypatch.setattr(waterlevel, "VMEM_LIMIT_BYTES", model_bytes)
    jax.clear_caches()
    try:
        hlo = _hlo(fn, one_chip, *shapes)
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in hlo


def test_rd_device_program_compiles(one_chip):
    """The whole single-instance RD program (deletion and dedup loops with
    the strip kernel inside) at one Google-2011 cell's width."""
    from repro.core.rd_jax import _rd_device

    m, c_cap, a_pad = 12_500, 4096, 16

    def program(busy0, mu, holders, size, cnt, grp):
        return _rd_device(
            busy0, mu, holders, size, cnt, grp,
            use_pallas=True, interpret=False,  # reprolint: disable=R007 compiles the kernel path for the described chip explicitly
        )

    hlo = _hlo(
        program, one_chip,
        (m,), (m,), (c_cap, a_pad), (c_cap,), (c_cap,), (c_cap,),
    )
    assert "tpu_custom_call" in hlo
