"""The warm-up's look for device programs, on the CPU at a tiny size: a
cell whose device policy the program runs off the chip (RD on the host)
stops with ``OffChip`` before the window opens, traced or not, and
prints no result; the same cell with RD on a device backend, and the
``wf_jax`` cells, dispatch a device program in every class and run
through, also in the second and later windows of one process, whose
programs are all compiled already (the knee sweep runs so)."""

import functools
import json

import jax
import pytest

from bench import gen, sweep, warmup
from bench_tiny import TINY_CONFIG, TINY_TRAFFIC
from repro.backend import set_backend

SEED = 2**33 + 17
SECONDS = 0.5


def _first_rd_class():
    steps = gen.make_steps(TINY_CONFIG, TINY_TRAFFIC["tiny-backlog"], SECONDS, SEED)
    return min(warmup.signatures("rd", steps, TINY_CONFIG["n_servers"]))


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
def test_host_rd_is_refused_before_the_window(tiny_root, chipless_harness, monkeypatch, traced):
    stepped = []

    def count_steps(plane):
        step_until = plane.step_until

        def counted(slot):
            stepped.append(slot)
            return step_until(slot)

        plane.step_until = counted

    def no_trace(*args, **kwargs):
        raise AssertionError("the profiler started")

    monkeypatch.setattr(jax.profiler, "start_trace", no_trace)
    with set_backend(rd="host"), pytest.raises(chipless_harness.OffChip) as e:
        chipless_harness.run(
            "tiny-rd-backlog", SEED, SECONDS, traced, 0.0, root=tiny_root, plane_hook=count_steps
        )
    assert str(e.value) == (
        f"cell tiny-rd-backlog: signature class {_first_rd_class()} at M=64 dispatched 0"
        " device programs in the warm-up, so the program ran it off the chip"
    )
    assert stepped == []


def test_main_refuses_host_rd_with_no_result_line(tiny_root, chipless_harness, monkeypatch, capsys):
    monkeypatch.setattr(chipless_harness, "run", functools.partial(chipless_harness.run, root=tiny_root))
    argv = ["--workload", "tiny-rd-backlog", "--seed", str(SEED), "--seconds", str(SECONDS)]
    with set_backend(rd="host"):
        rc = chipless_harness.main(argv + ["--trace", "0"], 0.0)
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert out.err.splitlines()[-1].startswith(
        f"bench: cell tiny-rd-backlog: signature class {_first_rd_class()} at M=64 dispatched 0"
    )


def test_device_rd_passes_the_check(tiny_root, chipless_harness):
    with set_backend(rd="jnp"):
        out = chipless_harness.run("tiny-rd-backlog", SEED, SECONDS, False, 0.0, root=tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("cell", ["tiny-wf-burst", "tiny-wf-single"])
def test_wf_jax_passes_the_check(tiny_root, chipless_harness, cell):
    out = chipless_harness.run(cell, SEED, 1.0, False, 0.0, root=tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 20 and out["failed"] == 0


def test_sweep_runs_two_windows_in_one_process(tiny_root, chipless_harness, monkeypatch, capsys):
    """Each rate's warm-up finds every class compiled by the one before,
    and still passes the check."""
    monkeypatch.setattr(chipless_harness, "run", functools.partial(chipless_harness.run, root=tiny_root))
    argv = ["--workload", "tiny-wf-single", "--rates", "40,60", "--seconds", "1.0", "--seed", str(SEED)]
    assert sweep.main(argv) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["rate_jobs_per_s"] for r in rows] == [40.0, 60.0]
    assert all(r["correct"] and r["jobs"] > 0 and r["unanswered"] == 0 for r in rows), rows
