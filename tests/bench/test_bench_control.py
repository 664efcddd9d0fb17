"""``correct`` holds for sound runs and fails for the control and for each
fault the cells can have, with the harness driving the rest of a run on
the CPU at a tiny size (the look for a chip skipped)."""

import dataclasses

import numpy as np
import pytest

from bench import control
from bench_tiny import TINY_TRAFFIC, TINY_CELLS

SECONDS = {"tiny-wf-burst": 1.0, "tiny-wf-single": 1.0, "tiny-rd-backlog": 0.5}


def _run(harness, root, cell, hook=None, seed=2**32 + 3):
    return harness.run(cell, seed, SECONDS[cell], False, 0.0, root=root, plane_hook=hook)


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_sound_run_is_correct(tiny_root, cpu_harness, cell):
    out = _run(cpu_harness, tiny_root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 20 and out["failed"] == 0


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_control_is_not_correct(tiny_root, cpu_harness, cell):
    out = _run(cpu_harness, tiny_root, cell, control.hook(TINY_TRAFFIC[TINY_CELLS[cell]]))
    assert not out["correct"]
    assert out["checks"]["placement_mismatch"]["value"] > 0


class _Wrapped:
    """A plane policy around the real one, with a fault planted."""

    def __init__(self, inner, alter=None, batch=None):
        self.inner, self.alter, self.batch = inner, alter, batch
        self.name, self.reorders = inner.name, inner.reorders

    def assign(self, problem):
        a = self.inner.assign(problem)
        return self.alter(problem, a) if self.alter else a

    def assign_batch(self, problems):
        if self.batch:
            return self.batch(self.inner, problems)
        out = self.inner.assign_batch(problems)
        return [self.alter(p, a) for p, a in zip(problems, out)] if self.alter else out


def _move_one_task(problem, a):
    """The answer altered where it is produced: one task of the first
    group moves to another of its eligible servers."""
    per = dict(a.alloc[0])
    src = max(per, key=per.get)
    dst = next(m for m in problem.groups[0].servers if m != src)
    per[src] -= 1
    per[dst] = per.get(dst, 0) + 1
    per = {m: c for m, c in per.items() if c}
    return dataclasses.replace(a, alloc=[per] + list(a.alloc[1:]))


def _half_burst_left_out(inner, problems):
    """Half of the burst left out of the chain: its jobs are placed
    against the pre-burst busy times."""
    half = len(problems) // 2
    out = inner.assign_batch(problems[: len(problems) - half]) if len(problems) > half else []
    return out + [inner.assign(p) for p in problems[len(problems) - half:]]


def _hook_policy(**kw):
    def hook(plane):
        plane.engine.policy = _Wrapped(plane.engine.policy, **kw)

    return hook


def _frozen_service(plane):
    """A step that returns its state unchanged: the service tick serves
    nothing."""
    plane.engine.cluster.process_slot = lambda: {}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_altered_answer_is_not_correct(tiny_root, cpu_harness, cell):
    out = _run(cpu_harness, tiny_root, cell, _hook_policy(alter=_move_one_task))
    assert not out["correct"] and out["checks"]["placement_mismatch"]["value"] > 0


def test_half_burst_left_out_is_not_correct(tiny_root, cpu_harness):
    out = _run(cpu_harness, tiny_root, "tiny-wf-burst", _hook_policy(batch=_half_burst_left_out))
    assert not out["correct"] and out["checks"]["placement_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_unchanged_state_is_not_correct(tiny_root, cpu_harness, cell):
    out = _run(cpu_harness, tiny_root, cell, _frozen_service)
    assert not out["correct"] and out["checks"]["schedule_mismatch"]["value"] > 0


def test_mirrored_reference_breaks_ties_the_other_way():
    from repro.core import AssignmentProblem, TaskGroup

    pol = control.MirroredReference("wf")
    p = AssignmentProblem(
        busy=np.zeros(16, int), mu=np.full(16, 4), groups=(TaskGroup(6, (0, 1)),)
    )
    # an even split at level 1 leaves server 1 the remainder; mirrored, server 0
    assert pol.assign(p).alloc == [{1: 4, 0: 2}]
