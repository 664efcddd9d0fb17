"""Warm every device program the window will run, before it opens.

The window's steps are known in advance (they come from the seed), so
the jit signatures they reach can be listed from the jobs:

- WF (``wf_jax``): a step of one job runs the single-problem program at
  ``(M, k_pad)``; a burst runs the chained program at ``(M, k_pad,
  b_pad)``, both padded to powers of two.  One real step of each class
  is placed through the policy, against empty queues.
- RD (``rd``): each job runs the strip program at ``(M, C, A)``, with
  ``C`` its slot capacity and ``A`` its padded group width.  Placing the
  real job of a large ``C`` takes many strips, so each class is warmed
  on a stand-in job with the same ``(C, A)`` and a handful of strips.
"""

from __future__ import annotations

import numpy as np


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _problem(job, m: int):
    from repro.core import AssignmentProblem, TaskGroup

    return AssignmentProblem(
        busy=np.zeros(m, dtype=np.int64),
        mu=job.mu,
        groups=tuple(TaskGroup(s, srv) for s, srv in job.groups),
    )


def _place_step(policy, step, m: int) -> None:
    problems = [_problem(j, m) for j in step.jobs]
    if len(problems) == 1:
        policy.assign(problems[0])
    else:
        busy = problems[0].busy  # one pre-burst vector, as the engine passes it
        policy.assign_batch(
            [type(p)(busy=busy, mu=p.mu, groups=p.groups) for p in problems]
        )


def _rd_stand_in(c: int, a: int, m: int):
    """A job with slot capacity ``c`` and width ``a`` that RD finishes in
    ``a − 1`` strips: one group of ``n`` tasks on ``a`` servers whose μ
    exceeds ``n``, so each strip drops a whole server's replicas."""
    from repro.core import AssignmentProblem, TaskGroup
    from repro.core.rd_jax import rd_slot_capacity

    n = max(1, -(-(c // 2 - 1) // (a - 1)))
    prob = AssignmentProblem(
        busy=np.zeros(m, dtype=np.int64),
        mu=np.full(m, c // 2 + 1, dtype=np.int64),
        groups=(TaskGroup(n, tuple(range(a))),),
    )
    got = rd_slot_capacity(prob)
    if got != c:
        raise AssertionError(f"RD stand-in for C={c} sized to C={got}")
    return prob


def signatures(policy_name: str, steps, m: int) -> dict[tuple, object]:
    """The window's signature classes, each with what warms it."""
    out: dict[tuple, object] = {}
    for step in steps:
        k = max(len(j.groups) for j in step.jobs)
        if policy_name == "rd":
            from repro.core.rd_jax import rd_slot_capacity

            for job in step.jobs:
                c = rd_slot_capacity(_problem(job, m))
                a = _pow2(max(2, max(len(srv) for _, srv in job.groups)))
                out.setdefault(("rd", c, a), (c, a))
        elif policy_name == "wf_jax":
            b = len(step.jobs)
            key = ("wf-groups", _pow2(k)) if b == 1 else ("wf-chain", _pow2(k), _pow2(b))
            out.setdefault(key, step)
        else:
            raise ValueError(f"no warm-up for policy {policy_name!r}")
    return out


def warm(policy, policy_name: str, steps, m: int) -> list[tuple]:
    """Run each signature class once; returns the classes warmed."""
    sigs = signatures(policy_name, steps, m)
    for key, what in sorted(sigs.items()):
        if policy_name == "rd":
            policy.assign(_rd_stand_in(*what, m))
        else:
            _place_step(policy, what, m)
    return sorted(sigs)
