"""The control of ``correct``: a run whose placements break one guarantee
of the configuration, which the check has to call not correct.

- Where steps are bursts, the guarantee broken is FIFO admission against
  current busy times (each job placed against the eq. 2 busy times left
  by every job admitted before it).  The program has a path that drops
  it, the step a faster admission would tempt a later change to take:
  ``water_filling_jax_batch`` places a burst's jobs as independent
  problems, all against the pre-burst busy vector.  The control is the
  plane with that path in place of the chained one.
- Where steps are single jobs, the guarantee broken is the exact
  placement with the repo's tie-breaks.  The control is the plain
  reference put in the program's place, run on the servers numbered in
  reverse, so every tie between servers falls the other way.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

runs the control at the cell's own size and load (one process, one
window per seed) and prints each seed's numbers; it is not part of a
benchmark run.  It needs the chip like the benchmark does.
"""

from __future__ import annotations

if __name__ == "__main__":  # run as a script: import the package's copy
    import pathlib
    import sys

    _root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_root / "src"), str(_root)]
    from bench.control import main

    sys.exit(main())

import numpy as np  # noqa: E402

from . import reference  # noqa: E402


class MirroredReference:
    """A plane policy: the plain reference on the servers numbered in
    reverse (``m -> M - 1 - m``), so ties between servers break the
    other way."""

    name = "mirrored-reference"
    reorders = False

    def __init__(self, placer: str):
        self.place = reference.PLACERS[placer]

    def assign(self, problem):
        from repro.core import Assignment

        top = problem.n_servers - 1
        groups = tuple(
            (g.size, tuple(sorted(top - m for m in g.servers))) for g in problem.groups
        )
        alloc = self.place(
            np.asarray(problem.busy)[::-1], np.asarray(problem.mu)[::-1], groups
        )
        alloc = [{top - m: c for m, c in per.items()} for per in alloc]
        result = Assignment(alloc=alloc, phi=0)
        result.phi = result.realized_phi(problem)
        return result

    def assign_batch(self, problems):
        from repro.core.reorder import commit_busy

        out, busy = [], None
        for prob in problems:
            if busy is not None:
                prob = type(prob)(busy=busy, mu=prob.mu, groups=prob.groups)
            a = self.assign(prob)
            out.append(a)
            busy = commit_busy(prob.busy, a, prob.mu, prob.n_servers)
        return out


def hook(traffic: dict):
    """The ``plane_hook`` that turns a run of this mix into its control."""

    def swap(plane) -> None:
        import dataclasses

        if traffic["submit"] == "burst" and traffic["policy"] == "wf_jax":
            from repro.core.wf_jax import water_filling_jax_batch

            plane.engine.policy = dataclasses.replace(
                plane.engine.policy, batch_assigner=water_filling_jax_batch
            )
        else:
            plane.engine.policy = MirroredReference(traffic["reference"])

    return swap


def main(argv=None) -> int:
    import argparse
    import json
    import time

    from . import harness, spec

    ap = argparse.ArgumentParser(description="Run the control of a cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    traffic = spec.load_cell(args.workload).traffic
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run(
            args.workload, seed, args.seconds, False, t, plane_hook=hook(traffic)
        )
        row = {k: c["value"] for k, c in out["checks"].items()}
        print(json.dumps({"seed": seed, "correct": out["correct"], **row}), flush=True)
    return 0

