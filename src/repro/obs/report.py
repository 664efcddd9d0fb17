"""Run a scenario under observability and emit trace + metrics artifacts.

::

    PYTHONPATH=src python -m repro.obs.report --scenario bursty --out results

writes ``results/OBS_<scenario>.trace.json`` (Chrome/Perfetto
``trace_event`` JSON — open at https://ui.perfetto.dev) and
``results/OBS_<scenario>.metrics.npz`` (per-tick gauge/counter snapshots
plus histogram summaries), next to the ``BENCH_*.json`` benchmark
artifacts, and prints a run summary: schedule aggregates, steal /
speculation win-loss accounting, control-plane tick-phase wall times,
and the device-dispatch profile.

Defaults mirror the acceptance scenario: ``bursty`` with stealing and
speculation on, so the emitted trace contains job-lifecycle spans with
steal/spec causality links out of the box.
"""

from __future__ import annotations

import argparse
import json
import os

__all__ = ["main"]


def _fmt_hist(h) -> str:
    s = h.summary()
    return (
        f"n={int(s['count'])} mean={s['mean']:.1f} "
        f"p50={int(s['p50'])} p99={int(s['p99'])} max={int(s['max'])}"
    )


def _section(title: str) -> str:
    return f"\n{title}\n{'-' * len(title)}"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report", description=__doc__
    )
    ap.add_argument("--scenario", default="bursty")
    ap.add_argument("--policy", default="wf")
    ap.add_argument("--ordering", default="fifo")
    ap.add_argument(
        "--no-stealing", dest="stealing", action="store_false", default=True
    )
    ap.add_argument(
        "--no-speculation",
        dest="speculation",
        action="store_false",
        default=True,
    )
    ap.add_argument("--metrics-every", type=int, default=1)
    ap.add_argument("--capacity", type=int, default=1 << 18)
    ap.add_argument("--out", default="results")
    args = ap.parse_args(argv)

    # runtime imports are deferred so `--help` never pays the jax import
    import repro.traces  # noqa: F401  (registers the scenario registry)
    from repro import obs
    from repro.runtime.loop import ControlPlane

    with obs.observe(
        trace_capacity=args.capacity, metrics_every=args.metrics_every
    ) as session:
        plane = ControlPlane(
            policy=args.policy,
            ordering=args.ordering,
            scenario=args.scenario,
            stealing=args.stealing,
            speculation=args.speculation,
        )
        result = plane.drain()

    os.makedirs(args.out, exist_ok=True)
    trace_path = os.path.join(args.out, f"OBS_{args.scenario}.trace.json")
    with open(trace_path, "w") as f:
        json.dump(session.trace.to_chrome_trace(), f)
    metrics_path = os.path.join(args.out, f"OBS_{args.scenario}.metrics.npz")
    session.metrics.save_npz(metrics_path)

    m = session.metrics
    lines = [
        f"scenario={args.scenario} policy={args.policy} "
        f"ordering={args.ordering} stealing={args.stealing} "
        f"speculation={args.speculation}",
        _section("schedule"),
        f"jobs: {m.counter('jobs.arrived')} arrived, "
        f"{m.counter('jobs.completed')} completed, "
        f"{m.counter('jobs.failed')} failed",
        f"mean JCT: {result.mean_jct:.2f} slots   "
        f"makespan: {result.makespan} slots   "
        f"reassigned tasks: {result.reassignments}",
        f"scheduling overhead: mean {result.mean_overhead_s * 1e6:.0f} us/job",
        f"inflight serve requests at drain: {result.inflight_requests}",
        _section("work-stealing / speculation"),
        f"steal: {m.counter('steal.attempted')} attempted, "
        f"{m.counter('steal.won')} won ({result.steals} tasks moved)",
        f"spec: {m.counter('spec.launched')} launched, "
        f"{m.counter('spec.won_clone')} clone wins, "
        f"{m.counter('spec.won_original')} original wins, "
        f"{m.counter('spec.aborted')} aborted "
        f"({result.spec_cancels} losers cancelled)",
        _section("locality"),
        f"rank-0 replica placements: {m.counter('locality.rank0_tasks')} "
        f"tasks; secondary replicas: {m.counter('locality.secondary_tasks')}",
    ]
    phase_hists = sorted(
        (name, h)
        for name, h in m.histograms.items()
        if name.startswith("tick.")
    )
    if phase_hists:
        lines.append(_section("control-plane tick phases (host us)"))
        lines.extend(
            f"{name.split('.')[1]:>10}: {_fmt_hist(h)}"
            for name, h in phase_hists
        )
    device = sorted(
        (name, count)
        for name, count in m.counters.items()
        if name.startswith("device.")
    )
    if device:
        lines.append(_section("device dispatch"))
        lines.extend(f"{name}: {count}" for name, count in device)
        for name, h in sorted(m.histograms.items()):
            if name.startswith("device."):
                lines.append(f"{name}: {_fmt_hist(h)}")
    lines.append(_section("artifacts"))
    lines.append(f"trace:   {trace_path} ({len(session.trace)} events)")
    lines.append(f"metrics: {metrics_path} ({m.n_snapshots} snapshots)")
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
