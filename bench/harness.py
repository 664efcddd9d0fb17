"""One run of one cell: set-up, the measured window, the check, the result.

Set-up is everything before the window opens: imports, the traffic
(jobs and schedule from the seed), the control plane and the warm-up of
every device program the window will run.  The window then drives
``ControlPlane.submit``/``step_until`` with the cell's steps for
``seconds``:

- open loop: each step is submitted at its due time (or at once, when
  the driver is late) and a job's latency runs from its due time to the
  return of the ``step_until`` that placed it.  Steps due in the window
  are still run after it closes, for up to ``GRACE_S``; one never run
  enters the latencies with its wait so far and counts as failed.
- backlog: every job is due when the window opens; steps run back to
  back until the backlog is drained or the window has closed, and the
  step in flight at the close finishes and counts.  ``tasks_per_s`` is
  the tasks placed over the time from the open to the return of that
  last step.

The window's steps are then replayed through the plain reference
(:mod:`reference`), and :mod:`check` compares placements and schedule.
With ``trace`` on, an observability session and a profiler trace cover
the window, and the per-layer readers get their numbers from those.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import shutil
import sys
import time

from . import check, gen, reference, spec, trace as trace_mod, warmup

GRACE_S = 60.0
# one event per program lowered (its backend compile may hit the cache)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
OBS_HISTS = (
    "tick.service.us",
    "sched.overhead_us",
    "rd.prep.us",
    "rd.wait.us",
    "rd.readback.us",
    "rd.decode.us",
    "rd.iters",
    "rd.moved",
)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Ctx:
    """What the metric readers read."""

    arrivals: str
    n_servers: int
    setup_s: float
    latencies_s: list[float]
    gen_lags_s: list[float]
    tasks_placed: int
    span_s: float
    window_compiles: int
    obs: dict[str, tuple[int, int]] | None = None  # hist -> (count, total)
    trace: dict | None = None
    wf_groups: int = 0  # real (job, group) water levels in the window
    peaks: dict | None = None


def enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed path, for
    every program however fast it compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _to_job(s):
    from repro.core import Job, TaskGroup

    return Job(
        job_id=s.job_id,
        arrival=s.slot,
        groups=tuple(TaskGroup(size, srv) for size, srv in s.groups),
        mu=s.mu,
    )


def _hists(session) -> dict[str, tuple[int, int]]:
    """(count, total) of the obs histograms the readers use."""
    out = {}
    for name, h in session.metrics.histograms.items():
        if name in OBS_HISTS or (name.startswith("device.") and name.endswith("exec_us")):
            out[name] = (h.count, h.total)
    return out


def drive(plane, steps, jobs, arrivals: str, seconds: float, annotate: bool) -> dict:
    """Run the window; returns what it measured and the steps it ran."""
    t0 = time.perf_counter()
    end = t0 + seconds
    ran, lat, lags = [], [], []
    tasks = 0
    last = t0
    with _span(annotate, "bench.window"):
        for step, js in zip(steps, jobs):
            if arrivals == "open":
                if step.due_s >= seconds:
                    break
                due = t0 + step.due_s
                wait = due - time.perf_counter()
                if wait > 0:
                    with _span(annotate, "bench.wait"):
                        time.sleep(wait)
                if time.perf_counter() > end + GRACE_S:
                    break
            else:
                due = t0
                if time.perf_counter() >= end:
                    break
            t_sub = time.perf_counter()
            with _span(annotate, "bench.submit"):
                for j in js:
                    plane.submit(j)
            with _span(annotate, "bench.step_until"):
                plane.step_until(step.slot)
            last = time.perf_counter()
            ran.append(step)
            lags.append(t_sub - due)
            lat.extend([last - due] * len(step.jobs))
            tasks += sum(j.n_tasks for j in step.jobs)
        else:
            if arrivals == "open":
                raise RuntimeError("the schedule ended inside the window")
    stop = time.perf_counter()
    due_left = [
        s for s in steps[len(ran):] if arrivals == "open" and s.due_s < seconds
    ]
    for s in due_left:  # never placed: their wait so far
        lat.extend([stop - (t0 + s.due_s)] * len(s.jobs))
    return {
        "ran": ran,
        "latencies_s": lat,
        "gen_lags_s": lags,
        "tasks": tasks,
        "span_s": last - t0,
        "unanswered": sum(len(s.jobs) for s in due_left),
        "attempted": len(lat) if arrivals == "open" else sum(len(s.jobs) for s in ran),
    }


def run(
    cell_name: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    root: pathlib.Path = spec.REPO_ROOT,
    plane_hook=None,
    rate: float | None = None,
    sink: dict | None = None,
) -> dict:
    """One run; returns the result object (its ``checks`` key last).
    ``plane_hook(plane)`` may swap parts of the plane before warm-up
    (the control and the fault tests use it); ``rate`` replaces the
    mix's offered rate and ``sink`` receives the window's raw record
    (the knee sweep uses both)."""
    cell = spec.load_cell(cell_name, root)
    import jax

    devices = check_device(cell.chips)
    from repro import obs
    from repro.runtime.loop import ControlPlane

    enable_cache()
    tr = dict(cell.traffic)
    if rate is not None:
        tr["rate_jobs_per_s"] = rate
    m = int(cell.config["n_servers"])
    steps = gen.make_steps(cell.config, tr, seconds, seed)
    jobs = [[_to_job(s) for s in step.jobs] for step in steps]

    lowered = [0]
    counting = [False]

    def on_duration(event: str, secs: float, **_) -> None:
        if counting[0] and event == LOWERING_EVENT:
            lowered[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    session_cm = obs.observe(trace=False, metrics_every=1 << 30) if trace else contextlib.nullcontext()
    trace_dir = root / ".bench_trace"
    try:
        with session_cm as session:
            plane = ControlPlane(n_servers=m, policy=tr["policy"], ordering=tr.get("ordering", "fifo"))
            if plane_hook is not None:
                plane_hook(plane)
            placed = []
            enqueue = plane.engine.cluster.enqueue

            def capture(job_id, assignment, gids):
                placed.append((job_id, assignment.alloc, list(gids)))
                enqueue(job_id, assignment, gids)

            plane.engine.cluster.enqueue = capture
            warmed = warmup.warm(plane.engine.policy, tr["policy"], steps, m)
            print(f"warmed {len(warmed)} signature classes: {warmed}", file=sys.stderr, flush=True)
            before = _hists(session) if trace else None
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                # Python's own tracer would time every call the plane makes
                # and slow the host it measures; host spans are the
                # harness's and jax's own
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            counting[0] = True
            setup_s = time.perf_counter() - t_start
            rec = drive(plane, steps, jobs, tr["arrivals"], seconds, trace)
            counting[0] = False
            if trace:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                after = _hists(session)
                print(f"trace written in {time.perf_counter() - t_stop:.1f} s", file=sys.stderr, flush=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    program = {
        "placement": {
            j: tuple(sorted(
                (gids[i], mm, c) for i, per in enumerate(alloc) for mm, c in per.items() if c > 0
            ))
            for j, alloc, gids in placed
        },
        "jct": dict(plane.jct),
        "remaining": dict(plane.engine.cluster.remaining),
    }
    del plane
    if sink is not None:
        sink["rec"] = rec
    t_ref = time.perf_counter()
    ref = reference.replay(m, tr["reference"], rec["ran"])
    print(f"reference replayed in {time.perf_counter() - t_ref:.1f} s", file=sys.stderr, flush=True)
    checks = check.compare(
        program,
        {"placement": ref.placement, "jct": ref.jct, "remaining": ref.remaining},
        rec["unanswered"],
    )

    ctx = Ctx(
        arrivals=tr["arrivals"],
        n_servers=m,
        setup_s=setup_s,
        latencies_s=rec["latencies_s"],
        gen_lags_s=rec["gen_lags_s"],
        tasks_placed=rec["tasks"],
        span_s=rec["span_s"],
        window_compiles=lowered[0],
        wf_groups=sum(len(j.groups) for s in rec["ran"] for j in s.jobs),
    )
    breakdown = None
    if trace:
        ctx.obs = {
            k: (v[0] - before.get(k, (0, 0))[0], v[1] - before.get(k, (0, 0))[1])
            for k, v in after.items()
        }
        t_red = time.perf_counter()
        ctx.trace = trace_mod.reduce(trace_mod.load(str(trace_dir)))
        print(f"trace reduced in {time.perf_counter() - t_red:.1f} s", file=sys.stderr, flush=True)
        ctx.peaks = spec.load_peaks(devices[0].device_kind, root)
        shutil.rmtree(trace_dir, ignore_errors=True)
        breakdown = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
    metrics = {}
    for metric in cell.per_layer if trace else cell.end_to_end:
        value = metric.read(ctx)
        if value is not None:
            metrics[metric.name] = {"value": value, "unit": metric.unit}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    if trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
    out = {
        "correct": check.passed(checks),
        "attempted": rec["attempted"],
        "failed": rec["unanswered"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv: list[str] | None, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
