"""Chip smoke test: the scheduler's device WF and RD on one TPU.

Drives :class:`repro.runtime.loop.ControlPlane` on the ``bursty``
scenario at the size of one Google-2011 cell (12,500 servers; the water
level pads to 16,384 lanes), once per policy:

- ``wf_jax`` (the fused water-level kernel) against the host ``wf``;
- ``rd`` under ``auto`` (the fused RD strip kernel) against the same
  policy under ``set_backend(rd="host")``.

Each device run's ``SimResult`` must be bit-identical to its host
reference (JCTs, makespan, failed jobs, reassignments), both backends
must resolve to Pallas, and the device profiler must count at least one
dispatch and no Pallas→jnp downgrade or host re-run.  The scenario keeps
its shape (8-12 eligible servers per group, μ 3-5, utilization 0.5,
Zipf α 1); ``total_tasks`` scales with the server count, and the run
submits a cut of ``--n-jobs`` jobs with the same per-job sizes.  The
cluster (``SERVERS``) and the trace seed (``SEED``) are fixed.  The
default cut keeps the run to a few minutes: the device RD walks its
strips one after another, about 134,000 of them for the first 120 jobs.

Usage::

    python chip_smoke.py [--n-jobs N]

The last line of standard output is ``{"ok": true, "device": {...}}``
on success.  Without a TPU, or outside a checkout of the repository, the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SERVERS = 12_500  # one Google-2011 cell
SEED = 0

# jax's lowering and backend-compile durations: one event per compiled
# program (its trace events nest, so they are left in the steady time)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def _same(a, b) -> bool:
    return (
        a.jct == b.jct
        and a.makespan == b.makespan
        and a.failed_jobs == b.failed_jobs
        and a.reassignments == b.reassignments
    )


def _device_counters(session) -> dict[str, dict[str, int]]:
    """``device.<kind>.<counter>`` totals grouped by dispatch kind."""
    out: dict[str, dict[str, int]] = {}
    for name, value in session.metrics.counters.items():
        parts = name.split(".")
        if parts[0] == "device" and len(parts) == 3:
            out.setdefault(parts[1], {})[parts[2]] = value
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n-jobs", type=int, default=120)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return _fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)

    import jax

    from repro import obs
    from repro.backend import set_backend
    from repro.core.rd import resolve_rd_backend
    from repro.kernels.waterlevel import _wl_lanes, resolve_use_pallas
    from repro.launch.cache import enable_compile_cache
    from repro.runtime.loop import ControlPlane
    from repro.traces.bursty import BurstyTraceConfig

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (platform {dev.platform!r})")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(f"device: {json.dumps(device)}; compile cache {cache_dir}", flush=True)

    # the scenario's defaults are sized for its own cluster: scale the
    # trace with the servers so the load stays the same
    base = BurstyTraceConfig()
    m = SERVERS
    cell_jobs = round(base.n_jobs * m / base.n_servers)
    cell_tasks = round(base.total_tasks * m / base.n_servers)
    n_jobs = min(args.n_jobs, cell_jobs)
    total_tasks = round(cell_tasks * n_jobs / cell_jobs)
    kw = {
        "n_servers": m,
        "n_jobs": n_jobs,
        "total_tasks": total_tasks,
        "seed": SEED,
    }
    print(
        f"scenario bursty: {m} servers, seed {SEED}; the cell's trace at "
        f"this load is {cell_jobs} jobs / {cell_tasks} tasks; cut to "
        f"n_jobs={n_jobs} (total_tasks={total_tasks}, same per-job sizes)",
        flush=True,
    )

    wl = "pallas" if resolve_use_pallas(None, m) else "jnp"
    rd = resolve_rd_backend()
    print(
        f"resolved backends: waterlevel={wl} ({_wl_lanes(m)} lanes), rd={rd}",
        flush=True,
    )
    problems = []
    if wl != "pallas":
        problems.append(f"waterlevel resolved to {wl}, not pallas")
    if rd != "pallas":
        problems.append(f"rd resolved to {rd}, not pallas")

    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            compile_s[0] += secs

    def run(policy: str):
        t0 = time.perf_counter()
        res = ControlPlane(policy=policy, scenario="bursty", scenario_kw=kw).drain()
        return res, time.perf_counter() - t0

    cases = (
        ("wf_jax", "wf", {}),
        ("rd", "rd", {"rd": "host"}),
    )
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for device_policy, host_policy, host_scope in cases:
            with set_backend(**host_scope):
                ref, ref_s = run(host_policy)
            compile_s[0] = 0.0
            with obs.observe(trace=False) as session:
                res, dev_s = run(device_policy)
            _report(
                device_policy, host_policy, host_scope, ref, ref_s, res, dev_s,
                compile_s[0], _device_counters(session), problems,
            )
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)

    if problems:
        for p in problems:
            _fail(p)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _report(
    device_policy, host_policy, host_scope, ref, ref_s, res, dev_s,
    compile_s, counters, problems,
) -> None:
    """Print one policy's lines and add what failed to ``problems``."""
    tag = f"[{device_policy}]"
    n_jct = len(res.jct)
    mean_jct = sum(res.jct.values()) / n_jct if n_jct else float("nan")
    scope = f" {host_scope}" if host_scope else ""
    print(
        f"{tag} host reference {host_policy}{scope}: {ref_s:.3f} s wall; "
        f"device run {dev_s:.3f} s wall = compile {compile_s:.3f} s "
        f"(lowering + XLA compile) + steady {dev_s - compile_s:.3f} s",
        flush=True,
    )
    print(
        f"{tag} jobs done {n_jct}, failed {len(res.failed_jobs)}, "
        f"reassignments {res.reassignments}, makespan {res.makespan}, "
        f"mean JCT {mean_jct:.4f} slots",
        flush=True,
    )
    dispatches = downgrades = fallbacks = 0
    for kind, c in sorted(counters.items()):
        print(
            f"{tag} device.{kind}: calls {c.get('calls', 0)}, "
            f"compiles {c.get('compiles', 0)}, pallas_downgrade "
            f"{c.get('pallas_downgrade', 0)}, host_fallback "
            f"{c.get('host_fallback', 0)}",
            flush=True,
        )
        dispatches += c.get("calls", 0)
        downgrades += c.get("pallas_downgrade", 0)
        fallbacks += c.get("host_fallback", 0)
    ident = _same(res, ref)
    print(f"{tag} bit-identical to {host_policy}: {ident}", flush=True)
    if not ident:
        problems.append(f"{device_policy} differs from {host_policy}")
    if dispatches == 0:
        problems.append(f"{device_policy} made no device dispatch")
    if downgrades:
        problems.append(f"{device_policy}: {downgrades} Pallas->jnp downgrades")
    if fallbacks:
        problems.append(f"{device_policy}: {fallbacks} host re-runs")


if __name__ == "__main__":
    sys.exit(main())
