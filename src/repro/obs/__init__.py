"""`repro.obs`: schedule-invariant observability for the control plane.

Three surfaces, one session object:

- **tracing** (:mod:`repro.obs.trace`) — typed span/instant events in a
  ring buffer: job lifecycle with steal/speculation/reassignment
  causality links, control-plane tick phases, placement churn, serve
  spans, device dispatches.  Exports Chrome/Perfetto ``trace_event``
  JSON and a columnar numpy table.
- **metrics** (:mod:`repro.obs.metrics`) — counters, gauges, and
  power-of-two histograms (queue depths, eq. 2 busy levels, locality
  tiers, steal/spec win-loss accounting, serve latency), snapshotted
  per tick at a configurable cadence.
- **host spans** (:func:`span`, :meth:`ObsSession.span`) — one helper
  for every host timing (tick phases ``tick.<phase>``, ``sched.admit``,
  the dispatch phases ``rd.*``/``wf.*``): each span lands in histogram
  ``<name>.us``, in the ring buffer when tracing, and, through
  ``jax.profiler.TraceAnnotation`` once jax is loaded, on the host line
  of a profiler trace.
- **device profiling** (:func:`device_span`,
  :class:`repro.obs.session.DeviceProfiler`) — the wall time of every
  ``wf_jax``/``rd_jax`` dispatch (``device.<kind>.exec_us``), keyed by
  the kernelcheck signatures, plus host-fallback and Pallas-downgrade
  counts.

Everything hangs off :class:`ObsSession`, activated ambiently::

    from repro import obs

    with obs.observe() as session:
        result = SchedulingEngine(...).run(jobs)
    json.dump(session.trace.to_chrome_trace(), open("run.trace.json", "w"))

The hard contract — proven by ``tests/test_obs.py`` and enforced by the
hook design — is that observability **on ≡ off is schedule-identical**:
hooks never mutate scheduler state, never touch jax or RNG, and wall
time flows only *out* (reprolint R008 funnels every runtime clock read
through :mod:`repro.obs.clock`).  This package imports only numpy and
the stdlib.

``python -m repro.obs.report`` runs a scenario under a session and
emits the trace + metrics artifacts next to ``results/BENCH_*.json``.
"""

from __future__ import annotations

from . import clock
from .metrics import Histogram, Metrics
from .session import (
    NO_SPAN,
    DeviceProfiler,
    DeviceSpan,
    ObsSession,
    Span,
    active,
    device_span,
    observe,
    span,
)
from .trace import KIND_NAMES, SLOT_US, TraceRecorder, parse_chrome_trace

__all__ = [
    "clock",
    "Histogram",
    "Metrics",
    "DeviceProfiler",
    "DeviceSpan",
    "NO_SPAN",
    "ObsSession",
    "Span",
    "active",
    "device_span",
    "observe",
    "span",
    "KIND_NAMES",
    "SLOT_US",
    "TraceRecorder",
    "parse_chrome_trace",
]
