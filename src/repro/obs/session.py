"""The observability session: one object carrying trace + metrics +
device profiling for one run, plus the ambient-activation protocol.

Instrumentation sites across the control plane resolve their session in
one of two ways:

- **constructed layers** (:class:`repro.runtime.loop.ControlPlane`,
  :class:`repro.runtime.engine.SchedulingEngine`,
  :class:`repro.runtime.cluster.ClusterState`) take an explicit ``obs=``
  parameter that defaults to the ambient :func:`active` session at
  construction;
- **module-level layers** (the ``wf_jax``/``rd_jax`` adapters,
  :class:`repro.placement.store.PlacementStore`, the serve engines) read
  :func:`active` / :func:`span` / :func:`device_span` per call.

Either way a disabled run pays one attribute/None check per site and
nothing else: a span site then gets the shared no-op :data:`NO_SPAN`.
Host time is timed in :class:`Span` objects, one helper for every
layer: a span lands in a histogram, in the ring buffer, and (through
``jax.profiler.TraceAnnotation``, once jax is loaded) on the host line
of a profiler trace, the line the device's idle gaps are put down to.
Activate with::

    from repro import obs

    with obs.observe() as session:
        result = engine.run(jobs)
    chrome = session.trace.to_chrome_trace()
    session.metrics.to_table()

**Schedule invariance is the contract**: every hook is observation-only.
No hook mutates cluster or queue state, calls into jax (a span's
profiler annotation only records), draws random numbers, or feeds a wall-clock reading back into a decision — so a run
with a session active is schedule-identical (bit-identical ``SimResult``)
to one without, which ``tests/test_obs.py`` proves across scenarios ×
orderings under ``--sanitize``.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Iterator

from . import clock
from .metrics import Metrics
from .trace import (
    INST_ADMIT,
    INST_ARRIVAL,
    INST_DEVICE,
    INST_FAILED,
    INST_FIRST_SERVICE,
    INST_PLACEMENT,
    INST_REASSIGN,
    INST_SPEC_LAUNCH,
    INST_SPEC_RESOLVE,
    INST_STEAL,
    SPAN_JOB,
    SPAN_HOST,
    SPAN_SERVE,
    TraceRecorder,
)

__all__ = [
    "ObsSession",
    "DeviceProfiler",
    "DeviceSpan",
    "NO_SPAN",
    "Span",
    "active",
    "device_span",
    "observe",
    "span",
]

# the shared no-op every span site gets when observability is off
NO_SPAN = contextlib.nullcontext()

# default histogram of ObsSession.span: ``<name>.us``
_NAME_US = "<name>.us"


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once jax is loaded, else None:
    this package never imports jax itself, so host-only runs stay
    jax-free."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


# spec-pair resolution codes (INST_SPEC_RESOLVE.b)
SPEC_ORIGINAL_WON = 0
SPEC_CLONE_WON = 1
SPEC_ABORTED = 2


class Span:
    """One timed host span, on both clocks.

    Entering opens a ``jax.profiler.TraceAnnotation`` of the same name
    (when jax is already loaded), so the span lands on the host line of
    a profiler trace; the wall time is read from :mod:`repro.obs.clock`
    inside the annotation.  On exit the time goes into the histogram
    ``hist`` (none when ``hist`` is None) and, with the session's trace
    on, into the ring buffer as a :data:`SPAN_HOST` record."""

    __slots__ = ("_session", "name", "_hist", "_ann", "_t0")

    def __init__(self, session: "ObsSession", name: str, hist: str | None):
        self._session = session
        self.name = name
        self._hist = hist

    def __enter__(self) -> "Span":
        ann = _trace_annotation()
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = clock.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall_us = clock.us_since(self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._close(self._t0, wall_us)

    def _close(self, t0: float, wall_us: int) -> None:
        s = self._session
        if self._hist is not None:
            s.metrics.observe(self._hist, wall_us)
        if s.trace is not None:
            s.trace.record(
                SPAN_HOST,
                ts=s.host_us(t0),
                dur=wall_us,
                a=s.trace.intern(self.name),
            )


class DeviceSpan(Span):
    """The span of one device dispatch, ``device.<kind>``: its wall time
    lands in ``device.<kind>.exec_us`` and the ring buffer's
    :data:`INST_DEVICE` record, keyed by the kernelcheck signature
    (``("wf-groups", m, k_pad, up)``, ``("rd-device", m, c_cap, a_pad)``,
    ...).  Set ``fallback`` before exit when the dispatch's result was
    discarded for a host re-run (RD capacity overflow): its wall time is
    genuine scheduling cost, not device time.  ``downgrade`` flags a
    dispatch that asked for the Pallas kernel but ran the jnp pipeline
    because its geometry is past the kernel's single-block bounds."""

    __slots__ = ("kind", "sig", "fallback", "downgrade")

    def __init__(
        self, session: "ObsSession", kind: str, sig: tuple, downgrade: bool
    ):
        super().__init__(session, f"device.{kind}", f"device.{kind}.exec_us")
        self.kind = kind
        self.sig = sig
        self.fallback = False
        self.downgrade = downgrade

    def _close(self, t0: float, wall_us: int) -> None:
        s = self._session
        m = s.metrics
        m.inc(f"device.{self.kind}.calls")
        m.observe(self._hist, wall_us)
        if self.fallback:
            m.inc(f"device.{self.kind}.host_fallback")
        if self.downgrade:
            m.inc(f"device.{self.kind}.pallas_downgrade")
        trace = s.trace
        if trace is not None:
            trace.record(
                INST_DEVICE,
                ts=s.host_us(t0),
                dur=wall_us,
                a=trace.intern(f"{self.kind}{self.sig}"),
                b=(2 if self.fallback else 0) | (4 if self.downgrade else 0),
                c=wall_us,
            )


class DeviceProfiler:
    """Dispatch accounting around the ``wf_jax``/``rd_jax`` adapters:
    every call's host-observed wall time (upload, run, sync, readback)
    lands in ``device.<kind>.exec_us``, with counts of calls, host
    fallbacks and Pallas downgrades.  Compiles are not guessed here:
    ``jax.monitoring`` counts the programs actually lowered."""

    def __init__(self, session: "ObsSession"):
        self._session = session

    def span(self, kind: str, sig: tuple, *, downgrade: bool = False) -> DeviceSpan:
        return DeviceSpan(self._session, kind, sig, downgrade)


class ObsSession:
    """Trace recorder + metrics registry + device profiler for one run."""

    def __init__(
        self,
        *,
        trace: bool = True,
        trace_capacity: int = 1 << 16,
        metrics_every: int = 1,
        device: bool = True,
    ):
        self.trace: TraceRecorder | None = (
            TraceRecorder(trace_capacity) if trace else None
        )
        self.metrics = Metrics()
        self.metrics_every = max(1, int(metrics_every))
        self.device: DeviceProfiler | None = (
            DeviceProfiler(self) if device else None
        )
        # current sim slot, kept fresh by the driving loop so layers
        # without their own clock (cluster, store) can timestamp events
        self.sim_now = 0
        self._t0 = clock.perf_counter()
        self._flow = 0
        self._started: set[int] = set()
        self._serve_submit: dict[int, tuple[int, int]] = {}  # rid -> (t, tokens)
        self._last_snap: int | None = None

    # ---- time bases ------------------------------------------------------

    def host_us(self, t: float) -> int:
        """A perf_counter reading as microseconds since session start."""
        return int((t - self._t0) * 1e6)

    def _next_flow(self) -> int:
        self._flow += 1
        return self._flow

    # ---- job lifecycle ---------------------------------------------------

    def job_arrival(self, t: int, job_id: int, n_tasks: int) -> None:
        self.metrics.inc("jobs.arrived")
        if self.trace is not None:
            self.trace.record(INST_ARRIVAL, ts=t, a=job_id, c=n_tasks)

    def job_admitted(self, t: int, job_id: int, overhead_s: float) -> None:
        self.metrics.inc("jobs.admitted")
        self.metrics.observe("sched.overhead_us", int(overhead_s * 1e6))
        if self.trace is not None:
            self.trace.record(
                INST_ADMIT, ts=t, a=job_id, c=int(overhead_s * 1e9)
            )

    def service_progress(self, t: int, job_id: int, n_done: int) -> None:
        if job_id not in self._started:
            self._started.add(job_id)
            self.metrics.inc("jobs.started")
            if self.trace is not None:
                self.trace.record(INST_FIRST_SERVICE, ts=t, a=job_id)

    def job_complete(
        self, t: int, job_id: int, arrival: int, jct: int, n_tasks: int
    ) -> None:
        self.metrics.inc("jobs.completed")
        self.metrics.observe("jobs.jct_slots", jct)
        if self.trace is not None:
            self.trace.record(
                SPAN_JOB, ts=arrival, dur=jct, a=job_id, c=n_tasks
            )

    def job_failed(self, t: int, job_id: int) -> None:
        self.metrics.inc("jobs.failed")
        if self.trace is not None:
            self.trace.record(INST_FAILED, ts=t, a=job_id)

    # admission / retry outcomes are counters only — no trace kind, so
    # existing trace consumers and the chrome export stay untouched
    def job_deferred(self, t: int, job_id: int) -> None:
        self.metrics.inc("jobs.deferred")

    def job_shed(self, t: int, job_id: int) -> None:
        self.metrics.inc("jobs.shed")

    def job_retry(self, t: int, job_id: int) -> None:
        self.metrics.inc("jobs.retried")

    # ---- host spans ------------------------------------------------------

    def span(self, name: str, hist: str | None = _NAME_US) -> Span:
        """A :class:`Span` named ``name`` whose wall time lands in
        histogram ``hist`` (default ``<name>.us``; None records none)::

            with session.span("tick.service"):
                ...
        """
        return Span(self, name, f"{name}.us" if hist is _NAME_US else hist)

    # ---- stealing / speculation / reassignment ---------------------------

    def steal_attempt(self, t: int, thief: int) -> None:
        self.metrics.inc("steal.attempted")

    def steal(
        self, t: int, job_id: int, donor: int, thief: int, tasks: int
    ) -> None:
        self.metrics.inc("steal.won")
        self.metrics.observe("steal.tasks", tasks)
        if self.trace is not None:
            self.trace.record(
                INST_STEAL,
                ts=t,
                dur=thief,
                a=job_id,
                b=donor,
                c=tasks,
                link=self._next_flow(),
            )

    def spec_launch(self, t: int, job_id: int, src: int, dst: int) -> int:
        """Record a speculative-clone launch; returns the causality link
        id the matching :meth:`spec_resolve` must echo."""
        self.metrics.inc("spec.launched")
        link = self._next_flow()
        if self.trace is not None:
            self.trace.record(
                INST_SPEC_LAUNCH, ts=t, a=job_id, b=src, c=dst, link=link
            )
        return link

    def spec_resolve(
        self, t: int, job_id: int, outcome: int, tasks: int, link: int
    ) -> None:
        name = {
            SPEC_ORIGINAL_WON: "spec.won_original",
            SPEC_CLONE_WON: "spec.won_clone",
        }.get(outcome, "spec.aborted")
        self.metrics.inc(name)
        if self.trace is not None:
            self.trace.record(
                INST_SPEC_RESOLVE,
                ts=t,
                a=job_id,
                b=outcome,
                c=tasks,
                link=link,
            )

    def reassign(self, t: int, job_id: int, tasks: int) -> None:
        self.metrics.inc("reassign.events")
        self.metrics.inc("reassign.tasks", tasks)
        if self.trace is not None:
            self.trace.record(INST_REASSIGN, ts=t, a=job_id, c=tasks)

    # ---- queue / placement -----------------------------------------------

    def enqueued(self, job, server: int, per_group: dict[int, int]) -> None:
        """Locality-tier accounting for one enqueued segment: replica
        rank 0 means ``server`` is the group's first-listed replica
        holder; higher ranks are secondary replicas.  Placement outside
        the locality set cannot happen (cluster invariant), so two tiers
        cover the space."""
        rank0 = other = 0
        for g, cnt in per_group.items():
            servers = job.groups[g].servers
            if servers and server == servers[0]:
                rank0 += cnt
            else:
                other += cnt
        if rank0:
            self.metrics.inc("locality.rank0_tasks", rank0)
        if other:
            self.metrics.inc("locality.secondary_tasks", other)

    def placement_event(self, t: int, kind: str, block: str, server: int) -> None:
        self.metrics.inc(f"placement.{kind}")
        if self.trace is not None:
            self.trace.record(
                INST_PLACEMENT,
                ts=t,
                a=self.trace.intern(f"{kind}:{block}"),
                b=server,
            )

    # ---- serving ---------------------------------------------------------

    def serve_request(self, t: int, rid: int, tokens: int) -> None:
        self.metrics.inc("serve.requests")
        self._serve_submit[rid] = (t, tokens)

    def serve_done(self, t_done: int, rid: int, latency: int) -> None:
        self.metrics.inc("serve.completed")
        self.metrics.observe("serve.latency_slots", latency)
        submit, tokens = self._serve_submit.pop(rid, (t_done - latency, 0))
        if self.trace is not None:
            self.trace.record(
                SPAN_SERVE, ts=submit, dur=latency, a=rid, c=tokens
            )

    def serve_routed(self, n_replicas: int) -> None:
        self.metrics.inc("serve.routed")
        self.metrics.observe("serve.fanout", n_replicas)

    # ---- per-tick snapshots ----------------------------------------------

    def snapshot(self, t: int, cluster) -> None:
        """Capture queue-depth and eq. 2 gauges at most once per
        ``metrics_every`` ticks.  Reads only (``busy_times`` may fill the
        incremental cache — bit-identical to the lazy fill by the rescan
        invariant)."""
        if self._last_snap is not None and t - self._last_snap < self.metrics_every:
            return
        self._last_snap = t
        m = self.metrics
        depths = [len(q) for q in cluster.queues]
        busy = cluster.busy_times()
        m.set_gauge("queue.segments", float(sum(depths)))
        m.set_gauge("queue.max_depth", float(max(depths, default=0)))
        m.set_gauge("busy.max", float(busy.max()) if busy.size else 0.0)
        m.set_gauge("busy.mean", float(busy.mean()) if busy.size else 0.0)
        m.set_gauge("jobs.live", float(len(cluster.remaining)))
        m.snapshot(t)


# ---- ambient activation --------------------------------------------------

_ACTIVE: list[ObsSession] = []


def active() -> ObsSession | None:
    """The innermost active session, or None when observability is off."""
    return _ACTIVE[-1] if _ACTIVE else None


def span(name: str, hist: str | None = _NAME_US):
    """:meth:`ObsSession.span` on the active session, or the shared
    :data:`NO_SPAN` when observability is off."""
    return _ACTIVE[-1].span(name, hist) if _ACTIVE else NO_SPAN


def device_span(kind: str, sig: tuple, *, downgrade: bool = False):
    """:meth:`DeviceProfiler.span` on the active session, or the shared
    :data:`NO_SPAN` (which enters as None) when observability or device
    profiling is off."""
    prof = _ACTIVE[-1].device if _ACTIVE else None
    if prof is None:
        return NO_SPAN
    return prof.span(kind, sig, downgrade=downgrade)


@contextlib.contextmanager
def observe(
    *,
    trace: bool = True,
    trace_capacity: int = 1 << 16,
    metrics_every: int = 1,
    device: bool = True,
) -> Iterator[ObsSession]:
    """Scope an :class:`ObsSession` as the ambient session::

        with obs.observe() as session:
            result = ControlPlane(scenario="bursty").drain()

    Nests like :func:`repro.backend.set_backend`; the innermost session
    wins.  Layers constructed inside the scope bind the session at
    construction, so the session outlives the ``with`` for export."""
    session = ObsSession(
        trace=trace,
        trace_capacity=trace_capacity,
        metrics_every=metrics_every,
        device=device,
    )
    _ACTIVE.append(session)
    try:
        yield session
    finally:
        _ACTIVE.pop()
