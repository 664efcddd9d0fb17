"""repro.obs: trace ring buffer, Chrome round-trip, metrics, device
profiling — and the load-bearing contract that observability on is
schedule-identical to observability off.

The equivalence half runs the same trace through ``SchedulingEngine`` /
``ControlPlane`` with and without an active :class:`ObsSession` and
requires the ``SimResult`` to be bit-identical (JCT map, makespan,
steals, speculation accounting, failures).  CI re-runs this file under
``--sanitize`` so the hooks also survive the armed runtime sanitizers.
The Chrome-export half pins the acceptance artifact: a valid
``trace_event`` JSON containing at least one complete job-lifecycle span
and a steal/speculation causality flow pair.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro.traces  # noqa: F401  (registers the scenario registry)
from repro import obs
from repro.core import AssignmentProblem, TaskGroup
from repro.obs import Histogram, Metrics, TraceRecorder, parse_chrome_trace
from repro.obs import trace as trace_mod
from repro.obs.session import (
    SPEC_CLONE_WON,
    DeviceProfiler,
    ObsSession,
    active,
)
from repro.runtime import ControlPlane, SchedulingEngine, make_policy
from repro.traces import generate

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

# ---- ring buffer ------------------------------------------------------------


def test_ring_buffer_overwrites_oldest():
    rec = TraceRecorder(capacity=8)
    for i in range(12):
        rec.record(trace_mod.INST_ARRIVAL, ts=i, a=i)
    assert len(rec) == 8
    assert rec.total == 12
    assert rec.dropped == 4
    # oldest-first order, with the first 4 rows overwritten
    assert [r[1] for r in rec.records()] == list(range(4, 12))


def test_ring_buffer_rejects_bad_capacity():
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_intern_is_stable():
    rec = TraceRecorder(capacity=4)
    a = rec.intern("wf-groups")
    b = rec.intern("rd-device")
    assert rec.intern("wf-groups") == a != b
    assert rec.strings == ("wf-groups", "rd-device")


def test_to_table_matches_records():
    rec = TraceRecorder(capacity=16)
    rec.record(trace_mod.SPAN_JOB, ts=3, dur=7, a=1, c=5)
    rec.record(trace_mod.INST_STEAL, ts=4, dur=2, a=1, b=0, c=3, link=1)
    table = rec.to_table()
    assert list(table["ts"]) == [3, 4]
    assert list(table["kind"]) == [trace_mod.SPAN_JOB, trace_mod.INST_STEAL]
    assert table["strings"].size == 0


# ---- Chrome trace_event export ---------------------------------------------


def _synthetic_recorder() -> TraceRecorder:
    """One of every kind, with a steal link and a matched spec pair."""
    rec = TraceRecorder(capacity=64)
    rec.record(trace_mod.INST_ARRIVAL, ts=0, a=1, c=4)
    rec.record(trace_mod.INST_ADMIT, ts=0, a=1, c=1200)
    rec.record(trace_mod.INST_FIRST_SERVICE, ts=1, a=1)
    rec.record(trace_mod.INST_STEAL, ts=2, dur=3, a=1, b=0, c=2, link=1)
    rec.record(trace_mod.INST_SPEC_LAUNCH, ts=3, a=1, b=0, c=2, link=2)
    rec.record(
        trace_mod.INST_SPEC_RESOLVE, ts=5, a=1, b=SPEC_CLONE_WON, c=4, link=2
    )
    rec.record(trace_mod.INST_REASSIGN, ts=5, a=1, c=1)
    rec.record(trace_mod.SPAN_JOB, ts=0, dur=6, a=1, c=4)
    rec.record(trace_mod.INST_FAILED, ts=6, a=2)
    rec.record(trace_mod.SPAN_SERVE, ts=1, dur=2, a=9, c=40)
    rec.record(
        trace_mod.INST_PLACEMENT, ts=4, a=rec.intern("evict:blk0"), b=3
    )
    rec.record(trace_mod.SPAN_HOST, ts=100, dur=50, a=rec.intern("tick.service"))
    rec.record(
        trace_mod.INST_DEVICE, ts=200, dur=30, a=rec.intern("wf-groups"), b=2, c=30
    )
    return rec


def test_chrome_trace_round_trips_through_json():
    rec = _synthetic_recorder()
    payload = json.loads(json.dumps(rec.to_chrome_trace()))
    records, strings = parse_chrome_trace(payload)
    assert records == rec.records()
    assert tuple(strings) == rec.strings


def test_chrome_trace_shape_is_valid():
    rec = _synthetic_recorder()
    chrome = rec.to_chrome_trace()
    events = chrome["traceEvents"]
    for ev in events:
        assert ev["ph"] in {"M", "X", "i", "s", "f"}
        assert "pid" in ev and "name" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 1 and ev["ts"] >= 0
    # the job lifecycle renders as a complete span at slot granularity
    job_spans = [
        e for e in events if e["ph"] == "X" and e.get("cat") == "job"
    ]
    assert len(job_spans) == 1
    assert job_spans[0]["ts"] == 0
    assert job_spans[0]["dur"] == 6 * trace_mod.SLOT_US
    # steal and spec causality render as matched s/f flow pairs
    for cat in ("steal", "spec"):
        starts = [e for e in events if e["ph"] == "s" and e["cat"] == cat]
        ends = [e for e in events if e["ph"] == "f" and e["cat"] == cat]
        assert len(starts) == 1 and len(ends) == 1
        assert starts[0]["id"] == ends[0]["id"]
    # the device dispatch decodes its flag bits
    device = [e for e in events if e.get("cat") == "device"]
    assert "cache_miss" not in device[0]["args"]
    assert device[0]["args"]["host_fallback"] is True
    assert device[0]["args"]["pallas_downgrade"] is False


def test_parse_accepts_bare_event_list():
    rec = _synthetic_recorder()
    events = rec.to_chrome_trace()["traceEvents"]
    records, strings = parse_chrome_trace(events)
    assert records == rec.records()
    assert strings == []


# ---- metrics ----------------------------------------------------------------


def test_histogram_buckets_and_quantiles():
    h = Histogram()
    for v in (0, 1, 1, 3, 100):
        h.observe(v)
    assert h.count == 5
    assert h.max == 100
    assert h.mean == pytest.approx(21.0)
    assert h.quantile(0.0) == 0
    assert h.quantile(0.5) == 1  # bucket upper bound containing the median
    assert h.quantile(1.0) >= 100  # p100 covers the max sample's bucket
    s = h.summary()
    assert s["count"] == 5.0 and s["max"] == 100.0


def test_histogram_clamps_negative_values():
    h = Histogram()
    h.observe(-5)
    assert h.count == 1 and h.max == 0 and h.total == 0


def test_metrics_snapshot_table_and_npz(tmp_path):
    m = Metrics()
    m.inc("jobs.arrived")
    m.set_gauge("queue.segments", 3.0)
    m.observe("jobs.jct_slots", 12)
    m.snapshot(5)
    m.inc("jobs.arrived", 2)
    m.set_gauge("queue.segments", 1.0)
    m.snapshot(9)
    table = m.to_table()
    assert list(table["tick"]) == [5, 9]
    assert list(table["gauge.queue.segments"]) == [3.0, 1.0]
    assert list(table["counter.jobs.arrived"]) == [1.0, 3.0]
    assert table["hist.jobs.jct_slots.count"][0] == 1.0
    path = tmp_path / "metrics.npz"
    m.save_npz(str(path))
    loaded = np.load(path)
    assert set(loaded.files) == set(table)
    np.testing.assert_array_equal(loaded["tick"], table["tick"])


def _n_servers(jobs) -> int:
    return 1 + max(max(g.servers) for j in jobs for g in j.groups)


def test_snapshot_cadence_respects_metrics_every():
    jobs = generate("bursty", n_jobs=25, seed=3)
    n = _n_servers(jobs)
    with obs.observe(trace=False, device=False, metrics_every=1) as dense:
        SchedulingEngine(n, make_policy("wf")).run(jobs)
    with obs.observe(trace=False, device=False, metrics_every=8) as sparse:
        SchedulingEngine(n, make_policy("wf")).run(jobs)
    assert dense.metrics.n_snapshots > sparse.metrics.n_snapshots > 0


# ---- host spans -------------------------------------------------------------


def test_span_without_session_is_a_noop_and_imports_no_jax():
    """Off, every span site gets one shared no-op; on, a span still times
    and records without loading jax (runs in a fresh interpreter)."""
    script = textwrap.dedent(
        """
        import sys
        from repro import obs

        assert obs.span("rd.prep") is obs.NO_SPAN
        assert obs.device_span("rd-device", (8, 128, 2)) is obs.NO_SPAN
        with obs.span("rd.prep") as sp, obs.device_span("rd-device", ()) as dev:
            assert sp is None and dev is None
        with obs.observe() as s:
            with obs.span("tick.service"):
                pass
        assert s.metrics.histogram("tick.service.us").count == 1
        assert "jax" not in sys.modules, "repro.obs imported jax"
        print("ok")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": _SRC, "JAX_PLATFORMS": "cpu"},  # reprolint: disable=R002 passthrough to a subprocess, no backend choice read
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip() == "ok"


def test_span_fills_its_histogram_and_the_ring_buffer():
    with obs.observe() as s:
        with obs.span("rd.prep"):
            with s.span("sched.admit", hist=None):
                pass
        with s.span("tick.service", hist="tick.other.us"):
            pass
    m = s.metrics
    assert m.histogram("rd.prep.us").count == 1
    assert m.histogram("sched.admit.us") is None  # hist=None: ring only
    assert m.histogram("tick.other.us").count == 1
    spans = [r for r in s.trace.records() if r[0] == trace_mod.SPAN_HOST]
    names = [s.trace.strings[r[3]] for r in spans]
    assert names == ["sched.admit", "rd.prep", "tick.service"]  # close order
    inner, outer = spans[0], spans[1]
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    with obs.observe(trace=False) as quiet:
        with obs.span("rd.prep"):
            pass
    assert quiet.trace is None
    assert quiet.metrics.histogram("rd.prep.us").count == 1


# ---- device profiler --------------------------------------------------------


def test_device_profiler_splits_compile_and_exec():
    """Every call lands in ``exec_us``: compiles are counted from
    ``jax.monitoring``, never guessed from a signature's first call."""
    s = ObsSession()
    prof = s.device
    assert isinstance(prof, DeviceProfiler)
    sig = (16, 32, 1)
    for _ in range(3):
        with prof.span("wf-groups", sig):
            pass
    with prof.span("rd-device", (8, 4, 2)) as dev:
        dev.fallback = True
    m = s.metrics
    assert m.counter("device.wf-groups.calls") == 3
    assert m.counter("device.wf-groups.compiles") == 0
    assert m.histogram("device.wf-groups.compile_us") is None
    assert m.histogram("device.wf-groups.exec_us").count == 3
    assert m.counter("device.rd-device.host_fallback") == 1
    device_events = [
        r for r in s.trace.records() if r[0] == trace_mod.INST_DEVICE
    ]
    assert len(device_events) == 4
    assert not any(r[4] & 1 for r in device_events)  # no cache-miss bit
    assert device_events[3][4] & 2  # the rd fallback is flagged


def test_wf_jax_dispatch_is_profiled():
    prob = AssignmentProblem(
        busy=np.zeros(4, dtype=np.int64),
        mu=np.ones(4, dtype=np.int64),
        groups=(TaskGroup(size=3, servers=(0, 1)),),
    )
    from repro.core.wf_jax import water_filling_jax

    baseline = water_filling_jax(prob)  # outside any session: no profiling
    with obs.observe() as s:
        profiled = water_filling_jax(prob)
    assert profiled.alloc == baseline.alloc and profiled.phi == baseline.phi
    assert s.metrics.counter("device.wf-groups.calls") == 1


@pytest.mark.parametrize("kind", ["wf-groups", "rd-device"])
def test_pallas_downgrade_is_counted(monkeypatch, kind):
    """A dispatch that asked for the Pallas kernel but ran the jnp
    pipeline (geometry past the kernel's bounds) keeps its result and is
    counted as a downgrade, not hidden."""
    from repro.backend import set_backend
    from repro.kernels import rd as rd_kernel
    from repro.kernels import waterlevel

    prob = AssignmentProblem(
        busy=np.array([0, 1, 0, 2], dtype=np.int64),
        mu=np.ones(4, dtype=np.int64),
        groups=(TaskGroup(size=3, servers=(0, 1)), TaskGroup(2, (1, 2, 3))),
    )
    if kind == "wf-groups":
        from repro.core.wf_jax import water_filling_jax as assign

        monkeypatch.setattr(waterlevel, "PALLAS_MAX_M", 2)  # below M = 4
        scope = {"waterlevel": "pallas"}
    else:
        from repro.core.rd import replica_deletion_auto as assign

        monkeypatch.setattr(rd_kernel, "rd_pallas_fits", lambda *a: False)
        scope = {"rd": "pallas"}
    baseline = assign(prob)
    with set_backend(**scope), obs.observe() as s:
        downgraded = assign(prob)
    assert downgraded.alloc == baseline.alloc
    assert s.metrics.counter(f"device.{kind}.calls") == 1
    assert s.metrics.counter(f"device.{kind}.pallas_downgrade") == 1
    assert s.metrics.counter(f"device.{kind}.host_fallback") == 0
    (event,) = [r for r in s.trace.records() if r[0] == trace_mod.INST_DEVICE]
    assert event[4] & 4  # the trace flags the downgrade


# ---- schedule invariance (the contract) ------------------------------------


def _result_key(res):
    return (
        dict(res.jct),
        res.makespan,
        sorted(res.failed_jobs),
        res.reassignments,
        res.steals,
        res.speculations,
        res.spec_cancels,
        dict(res.serve_latency),
        res.inflight_requests,
    )


@pytest.mark.parametrize(
    "scenario,ordering",
    [("bursty", "fifo"), ("bursty", "setf"), ("alibaba", "fifo")],
)
def test_observed_engine_run_is_schedule_identical(scenario, ordering):
    jobs = generate(scenario, n_jobs=30, seed=7)
    n = _n_servers(jobs)
    plain = SchedulingEngine(n, make_policy("wf", ordering)).run(jobs)
    with obs.observe() as s:
        observed = SchedulingEngine(n, make_policy("wf", ordering)).run(jobs)
    assert _result_key(observed) == _result_key(plain)
    assert s.metrics.counter("jobs.arrived") == len(jobs)
    assert s.metrics.counter("jobs.completed") == len(plain.jct)


def _rd_problems():
    rng = np.random.default_rng(11)
    out = []
    for n in range(4):
        m = 12
        groups = tuple(
            TaskGroup(
                int(rng.integers(1, 9)),
                tuple(sorted(rng.choice(m, size=int(rng.integers(2, 5)), replace=False).tolist())),
            )
            for _ in range(1 + n)
        )
        out.append(AssignmentProblem(
            busy=rng.integers(0, 4, m).astype(np.int64),
            mu=rng.integers(1, 4, m).astype(np.int64),
            groups=groups,
        ))
    return out


def test_rd_iters_agree_across_device_backends_and_obs_leaves_rd_alone():
    """The RD program's loop counter reads the same under the jnp strip
    and the Pallas kernel (interpret mode), one ``rd.iters`` observation
    per job, single and chained; with the phase spans on, every
    assignment equals the one made with observability off."""
    from repro.core.rd_jax import replica_deletion_jax, replica_deletion_jax_chain

    problems = _rd_problems()
    chain = [dataclasses.replace(p, busy=problems[0].busy) for p in problems]
    iters = {}
    for backend in ("jnp", "pallas"):
        off = [replica_deletion_jax(p, backend=backend).alloc for p in problems]
        off_chain = [a.alloc for a in replica_deletion_jax_chain(chain, backend=backend)]
        with obs.observe() as s:
            on = [replica_deletion_jax(p, backend=backend).alloc for p in problems]
            on_chain = [
                a.alloc for a in replica_deletion_jax_chain(chain, backend=backend)
            ]
        assert on == off and on_chain == off_chain
        h = s.metrics.histogram("rd.iters")
        assert h.count == 2 * len(problems)  # one per job, padded jobs left out
        iters[backend] = (h.count, h.total, h.max)
        for phase in ("prep", "wait", "readback", "decode"):
            assert s.metrics.histogram(f"rd.{phase}.us").count == len(problems) + 1
        assert s.metrics.counter("device.rd-device.calls") == len(problems)
        assert s.metrics.counter("device.rd-chain.calls") == 1
    assert iters["jnp"] == iters["pallas"]
    assert iters["jnp"][1] >= 2 * len(problems)  # every job ran its loops


def test_rd_moved_is_observed_once_per_job(monkeypatch):
    """``rd.moved``, the classes the RD program's strips moved, is one
    observation per job, single and chained; a job moves at most μ
    classes a strip, and the total is the same with observability off."""
    from repro.core import rd_jax

    problems = _rd_problems()
    chain = [dataclasses.replace(p, busy=problems[0].busy) for p in problems]
    loops = []
    observe = rd_jax._observe_loops

    def _spy(iters, moved):
        loops.extend(zip(np.atleast_1d(iters).tolist(), np.atleast_1d(moved).tolist()))
        observe(iters, moved)

    monkeypatch.setattr(rd_jax, "_observe_loops", _spy)

    def run():
        loops.clear()
        for p in problems:
            rd_jax.replica_deletion_jax(p)
        rd_jax.replica_deletion_jax_chain(chain)
        return list(loops)

    off = run()
    with obs.observe() as s:
        on = run()
    assert on == off
    assert len(off) == 2 * len(problems)  # one per job, padded jobs left out
    for (iters, moved), p in zip(off, problems + chain):
        assert 0 < moved <= int(p.mu.max()) * iters
    h = s.metrics.histogram("rd.moved")
    assert (h.count, h.total) == (len(off), sum(m for _, m in off))


def test_observed_device_rd_run_is_schedule_identical():
    """The obs on ≡ off contract with the device RD path and its spans
    (dispatch phases, ``sched.admit``, tick phases) all firing."""
    from repro.backend import set_backend
    from repro.core import Job

    jobs = [
        Job(job_id=i, arrival=i // 2, groups=p.groups, mu=p.mu)
        for i, p in enumerate(_rd_problems() * 2)
    ]
    with set_backend(rd="jnp"):
        plain = SchedulingEngine(12, make_policy("rd")).run(jobs)
        with obs.observe() as s:
            observed = SchedulingEngine(12, make_policy("rd")).run(jobs)
    assert _result_key(observed) == _result_key(plain)
    hists = s.metrics.histograms
    for name in ("rd.prep.us", "rd.wait.us", "rd.readback.us", "rd.decode.us",
                 "rd.iters", "sched.overhead_us"):
        assert hists[name].count > 0, name
    spans = {s.trace.strings[r[3]] for r in s.trace.records() if r[0] == trace_mod.SPAN_HOST}
    assert {"sched.admit", "rd.prep", "rd.wait", "rd.readback", "rd.decode"} <= spans


def test_observed_online_plane_is_schedule_identical():
    kw = dict(
        scenario="bursty",
        scenario_kw={"n_jobs": 100, "seed": 0},
        stealing=True,
        speculation=True,
    )
    plain = ControlPlane(**kw).drain()
    with obs.observe() as s:
        observed = ControlPlane(**kw).drain()
    assert _result_key(observed) == _result_key(plain)
    # the run exercised the online mechanisms, not just the hooks
    assert s.metrics.counter("steal.won") > 0
    assert s.metrics.counter("spec.launched") > 0
    spec_outcomes = (
        s.metrics.counter("spec.won_clone")
        + s.metrics.counter("spec.won_original")
        + s.metrics.counter("spec.aborted")
    )
    assert spec_outcomes == s.metrics.counter("spec.launched")


def test_acceptance_trace_has_lifecycle_span_and_causality_link():
    """The ISSUE acceptance artifact: the exported bursty trace is valid
    Chrome trace_event JSON with a complete job-lifecycle span and a
    steal/speculation flow pair, and survives a full json round trip."""
    with obs.observe() as s:
        ControlPlane(
            scenario="bursty",
            scenario_kw={"n_jobs": 100, "seed": 0},
            stealing=True,
            speculation=True,
        ).drain()
    payload = json.loads(json.dumps(s.trace.to_chrome_trace()))
    events = payload["traceEvents"]
    job_spans = [
        e for e in events if e["ph"] == "X" and e.get("cat") == "job"
    ]
    assert job_spans, "no complete job-lifecycle span in the trace"
    flow_ids = {
        (e["cat"], e["id"]) for e in events if e["ph"] == "s"
    } & {(e["cat"], e["id"]) for e in events if e["ph"] == "f"}
    assert flow_ids, "no steal/spec causality flow pair in the trace"
    records, strings = parse_chrome_trace(payload)
    assert records == s.trace.records()
    assert tuple(strings) == s.trace.strings


def test_trace_ring_wrap_keeps_run_schedule_identical():
    kw = dict(scenario="bursty", scenario_kw={"n_jobs": 30, "seed": 5})
    plain = ControlPlane(**kw).drain()
    with obs.observe(trace_capacity=32) as s:
        wrapped = ControlPlane(**kw).drain()
    assert _result_key(wrapped) == _result_key(plain)
    assert s.trace.dropped > 0
    assert len(s.trace) == 32


# ---- serve + inflight accounting -------------------------------------------


class _SlowPool:
    """Serve-pool stub whose single request finishes on the Nth heartbeat."""

    router = None

    def __init__(self, finish_after: int):
        self.finish_after = finish_after
        self.steps = 0
        self.pending = []

    def submit(self, request, *, model=None, adapter=None, eligible=None):
        self.pending.append(request)
        return 0

    def step(self):
        self.steps += 1
        if self.steps >= self.finish_after and self.pending:
            return [self.pending.pop()]
        return []

    def busy(self):
        return bool(self.pending)


class _Req:
    def __init__(self, rid):
        self.request_id = rid


def test_inflight_requests_surfaced_on_result():
    with obs.observe() as s:
        plane = ControlPlane(4, policy="wf", serve_pool=_SlowPool(3))
        plane.submit_request(8, at=0, request=_Req(7))
        plane.step_until(1)
        assert plane.result().inflight_requests == 1
        res = plane.drain()
    assert res.inflight_requests == 0
    # heartbeats tick at t=1,2,3; the 3rd finishes the request at t+1=4
    assert res.serve_latency[7] == 4
    assert s.metrics.counter("serve.requests") == 1
    assert s.metrics.counter("serve.completed") == 1
    serve_spans = [
        r for r in s.trace.records() if r[0] == trace_mod.SPAN_SERVE
    ]
    assert len(serve_spans) == 1
    assert serve_spans[0][2] == 4  # dur carries the latency in slots


# ---- ambient activation -----------------------------------------------------


def test_observe_scopes_nest_and_clear():
    assert active() is None
    with obs.observe(trace=False, device=False) as outer:
        assert active() is outer
        with obs.observe(trace=False, device=False) as inner:
            assert active() is inner
        assert active() is outer
    assert active() is None
