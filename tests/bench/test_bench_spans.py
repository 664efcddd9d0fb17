"""The program's spans on the profiler's clock: on the CPU a profiler
trace shows the tick phases, ``sched.admit`` and the RD dispatch phases
on ``bench.window``'s host line; and the readers of those spans, of the
RD loop counter and of the idle time the host was not waiting in, on a
hand-built trace with known answers."""

import numpy as np
import pytest

from bench import harness, spec, trace


def _ev(name, start, dur):
    return [name, float(start), float(dur)]


def _planes(spans: bool):
    """One RD dispatch in a 1,000 ns window.  Idle: [40,110) in
    ``rd.prep``, [300,320) in ``PjitFunction(_rd_device)`` nested in
    ``rd.wait``, [560,610) in ``rd.wait``, [620,720) in ``rd.readback``
    and [900,960) in ``tick.service``: 300 ns, of which 70 waiting.
    Without ``spans`` the host line holds what a program without the
    spans shows (the jax events alone)."""
    host = [
        _ev("bench.window", 0, 1000),
        _ev("bench.step_until", 0, 1000),
        _ev("tick.arrival", 0, 900),
        _ev("sched.admit", 10, 880),
        _ev("rd.prep", 20, 80),
        _ev("device.rd-device", 90, 610),
        _ev("DevicePut", 90, 8),
        _ev("rd.wait", 100, 500),
        _ev("PjitFunction(_rd_device)", 300, 30),
        _ev("rd.readback", 600, 90),
        _ev("rd.decode", 700, 180),
        _ev("tick.service", 900, 50),
    ]
    if not spans:
        host = [e for e in host if not e[0].startswith(("rd.", "tick.", "sched.", "device."))]
    ops = [(0, 40), (110, 300), (320, 560), (610, 620), (720, 900), (960, 1000)]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                _ev(f"%fusion.{i} = s32[8]{{0}} fusion(s32[8]{{0}} %a)", s, e - s)
                for i, (s, e) in enumerate(ops)
            ]},
            {"name": "XLA Modules", "events": [_ev("jit__rd_device(42)", 110, 450)]},
        ]},
    ]


OBS = {
    "rd.prep.us": (2, 3_000),
    "rd.wait.us": (2, 700_000),
    "rd.readback.us": (2, 400),
    "rd.decode.us": (2, 5_000),
    "rd.iters": (2, 1_000),
    "rd.moved": (2, 2_280),
}


def _ctx(spans: bool):
    ctx = harness.Ctx("backlog", 64, 0.0, [], [], 0, 1.0, 0, trace=trace.reduce(_planes(spans)))
    ctx.obs = dict(OBS) if spans else {"sched.overhead_us": (2, 700_000)}
    return ctx


def _read(name, ctx):
    return spec._load_reader(spec.BENCH_DIR / "layer_metrics" / f"{name}.py")(ctx)


@pytest.mark.parametrize(
    "name,want",
    [
        ("rd_prep_ms", 1.5),
        ("rd_wait_ms", 350.0),
        ("rd_readback_ms", 0.2),
        ("rd_decode_ms", 2.5),
        ("rd_iters_per_job", 500.0),
        ("rd_iter_us", 450e-9 / 1_000 * 1e6),  # 450 ns of _rd_device over 1,000 iterations
        ("rd_movers_per_iter", 2.28),  # 2,280 classes moved over 1,000 iterations
        ("idle_host_pct.backlog", 23.0),  # 30% idle less 7% waiting
    ],
)
def test_span_reader(name, want):
    assert _read(name, _ctx(True)) == pytest.approx(want)
    # a program without the spans and the counter: nothing to read, no number
    assert _read(name, _ctx(False)) is None


def test_idle_splits_into_host_and_wait():
    ctx = _ctx(True)
    gaps = dict(ctx.trace["idle_gaps"])
    wait = sum(gaps.get(label, 0.0) for label in ("rd.wait", "PjitFunction(_rd_device)"))
    assert wait == pytest.approx(70e-9)
    assert gaps["rd.prep"] == pytest.approx(70e-9)
    assert gaps["rd.readback"] == pytest.approx(100e-9)
    assert _read("device_idle_pct.backlog", ctx) == pytest.approx(
        _read("idle_host_pct.backlog", ctx) + 100 * wait / ctx.trace["window_s"]
    )


def _gapped(spans):
    """Host spans of 100 ns back to back, each ``(name, idle_ns)``: the
    chip runs ops over each span but an idle gap of ``idle_ns`` in its
    middle."""
    host = [_ev("bench.window", 0, 100 * len(spans))]
    ops = []
    for i, (name, idle) in enumerate(spans):
        s = 100 * i
        host.append(_ev(name, s, 100))
        ops += [(s, s + 50 - idle // 2), (s + 50 - idle // 2 + idle, s + 100)]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            _ev(f"%fusion.{i} = s32[8]{{0}} fusion(s32[8]{{0}} %a)", a, b - a)
            for i, (a, b) in enumerate(ops) if b > a
        ]}]},
    ]


def _backlog_ctx(spans):
    return harness.Ctx("backlog", 64, 0.0, [], [], 0, 1.0, 0, trace=trace.reduce(_gapped(spans)))


def test_wait_below_the_largest_ten_labels():
    """Eleven host labels each leave the chip idle longer than ``rd.wait``
    does, so ``rd.wait`` is not among ``idle_gaps``; the reader takes it
    from the full table: 230 ns idle in 1,200, 10 of them waiting."""
    ctx = _backlog_ctx([(f"host.{i}", 20) for i in range(11)] + [("rd.wait", 10)])
    assert "rd.wait" not in dict(ctx.trace["idle_gaps"])
    assert _read("device_idle_pct.backlog", ctx) == pytest.approx(100 * 230 / 1200)
    assert _read("idle_host_pct.backlog", ctx) == pytest.approx(100 * 220 / 1200)


def test_wait_with_no_idle_reads_all_idle_as_host():
    """``rd.wait`` spans in the window but the chip busy under them: every
    idle gap is host work."""
    ctx = _backlog_ctx([("rd.prep", 30), ("rd.wait", 0), ("rd.decode", 40), ("rd.wait", 0)])
    assert "rd.wait" not in ctx.trace["idle_by_label"]
    assert ctx.trace["host_spans"]["rd.wait"] == 2
    assert _read("idle_host_pct.backlog", ctx) == pytest.approx(
        _read("device_idle_pct.backlog", ctx), rel=1e-12
    )
    assert _read("idle_host_pct.backlog", ctx) == pytest.approx(100 * 70 / 400)


def test_no_wait_span_reads_nothing():
    """No ``rd.wait`` span in the window (RD never dispatched to the
    device): the share has nothing to split, though the chip idled."""
    ctx = _backlog_ctx([("rd.host", 30), ("tick.service", 20)])
    assert _read("device_idle_pct.backlog", ctx) == pytest.approx(25.0)
    assert _read("idle_host_pct.backlog", ctx) is None


def test_program_spans_reach_the_profiler_host_line(tmp_path):
    """On the CPU: a control plane placing jobs with the device RD, under
    an obs session without its own trace (as the traced benchmark run
    opens it), inside a profiler trace.  The spans land on the host line
    that holds ``bench.window``."""
    import jax

    from repro import obs
    from repro.backend import set_backend
    from repro.core import Job, TaskGroup
    from repro.runtime import ControlPlane

    mu = np.array([2, 1, 1, 2, 1, 1])
    jobs = [
        Job(job_id=i, arrival=i,
            groups=(TaskGroup(5 + i, (i % 6, (i + 1) % 6, (i + 3) % 6)), TaskGroup(3, (1, 2))),
            mu=mu)
        for i in range(3)
    ]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with set_backend(rd="jnp"), obs.observe(trace=False):
        plane = ControlPlane(n_servers=6, policy="rd")
        plane.submit_many(jobs)
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                plane.drain()
        finally:
            jax.profiler.stop_trace()
    lines = [
        line for plane_ in trace.load(str(tmp_path)) for line in plane_["lines"]
        if any(e[0] == trace.WINDOW_SPAN for e in line["events"])
    ]
    assert len(lines) == 1
    names = {e[0] for e in lines[0]["events"]}
    want = {"tick.arrival", "tick.service", "sched.admit", "device.rd-device",
            "rd.prep", "rd.wait", "rd.readback", "rd.decode"}
    assert want <= names, want - names
