"""The reduction from a profiler trace to busy time, idle gaps, kernel
launches and program time, on a hand-built trace with known answers and
on a small trace recorded on the chip; a window in which the chip ran
nothing, reduced and in a whole traced run; and the byte count behind
``wf_kernel_roofline_pct``."""

import json
import pathlib

import pytest

from bench import harness, roofline, spec, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return [name, float(start), float(dur)]


HAND = [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        _ev("bench.window", 0, 1000),
        _ev("bench.wait", 0, 100),
        _ev("bench.step_until", 100, 500),
        _ev("PjitFunction(water_fill_chain)", 150, 50),
        _ev("bench.wait", 600, 400),
    ]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            _ev("%while.2 = (s32[], s32[8]{0}) while((s32[], s32[8]{0}) %t)", 200, 220),
            _ev("%fusion.1 = s32[8]{0:T(128)} fusion(s32[8]{0} %a)", 120, 60),
            _ev("%_waterlevel_call_padded.3 = (s32[1]{0:T(128)}, s32[8]{0}) "
                "custom-call(s32[1]{0:T(128)} %b), custom_call_target=\"tpu_custom_call\"", 200, 100),
            _ev("%fusion.2 = s32[8]{0:T(128)} fusion(s32[8]{0} %c)", 300, 120),
            _ev("%fusion.3 = s32[8]{0:T(128)} fusion(s32[8]{0} %d)", 900, 200),  # clipped
        ]},
        {"name": "XLA Modules", "events": [_ev("jit_water_fill_chain", 110, 250)]},
    ]},
]


def test_hand_built_trace():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(380e-9)  # [120,180] + [200,420] + [900,1000]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.wait"] == pytest.approx(600e-9)  # [0,120] and [420,900]
    assert gaps["PjitFunction(water_fill_chain)"] == pytest.approx(20e-9)
    assert trace.find(r["ops"], "_waterlevel_call", "custom-call") == (1, pytest.approx(100e-9))
    assert trace.find(r["ops"], "_waterlevel_call", "fusion") == (0, 0)
    assert trace.find(r["programs"], "water_fill_chain") == (1, pytest.approx(250e-9))
    # the while spans its body: its self time is what its children leave
    top = dict(r["device_ops"])
    assert r["device_ops"][0] == ["%fusion.2 = s32[8]{0:T(128)} fusion", pytest.approx(120e-9)]
    assert top["%while.2 = (s32[], s32[8]{0}) while"] == pytest.approx(0.0, abs=1e-15)


def test_idle_share_reader():
    r = trace.reduce(HAND)
    read = spec._load_reader(spec.BENCH_DIR / "layer_metrics" / "device_idle_pct.rate.py")
    ctx = harness.Ctx("open", 64, 0.0, [], [], 0, 1.0, 0, trace=r)
    assert read(ctx) == pytest.approx(62.0)


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("trace_*.json")))
def test_recorded_trace(name):
    rec = json.loads((DATA / name).read_text())
    r = trace.reduce(rec["planes"])
    want = rec["expect"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for key, (count, total) in want["kernels"].items():
        assert trace.find(r["ops"], key, "custom-call") == (count, pytest.approx(total, rel=1e-9))


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("trace_*.json")))
def test_recorded_trace_idle_table(name):
    """Every idle label is kept: the ten largest are ``idle_gaps``, and
    with busy they fill the window."""
    r = trace.reduce(json.loads((DATA / name).read_text())["planes"])
    full = sorted(r["idle_by_label"].items(), key=lambda kv: -kv[1])
    assert [k for k, _ in r["idle_gaps"]] == [k for k, _ in full[:10]]
    assert dict(r["idle_gaps"]) == {k: v for k, v in full[:10]}
    assert sum(r["idle_by_label"].values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    assert r["host_spans"]


HOST = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
    _ev("bench.window", 1000, 1000),
    _ev("bench.step_until", 1000, 1000),
    _ev("sched.admit", 1100, 800),
    _ev("rd.host", 1200, 600),
    _ev("bench.submit", 2500, 10),  # after the window
]}]}
OPS_OUTSIDE = [
    _ev("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a)", 100, 400),
    _ev("%_rd_strip_call.2 = (s32[1,8]{1,0}) custom-call(s32[1,8]{1,0} %b)", 2000, 50),
]


@pytest.mark.parametrize("device", [
    [],  # no device plane, as a CPU trace
    [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": []},
                                         {"name": "XLA Modules", "events": []}]}],
    [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": OPS_OUTSIDE},
        {"name": "XLA Modules", "events": [_ev("jit__rd_device(7)", 2000, 60)]},
    ]}],
], ids=["no-device-plane", "empty-op-lines", "ops-outside-window"])
def test_chip_ran_nothing(device):
    """A window in which the chip ran no op reads busy 0 and 100% idle, put
    down to host spans; the readers of device ops read nothing."""
    r = trace.reduce([HOST] + device)
    assert r["busy_s"] == 0
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["ops"] == {} and r["programs"] == {} and r["device_ops"] == []
    assert r["idle_gaps"] == [["rd.host", pytest.approx(1000e-9)]]
    assert r["idle_by_label"] == {"rd.host": pytest.approx(1000e-9)}
    assert r["host_spans"] == {"bench.step_until": 1, "sched.admit": 1, "rd.host": 1}
    ctx = harness.Ctx("backlog", 100_000, 0.0, [], [], 0, 1.0, 0, trace=r)
    ctx.obs = {"rd.iters": (2, 1_000)}
    read = {
        name: spec._load_reader(spec.BENCH_DIR / "layer_metrics" / f"{name}.py")
        for name in ("device_idle_pct.backlog", "rd_strip_us", "rd_kernel_us", "rd_iter_us")
    }
    assert read["device_idle_pct.backlog"](ctx) == 100.0
    for name in ("rd_strip_us", "rd_kernel_us", "rd_iter_us"):
        assert read[name](ctx) is None, name


def test_traced_run_on_a_chip_that_ran_nothing(tiny_root, cpu_harness, monkeypatch):
    """The whole traced run on the CPU, whose trace has no device plane
    (as a program that places every job on the host): it completes,
    correct, and reports the chip idle for the whole window."""
    monkeypatch.setattr(spec, "load_peaks", lambda kind, root: {"hbm_bytes_per_s": 819e9})
    out = cpu_harness.run("tiny-rd-backlog", 2**33 + 11, 1.0, True, 0.0, root=tiny_root)
    assert out["correct"], out["checks"]
    assert out["device"]["busy_s"] == 0 and out["device"]["window_s"] > 0
    assert out["metrics"]["device_idle_pct.backlog"] == {"value": 100.0, "unit": "%"}
    assert out["breakdown"]["device_ops"] == []
    assert sum(v for _, v in out["breakdown"]["idle_gaps"]) > 0
    assert not {"rd_strip_us", "rd_kernel_us", "rd_iter_us"} & set(out["metrics"])
    assert list(out)[-1] == "checks"


def test_wf_level_bytes_count_the_real_servers():
    assert roofline.wf_level_bytes(12_500) == 13 * 12_500
    assert roofline.wf_level_bytes(1_300) == 16_900


def test_roofline_share():
    read = spec._load_reader(spec.BENCH_DIR / "layer_metrics" / "wf_kernel_roofline_pct.py")
    ops = {"%_waterlevel_call_padded.3 = (s32[1]) custom-call": {"count": 4, "total_s": 4e-6}}
    ctx = harness.Ctx(
        "open", 12_500, 0.0, [], [], 0, 1.0, 0,
        trace={"ops": ops}, wf_groups=2, peaks={"hbm_bytes_per_s": 819e9},
    )
    # 2 levels x 162,500 bytes at 819 GB/s over 4 us of kernel time
    assert read(ctx) == pytest.approx(100 * 2 * 162_500 / 819e9 / 4e-6)
    ctx.trace = {"ops": {}}
    assert read(ctx) is None  # nothing to read: no number, never 0
