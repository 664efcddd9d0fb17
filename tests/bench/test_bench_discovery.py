"""A configuration, a traffic mix, a cell and a per-layer metric are
added by adding files and entries only: the harness finds each by name."""

import json

from bench import spec

NEW_METRIC = '''"""Test metric: jobs placed per step of the window."""


def read(ctx):
    return len(ctx.latencies_s) / max(1, len(ctx.gen_lags_s))
'''


def _add_metric(root):
    (root / "bench" / "layer_metrics" / "jobs_per_step.rate.py").write_text(NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "jobs_per_step.rate", "unit": "jobs", "better": "higher",
        "source": "host_clock", "layer": "load generator", "moves": "place_p95_ms",
        "workloads": ["tiny-wf-burst"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_parts_are_found_by_name(tiny_root):
    _add_metric(tiny_root)
    cell = spec.load_cell("tiny-wf-burst", tiny_root)
    assert cell.config["n_servers"] == 64
    assert cell.traffic["submit"] == "burst"
    assert "jobs_per_step.rate" in [m.name for m in cell.per_layer]
    assert {m.name for m in cell.end_to_end} == {"place_p50_ms", "place_p95_ms", "setup_s"}
    backlog = spec.load_cell("tiny-rd-backlog", tiny_root)
    assert {m.name for m in backlog.end_to_end} == {"tasks_per_s", "setup_s"}
    assert "jobs_per_step.rate" not in [m.name for m in backlog.per_layer]


def test_every_committed_cell_and_metric_resolves():
    bench = json.loads((spec.REPO_ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m.moves in {e.name for e in cell.end_to_end}


def test_added_cell_runs_end_to_end(tiny_root, cpu_harness):
    _add_metric(tiny_root)
    out = cpu_harness.run("tiny-wf-burst", 2**33 + 5, 1.0, False, 0.0, root=tiny_root)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"place_p50_ms", "place_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    cell = spec.load_cell("tiny-wf-burst", tiny_root)
    ctx = cpu_harness.Ctx(
        arrivals="open", n_servers=64, setup_s=0.0,
        latencies_s=[0.1] * 6, gen_lags_s=[0.0] * 2, tasks_placed=0,
        span_s=1.0, window_compiles=0,
    )
    reader = next(m for m in cell.per_layer if m.name == "jobs_per_step.rate")
    assert reader.read(ctx) == 3.0


def test_unknown_device_kind_has_no_peaks():
    import pytest

    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.load_peaks("cpu")
