"""The tiny configuration, mixes and cells the benchmark's tests run on
the CPU, and a checkout-shaped copy of the benchmark that adds them."""

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny64",
    "source": "test size of the bursty job model",
    "n_servers": 64,
    "job_model": {
        "generator": "bursty",
        "trace_jobs": 4000,
        "trace_tasks": 160000,
        "size_sigma": 1.6,
        "mean_burst": 6.0,
        "mean_groups_per_job": 5.52,
        "zipf_alpha": 1.0,
        "avail_lo": 8,
        "avail_hi": 12,
        "cap_lo": 3,
        "cap_hi": 5,
        "utilization": 0.5,
    },
}

TINY_TRAFFIC = {
    "tiny-burst": {"policy": "wf_jax", "reference": "wf", "ordering": "fifo",
                   "arrivals": "open", "submit": "burst", "rate_jobs_per_s": 120.0,
                   "work_seed": 0},
    "tiny-single": {"policy": "wf_jax", "reference": "wf", "ordering": "fifo",
                    "arrivals": "open", "submit": "job", "rate_jobs_per_s": 60.0,
                    "work_seed": 0},
    "tiny-backlog": {"policy": "rd", "reference": "rd", "ordering": "fifo",
                     "arrivals": "backlog", "submit": "job", "backlog_jobs": 400,
                     "work_seed": 0},
}

TINY_CELLS = {
    "tiny-wf-burst": "tiny-burst",
    "tiny-wf-single": "tiny-single",
    "tiny-rd-backlog": "tiny-backlog",
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory: the benchmark's files plus the tiny
    configuration, mixes and cells, added as files and entries only."""
    shutil.copytree(ROOT / "bench", tmp / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs" / "tiny64.json").write_text(json.dumps(TINY_CONFIG))
    for name, mix in TINY_TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec["configs"].append({"name": "tiny64", "source": "test", "file": "bench/configs/tiny64.json",
                            "reduced": [], "why": "test size"})
    for cell, mix in TINY_CELLS.items():
        spec["workloads"].append({"name": cell, "config": "tiny64", "traffic": mix,
                                  "chips": 1, "why": "test size"})
    names = {m["name"] for m in spec["end_to_end"]}
    for name in ("place_p50_ms", "place_p95_ms"):  # open-loop metrics, if no cell has them
        if name not in names:
            spec["end_to_end"].append({"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
                                       "source": "host_clock", "workloads": []})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = {"place_p50_ms", "place_p95_ms"} | {
                n["name"] for n in spec["per_layer"] if n.get("moves") == "place_p95_ms"
            }
            m["workloads"] += (
                ["tiny-wf-burst", "tiny-wf-single"] if m["name"] in kinds else ["tiny-rd-backlog"]
            )
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
