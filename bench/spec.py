"""Find a cell's parts by name.

A cell is one ``workloads`` entry of ``BENCHMARK.json``: it names a
configuration (``<bench>/configs/<config>.json``) and a traffic mix
(``<bench>/traffic/<traffic>.json``).  Each metric is a reader of its
own, ``<bench>/end_to_end/<name>.py`` or ``<bench>/layer_metrics/<name>.py``,
whose ``read(ctx)`` returns a number or ``None`` when the run holds
nothing for it to read.  Adding a configuration, a mix, a cell or a
metric therefore adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: str | None  # per-layer metrics only
    read: Callable


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_reader(path: pathlib.Path) -> Callable:
    # metric names hold dots (``admit_ms.rate``), so load by file location
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no metric reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric(entry: dict, kind_dir: pathlib.Path) -> Metric:
    return Metric(
        name=entry["name"],
        unit=entry["unit"],
        moves=entry.get("moves"),
        read=_load_reader(kind_dir / f"{entry['name']}.py"),
    )


def load_cell(name: str, root: pathlib.Path = REPO_ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metric readers it reports."""
    spec = _load_json(root / "BENCHMARK.json")
    bench = root / spec["paths"][0]
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(bench / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(
        _metric(m, bench / "end_to_end")
        for m in spec["end_to_end"]
        if m.get("workloads") is None or name in m["workloads"]
    )
    e2e_names = {m.name for m in e2e}
    layer = tuple(
        _metric(m, bench / "layer_metrics")
        for m in spec["per_layer"]
        if (m.get("workloads") is None and m["moves"] in e2e_names)
        or name in m.get("workloads", ())
    )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=layer,
    )


def load_peaks(device_kind: str, root: pathlib.Path = REPO_ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    spec = _load_json(root / "BENCHMARK.json")
    table = _load_json(root / spec["paths"][0] / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]
