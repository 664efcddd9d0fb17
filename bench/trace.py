"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is read into plain data first (:func:`load`): a list of planes,
each ``{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}``.  :func:`reduce` then
works on that plain data alone, so a small recorded trace checks it.

- Device busy time is the union of the op intervals on the device
  planes' ``XLA Ops`` lines, clipped to the traced window; idle is the
  rest of the window.
- An op's event name is its HLO text.  Ops are keyed by that text up to
  the opcode (``%fusion.24 = s32[12500]{...} fusion``).  While and cond ops span the ops of their
  bodies, so each op's self time (its time less that of the ops it
  spans) ranks the costliest.
- A Pallas kernel is a ``custom-call`` op named after the jitted function
  that calls ``pallas_call`` (``_waterlevel_call_padded`` around
  ``_waterlevel_kernel``, ``_rd_strip_call`` around ``_rd_strip_kernel``):
  the kernels carry no ``name=`` of their own.
- A program is found by its jitted function's name in the events of the
  ``XLA Modules`` lines.
- Each idle gap is put down to the innermost host event around its
  midpoint, and gaps are summed by that name.
- A trace in which the chip ran no op inside the window (no device
  plane, as on the CPU, or device planes with no op event there, as when
  the program placed every job on the host) reads busy 0: the window is
  one idle gap, put down to host events like any other.
"""

from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


def load(trace_dir: str) -> list[dict]:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain planes."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            # one string per distinct op: an op's HLO text repeats per launch
            names: dict[str, str] = {}
            events = []
            for ev in line.events:
                name = ev.name
                events.append([names.setdefault(name, name), ev.start_ns, ev.duration_ns])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def op_key(text: str) -> str:
    """An HLO op's text up to its opcode: name, result shape, opcode."""
    eq = text.find(" = ")
    if eq < 0:
        return text[:120]
    m = _OPCODE.search(text, eq + 2)
    return text[: m.end(1)] if m else text[:120]


def _self_times(events: list) -> list[float]:
    """Each event's duration less that of the events nested in it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [e - s for s, e in events]
    stack: list[int] = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _window(planes: list[dict]) -> tuple[float, float, list]:
    """The window span and the host thread's events that it sits on."""
    for plane in planes:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur, line["events"]
    raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")


def _innermost(spans: list, points: list[float]) -> list[str]:
    """For each point, the innermost span of one thread around it (its
    spans nest); a sweep over spans and points in time order."""
    spans = sorted((s, s + d, n) for n, s, d in spans if d > 0 and n != WINDOW_SPAN)
    order = sorted(range(len(points)), key=points.__getitem__)
    out = ["(no host span)"] * len(points)
    stack: list[tuple[float, float, str]] = []
    i = 0
    for j in order:
        p = points[j]
        while i < len(spans) and spans[i][0] <= p:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        if stack:
            out[j] = stack[-1][2]
    return out


def find(table: dict[str, dict], key: str, opcode: str | None = None) -> tuple[int, float]:
    """Launches and device seconds of the rows of ``table`` (``ops`` or
    ``programs`` of :func:`reduce`) whose name holds ``key`` (and, for
    ops, whose opcode is ``opcode``)."""
    rows = [
        r for n, r in table.items()
        if key in n and (opcode is None or n.endswith(" " + opcode))
    ]
    return sum(r["count"] for r in rows), sum(r["total_s"] for r in rows)


def reduce(planes: list[dict], top: int = 10) -> dict:
    """Busy and window seconds, launch counts and device seconds of every
    op and every program, the costliest ops and the idle gaps by host
    activity, all inside the ``bench.window`` span (times averaged over
    the device planes that ran something).  ``idle_gaps`` holds the
    ``top`` largest idle labels, ``idle_by_label`` every one, and
    ``host_spans`` counts each host event name that starts in the window
    on the window's host line."""
    ws, we, host = _window(planes)
    device_planes = [
        p for p in planes
        if p["name"].startswith(DEVICE_PREFIX)
        and any(line["name"] == OPS_LINE and line["events"] for line in p["lines"])
    ]
    busy_ns = 0.0
    ops: dict[str, dict] = {}
    programs: dict[str, dict] = {}
    merged_all: list[list[tuple[float, float]]] = []
    for plane in device_planes:
        ivs = []
        for line in plane["lines"]:
            if line["name"] not in (OPS_LINE, MODULES_LINE):
                continue
            kept = [
                (name, max(start, ws), min(start + dur, we))
                for name, start, dur in line["events"]
                if min(start + dur, we) > max(start, ws)
            ]
            spans = [(s, e) for _, s, e in kept]
            is_ops = line["name"] == OPS_LINE
            table = ops if is_ops else programs
            selfs = _self_times(spans) if is_ops else [e - s for s, e in spans]
            if is_ops:
                ivs.extend(spans)
            for (name, s, e), own in zip(kept, selfs):
                key = op_key(name) if is_ops else name
                row = table.setdefault(key, {"count": 0, "total_s": 0.0, "self_s": 0.0})
                row["count"] += 1
                row["total_s"] += (e - s) * 1e-9
                row["self_s"] += own * 1e-9
        merged = _merge(ivs)
        merged_all.append(merged)
        busy_ns += sum(e - s for s, e in merged)
    n_dev = max(len(device_planes), 1)
    gaps: dict[str, float] = {}
    for merged in merged_all or [[]]:  # no device ran: the window is one gap
        edges = [ws] + [x for iv in merged for x in iv] + [we]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        labels = _innermost(host, [(s + e) / 2 for s, e in idle])
        for (s, e), label in zip(idle, labels):
            gaps[label] = gaps.get(label, 0.0) + (e - s) * 1e-9 / n_dev
    return {
        "busy_s": busy_ns * 1e-9 / n_dev,
        "window_s": (we - ws) * 1e-9,
        "ops": ops,
        "programs": programs,
        "device_ops": [
            [n, r["self_s"] / n_dev]
            for n, r in sorted(ops.items(), key=lambda kv: -kv[1]["self_s"])[:top]
        ],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:top],
        "idle_by_label": gaps,
        "host_spans": dict(collections.Counter(
            name for name, start, _ in host if ws <= start < we and name != WINDOW_SPAN
        )),
    }
