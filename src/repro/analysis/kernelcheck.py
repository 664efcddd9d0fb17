"""kernelcheck — abstract-interpretation verifier for geometry contracts.

Run as ``python -m repro.analysis.kernelcheck``.  The driver imports the
modules that declare :func:`repro.analysis.contracts.contract` entries
(default: both kernel modules plus the ``wf_jax``/``rd_jax`` device
adapters), sweeps each contract's boundary-focused geometry lattice, and
proves four properties per entry point **without executing on any device**:

- **memory** — summed VMEM footprint of the declared Pallas blocks, each
  padded to whole (8, 128) tiles, stays within the budget
  (``--budget-mb``, default the kernels' scoped VMEM limit, 64 MiB);
- **range** — interval claims over the declared input envelope fit their
  dtypes / bit-fields (packed server ids, prefix sums, eq. 2 carries);
- **coverage** — every lattice point, including past-ceiling probes,
  dispatches to a declared backend (host fallback counts; an exception or
  an unknown backend name is a gap);
- **recompile surface** — the sweep's distinct jit-cache signatures stay
  within the declared bound, every signature component is static, and
  equal signatures imply identical abstract input shapes.

A sample of admissible device points is additionally traced through
``jax.eval_shape`` so shape/dtype errors in the jitted entry surface here
rather than on hardware.  Results land in a machine-readable JSON report
(``--report``, default ``results/KERNELCHECK.json``); exit status is 0
iff no contract has violations.

jax is imported lazily: importing this module (and ``repro.analysis``)
stays stdlib-only, but running the checks requires jax because the
contracted modules are the kernels themselves.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
from typing import Any

from .contracts import CONTRACTS, VMEM_LIMIT_BYTES, KernelContract, lattice

__all__ = ["DEFAULT_BUDGET_BYTES", "DEFAULT_MODULES", "check_contract", "main"]

# Modules whose import registers the repo's device entry-point contracts.
DEFAULT_MODULES = (
    "repro.kernels.waterlevel",
    "repro.kernels.rd",
    "repro.core.wf_jax",
    "repro.core.rd_jax",
)

# The scoped VMEM the kernels request from the TPU compiler.
DEFAULT_BUDGET_BYTES = VMEM_LIMIT_BYTES

# A VMEM tile is (8 sublanes of 32 bits, 128 lanes): narrower dtypes
# pack more rows into a sublane.
_TILE_SUBLANE_BYTES = 8 * 4
_TILE_LANES = 128

DEFAULT_REPORT = os.path.join("results", "KERNELCHECK.json")

_STATIC_LEAVES = (int, str, bool, type(None))


@dataclasses.dataclass(frozen=True)
class CheckViolation:
    contract: str
    check: str  # memory | range | coverage | recompile | abstract-eval
    geometry: dict[str, Any] | None
    detail: str

    def as_json(self) -> dict[str, Any]:
        return {
            "contract": self.contract,
            "check": self.check,
            "geometry": self.geometry,
            "detail": self.detail,
        }


def _tiled_bytes(shape: tuple[int, ...], itemsize: int) -> int:
    """Bytes a block occupies in VMEM: its last two dims round up to
    whole tiles (a ``(1, n)`` int32 row takes eight sublanes); leading
    dims count separate tiled arrays.  A 1-D block is one row."""
    *lead, rows, lanes = (1, 1, *shape)[-max(2, len(shape)):]
    sublanes = _TILE_SUBLANE_BYTES // itemsize
    rows = -(-rows // sublanes) * sublanes
    lanes = -(-lanes // _TILE_LANES) * _TILE_LANES
    return int(math.prod(lead)) * rows * lanes * itemsize


def _block_bytes(blocks: Any) -> tuple[int, dict[str, int]]:
    per_block: dict[str, int] = {}
    for name, (shape, itemsize) in blocks.items():
        per_block[name] = _tiled_bytes(tuple(shape), int(itemsize))
    return sum(per_block.values()), per_block


def _signature_static(sig: tuple) -> str | None:
    """Return a complaint if any signature leaf is not a static scalar."""
    for leaf in sig:
        if not isinstance(leaf, _STATIC_LEAVES):
            return (
                f"non-static signature component {leaf!r} "
                f"({type(leaf).__name__}): the jit cache key would depend "
                "on runtime data"
            )
    return None


def _sample(points: list, limit: int) -> list:
    """Evenly spaced sample including both extremes."""
    if limit <= 0 or len(points) <= limit:
        return list(points)
    if limit == 1:
        return [points[-1]]
    step = (len(points) - 1) / (limit - 1)
    idx = sorted({round(i * step) for i in range(limit)})
    return [points[i] for i in idx]


def check_contract(
    c: KernelContract,
    *,
    budget_bytes: int = DEFAULT_BUDGET_BYTES,
    eval_limit: int | None = None,
) -> tuple[dict[str, Any], list[CheckViolation]]:
    """Sweep one contract's lattice; return (report entry, violations)."""
    violations: list[CheckViolation] = []
    backend_hist: dict[str, int] = {}
    signatures: dict[tuple, dict[str, Any]] = {}
    device_points: list[tuple[dict[str, Any], str]] = []
    peak_vmem = 0
    n_points = 0

    for geom, admissible in lattice(c):
        n_points += 1
        try:
            backend = c.dispatch(dict(geom))
        except Exception as exc:  # a geometry with no dispatch path is a gap
            violations.append(
                CheckViolation(c.name, "coverage", geom, f"dispatch raised {exc!r}")
            )
            continue
        if backend not in c.backends:
            violations.append(
                CheckViolation(
                    c.name,
                    "coverage",
                    geom,
                    f"dispatch returned {backend!r}, not one of {c.backends}",
                )
            )
            continue
        backend_hist[backend] = backend_hist.get(backend, 0) + 1

        if backend == "pallas" and c.vmem is not None:
            total, per_block = _block_bytes(c.vmem(dict(geom)))
            peak_vmem = max(peak_vmem, total)
            if total > budget_bytes:
                breakdown = ", ".join(
                    f"{k}={v}B" for k, v in sorted(per_block.items())
                )
                violations.append(
                    CheckViolation(
                        c.name,
                        "memory",
                        geom,
                        f"VMEM blocks total {total} B > budget "
                        f"{budget_bytes} B ({breakdown})",
                    )
                )

        if not (admissible and backend in c.device_backends):
            continue
        device_points.append((geom, backend))

        if c.ranges is not None:
            for claim in c.ranges(dict(geom)):
                msg = claim.check()
                if msg is not None:
                    violations.append(CheckViolation(c.name, "range", geom, msg))

        if c.signature is not None:
            sig = c.signature(dict(geom))
            complaint = _signature_static(sig)
            if complaint is not None:
                violations.append(
                    CheckViolation(c.name, "recompile", geom, complaint)
                )
            else:
                signatures.setdefault(sig, geom)

    if (
        c.signature is not None
        and c.max_signatures is not None
        and len(signatures) > c.max_signatures
    ):
        violations.append(
            CheckViolation(
                c.name,
                "recompile",
                None,
                f"sweep induces {len(signatures)} distinct jit signatures "
                f"(declared bound {c.max_signatures}) — unbounded cache "
                "growth for this scenario class",
            )
        )

    n_eval = 0
    if c.abstract is not None and device_points:
        limit = c.eval_points if eval_limit is None else min(eval_limit, c.eval_points)
        sig_shapes: dict[tuple, tuple] = {}
        for geom, backend in _sample(device_points, limit):
            try:
                fn, args = c.abstract(dict(geom))
                import jax

                jax.eval_shape(fn, *args)
                n_eval += 1
            except Exception as exc:
                violations.append(
                    CheckViolation(
                        c.name,
                        "abstract-eval",
                        geom,
                        f"jax.eval_shape failed: {exc!r}",
                    )
                )
                continue
            if c.signature is None:
                continue
            sig = c.signature(dict(geom))
            shapes = tuple(tuple(int(d) for d in a.shape) for a in args)
            prev = sig_shapes.setdefault(sig, shapes)
            if prev != shapes:
                violations.append(
                    CheckViolation(
                        c.name,
                        "recompile",
                        geom,
                        f"signature {sig!r} maps to distinct abstract "
                        f"shapes {prev} vs {shapes} — the cache key "
                        "underdetermines the trace (shape is data-dependent)",
                    )
                )

    checks = {
        "memory": "skipped" if c.vmem is None else "ok",
        "range": "skipped" if c.ranges is None else "ok",
        "coverage": "ok",
        "recompile": "skipped" if c.signature is None else "ok",
        "abstract-eval": "skipped" if c.abstract is None else "ok",
    }
    for v in violations:
        checks[v.check] = "violated"

    entry = {
        "contract": c.name,
        "entry": c.entry,
        "module": c.module,
        "lattice_points": n_points,
        "backends": dict(sorted(backend_hist.items())),
        "distinct_signatures": len(signatures) if c.signature is not None else None,
        "max_signatures": c.max_signatures,
        "peak_vmem_bytes": peak_vmem if c.vmem is not None else None,
        "abstract_evals": n_eval,
        "checks": checks,
        "violations": [v.as_json() for v in violations],
        "notes": c.notes,
    }
    return entry, violations


def _import_module(spec: str):
    """Import a contract module by dotted name or filesystem path."""
    if spec.endswith(".py") or os.sep in spec:
        name = "kernelcheck_fixture_" + os.path.splitext(os.path.basename(spec))[0]
        if name in sys.modules:
            return sys.modules[name]
        loader_spec = importlib.util.spec_from_file_location(name, spec)
        if loader_spec is None or loader_spec.loader is None:
            raise ImportError(f"cannot load contract module from {spec!r}")
        mod = importlib.util.module_from_spec(loader_spec)
        sys.modules[name] = mod
        loader_spec.loader.exec_module(mod)
        return mod
    return importlib.import_module(spec)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.kernelcheck",
        description="abstract-interpretation verifier for jit/Pallas geometry contracts",
    )
    parser.add_argument(
        "--modules",
        nargs="+",
        default=list(DEFAULT_MODULES),
        help="contract modules to import (dotted names or .py paths); "
        "only contracts defined by these modules are checked",
    )
    parser.add_argument(
        "--entry",
        action="append",
        default=None,
        help="check only the named contract(s); repeatable",
    )
    parser.add_argument(
        "--budget-mb",
        type=float,
        default=DEFAULT_BUDGET_BYTES / (1024 * 1024),
        help="VMEM budget per kernel invocation in MiB (default: %(default)s)",
    )
    parser.add_argument(
        "--max-eval",
        type=int,
        default=None,
        help="cap the number of jax.eval_shape points per contract",
    )
    parser.add_argument(
        "--report",
        default=DEFAULT_REPORT,
        help="JSON report path (default: %(default)s)",
    )
    parser.add_argument(
        "--list", action="store_true", help="list registered contracts and exit"
    )
    args = parser.parse_args(argv)

    module_names = []
    for spec in args.modules:
        mod = _import_module(spec)
        module_names.append(mod.__name__)

    selected = [
        c
        for _, c in sorted(CONTRACTS.items())
        if c.module in module_names
        and (args.entry is None or c.name in args.entry)
    ]
    if args.list:
        for c in selected:
            print(f"{c.name}: {c.entry} ({len(c.axes)} axes)")
        return 0
    if not selected:
        print("kernelcheck: no contracts registered by the requested modules")
        return 2

    budget_bytes = int(args.budget_mb * 1024 * 1024)
    entries = []
    all_violations: list[CheckViolation] = []
    for c in selected:
        entry, violations = check_contract(
            c, budget_bytes=budget_bytes, eval_limit=args.max_eval
        )
        entries.append(entry)
        all_violations.extend(violations)
        status = "OK" if not violations else f"{len(violations)} violation(s)"
        print(
            f"kernelcheck: {c.name}: {entry['lattice_points']} lattice points, "
            f"backends {entry['backends']}, {status}"
        )

    report = {
        "tool": "kernelcheck",
        "budget_bytes": budget_bytes,
        "modules": module_names,
        "contracts": entries,
        "total_violations": len(all_violations),
    }
    report_dir = os.path.dirname(args.report)
    if report_dir:
        os.makedirs(report_dir, exist_ok=True)
    with open(args.report, "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"kernelcheck: report written to {args.report}")

    if all_violations:
        for v in all_violations:
            print(f"kernelcheck: VIOLATION [{v.check}] {v.contract}: {v.detail}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
