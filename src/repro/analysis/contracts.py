"""Geometry contracts for device entry points (the kernelcheck registry).

A :class:`KernelContract` declares, for one registered device entry point,
the geometry lattice it must be checked over and the facts the checker
(`python -m repro.analysis.kernelcheck`) proves at every lattice point:

- ``dispatch`` — which backend a geometry routes to (coverage: every point,
  including past-ceiling probes, must resolve to a declared backend or the
  host fallback; an exception is a coverage gap);
- ``vmem`` — the Pallas block shapes materialised per kernel invocation
  (memory: their summed footprint must fit the VMEM budget);
- ``ranges`` — interval claims over the declared input envelope (range:
  packed bit-fields and accumulating dtypes cannot overflow);
- ``signature`` — the static jit-cache key a geometry induces (recompile
  surface: the sweep's distinct signatures stay bounded and fully static);
- ``abstract`` — a callable + ``ShapeDtypeStruct`` args handed to
  ``jax.eval_shape`` so the trace itself is exercised without a device.

This module is stdlib-only on purpose: the kernels modules decorate their
entry points with :func:`contract` at import time, and nothing here may
drag in jax (the reprolint CI job imports ``repro.analysis`` without it).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, Mapping

__all__ = [
    "INT32_MAX",
    "INT32_MIN",
    "Axis",
    "CONTRACTS",
    "Interval",
    "KernelContract",
    "RangeClaim",
    "VMEM_LIMIT_BYTES",
    "choice",
    "contract",
    "lattice",
    "register",
    "span",
]

INT32_MIN = -(1 << 31)
INT32_MAX = (1 << 31) - 1

# Scoped VMEM the single-block Pallas kernels (water level, RD strip) ask
# the TPU compiler for, and so kernelcheck's default memory budget: its
# proof is a claim about this compile.  The compiler's default, 16 MiB,
# is less than both kernels need at their ceilings; a v5e core has
# 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

_DTYPE_BOUNDS = {
    "int32": (INT32_MIN, INT32_MAX),
    "int64": (-(1 << 63), (1 << 63) - 1),
}


@dataclasses.dataclass(frozen=True)
class Interval:
    """Closed integer interval ``[lo, hi]`` with conservative arithmetic."""

    lo: int
    hi: int

    def __post_init__(self) -> None:
        if self.lo > self.hi:
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    @staticmethod
    def const(value: int) -> "Interval":
        return Interval(value, value)

    @staticmethod
    def _coerce(value: "Interval | int") -> "Interval":
        return value if isinstance(value, Interval) else Interval.const(int(value))

    def __add__(self, other: "Interval | int") -> "Interval":
        o = Interval._coerce(other)
        return Interval(self.lo + o.lo, self.hi + o.hi)

    __radd__ = __add__

    def __neg__(self) -> "Interval":
        return Interval(-self.hi, -self.lo)

    def __sub__(self, other: "Interval | int") -> "Interval":
        return self + (-Interval._coerce(other))

    def __rsub__(self, other: "Interval | int") -> "Interval":
        return Interval._coerce(other) + (-self)

    def __mul__(self, other: "Interval | int") -> "Interval":
        o = Interval._coerce(other)
        corners = (
            self.lo * o.lo,
            self.lo * o.hi,
            self.hi * o.lo,
            self.hi * o.hi,
        )
        return Interval(min(corners), max(corners))

    __rmul__ = __mul__

    def __lshift__(self, bits: int) -> "Interval":
        if self.lo < 0:
            raise ValueError("left shift of a possibly-negative interval")
        return Interval(self.lo << bits, self.hi << bits)

    def __or__(self, other: "Interval | int") -> "Interval":
        # Bit-packing bound: for non-negative a, b we have
        # max(a, b) <= a | b <= a + b, which is exact for disjoint fields.
        o = Interval._coerce(other)
        if self.lo < 0 or o.lo < 0:
            raise ValueError("bitwise-or bound requires non-negative intervals")
        return Interval(max(self.lo, o.lo), self.hi + o.hi)


@dataclasses.dataclass(frozen=True)
class RangeClaim:
    """One overflow/ordering claim the range check validates.

    ``dtype`` asserts the interval fits the dtype; ``bits`` asserts it fits
    an unsigned bit-field of that width (e.g. a 15-bit packed server id);
    ``bound`` asserts ``value.hi <= bound`` (envelope preservation, e.g.
    "the evolved busy vector still satisfies the kernel's precondition");
    ``positive`` asserts ``value.lo > 0`` (sentinel-headroom ordering).
    """

    name: str
    value: Interval
    dtype: str | None = "int32"
    bits: int | None = None
    bound: int | None = None
    positive: bool = False

    def check(self) -> str | None:
        v = self.value
        if self.dtype is not None:
            lo, hi = _DTYPE_BOUNDS[self.dtype]
            if v.lo < lo or v.hi > hi:
                return (
                    f"{self.name}: interval [{v.lo}, {v.hi}] exceeds "
                    f"{self.dtype} [{lo}, {hi}]"
                )
        if self.bits is not None and (v.lo < 0 or v.hi >= (1 << self.bits)):
            return (
                f"{self.name}: interval [{v.lo}, {v.hi}] does not fit an "
                f"unsigned {self.bits}-bit field"
            )
        if self.bound is not None and v.hi > self.bound:
            return (
                f"{self.name}: interval high {v.hi} exceeds declared "
                f"bound {self.bound}"
            )
        if self.positive and v.lo <= 0:
            return f"{self.name}: interval low {v.lo} is not strictly positive"
        return None


@dataclasses.dataclass(frozen=True)
class Axis:
    """One lattice axis: admissible ``points`` plus ``past``-ceiling probes.

    ``past`` values lie beyond the entry point's declared admissible range;
    the coverage check still requires dispatch to resolve them (to the host
    fallback), but range/memory/signature claims are not evaluated there.
    """

    name: str
    points: tuple[Any, ...]
    past: tuple[Any, ...] = ()

    def __post_init__(self) -> None:
        if not self.points:
            raise ValueError(f"axis {self.name!r} has no lattice points")


def span(
    name: str,
    lo: int,
    hi: int,
    *,
    boundaries: tuple[int, ...] = (),
    past: tuple[int, ...] = (),
) -> Axis:
    """Boundary-focused integer axis: endpoints plus ``b - 1, b, b + 1``
    around every declared boundary, clipped to ``[lo, hi]``."""
    pts = {lo, hi}
    for b in boundaries:
        pts.update(v for v in (b - 1, b, b + 1) if lo <= v <= hi)
    return Axis(name, tuple(sorted(pts)), tuple(sorted(past)))


def choice(name: str, *values: Any) -> Axis:
    """Categorical axis (requested backend, chain length classes, ...)."""
    return Axis(name, values)


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Declared geometry contract for one device entry point."""

    name: str
    entry: str  # dotted qualname of the decorated callable (for the report)
    module: str  # defining module; the driver selects contracts by module
    axes: tuple[Axis, ...]
    backends: tuple[str, ...]  # every backend dispatch may legally return
    device_backends: tuple[str, ...]  # subset whose points carry device claims
    dispatch: Callable[[dict[str, Any]], str]
    vmem: Callable[[dict[str, Any]], Mapping[str, tuple[tuple[int, ...], int]]] | None = None
    ranges: Callable[[dict[str, Any]], list[RangeClaim]] | None = None
    signature: Callable[[dict[str, Any]], tuple] | None = None
    max_signatures: int | None = None
    abstract: Callable[[dict[str, Any]], tuple[Callable, tuple]] | None = None
    eval_points: int = 4  # admissible device points handed to jax.eval_shape
    notes: str = ""


CONTRACTS: dict[str, KernelContract] = {}


def register(c: KernelContract) -> None:
    existing = CONTRACTS.get(c.name)
    if existing is not None and existing.entry != c.entry:
        raise ValueError(
            f"kernelcheck contract {c.name!r} already registered for "
            f"{existing.entry} (attempted re-registration from {c.entry})"
        )
    CONTRACTS[c.name] = c


def contract(
    name: str,
    *,
    axes: tuple[Axis, ...],
    backends: tuple[str, ...],
    dispatch: Callable[[dict[str, Any]], str],
    device_backends: tuple[str, ...] | None = None,
    vmem: Callable[[dict[str, Any]], Mapping[str, tuple[tuple[int, ...], int]]] | None = None,
    ranges: Callable[[dict[str, Any]], list[RangeClaim]] | None = None,
    signature: Callable[[dict[str, Any]], tuple] | None = None,
    max_signatures: int | None = None,
    abstract: Callable[[dict[str, Any]], tuple[Callable, tuple]] | None = None,
    eval_points: int = 4,
    notes: str = "",
) -> Callable:
    """Decorator: register a :class:`KernelContract` for the wrapped entry
    point and return the entry point unchanged (zero runtime overhead)."""

    def deco(fn: Callable) -> Callable:
        register(
            KernelContract(
                name=name,
                entry=f"{fn.__module__}.{fn.__qualname__}",
                module=fn.__module__,
                axes=axes,
                backends=backends,
                device_backends=(
                    backends if device_backends is None else device_backends
                ),
                dispatch=dispatch,
                vmem=vmem,
                ranges=ranges,
                signature=signature,
                max_signatures=max_signatures,
                abstract=abstract,
                eval_points=eval_points,
                notes=notes,
            )
        )
        return fn

    return deco


def lattice(c: KernelContract) -> Iterator[tuple[dict[str, Any], bool]]:
    """Yield ``(geometry, admissible)`` over the full product lattice.

    A geometry is admissible when every component is an in-range point;
    any ``past`` component makes the point a coverage-only probe.
    """
    axes = c.axes

    def rec(i: int, geom: dict[str, Any], admissible: bool) -> Iterator[tuple[dict[str, Any], bool]]:
        if i == len(axes):
            yield dict(geom), admissible
            return
        ax = axes[i]
        for v in ax.points:
            geom[ax.name] = v
            yield from rec(i + 1, geom, admissible)
        for v in ax.past:
            geom[ax.name] = v
            yield from rec(i + 1, geom, False)
        geom.pop(ax.name, None)

    yield from rec(0, {}, True)
