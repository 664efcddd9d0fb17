"""One config object for every numeric-backend choice.

Backend selection used to be spread over ad-hoc surfaces (environment
variables plus per-call ``use_pallas`` flags).  This module is now the
single resolution point:

- :func:`resolve(kind)` returns the configured backend for ``kind``
  (``"waterlevel"`` → ``auto|pallas|jnp``, ``"rd"`` →
  ``auto|host|jnp|pallas``);
- :func:`set_backend` is a context manager that scopes an explicit
  choice (``with set_backend(rd="jnp"): ...``) — it nests and restores
  on exit.  It is the only process-wide override; the legacy
  ``REPRO_{KIND}_BACKEND`` env vars are gone.

``auto`` is returned verbatim — platform-dependent auto-dispatch (TPU →
device, CPU → host/jnp) stays with the consumer
(:func:`repro.kernels.waterlevel.resolve_use_pallas`,
:func:`repro.core.rd.resolve_rd_backend`) because *this* module must
never import jax: a run scoped to ``set_backend(rd="host")`` resolves
its backend inside the first arrival's timed scheduling step, and a
multi-second jax import does not belong there.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator

__all__ = ["BACKEND_KINDS", "BackendConfig", "current", "resolve", "set_backend"]

# kind -> valid choices
BACKEND_KINDS: dict[str, tuple[str, ...]] = {
    "waterlevel": ("auto", "pallas", "jnp"),
    "rd": ("auto", "host", "jnp", "pallas"),
}


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    """Explicit backend choices; ``None`` means "not set here" (fall
    through to ``auto``)."""

    waterlevel: str | None = None
    rd: str | None = None

    def __post_init__(self) -> None:
        for kind in BACKEND_KINDS:
            choice = getattr(self, kind)
            if choice is not None:
                _check(kind, choice, source="set_backend")


def _check(kind: str, choice: str, *, source: str) -> str:
    try:
        valid = BACKEND_KINDS[kind]
    except KeyError:
        raise KeyError(
            f"unknown backend kind {kind!r}; known: {sorted(BACKEND_KINDS)}"
        ) from None
    if choice not in valid:
        raise ValueError(
            f"{source}: {kind} backend {choice!r}: expected one of {valid}"
        )
    return choice


_stack: list[BackendConfig] = [BackendConfig()]


def current() -> BackendConfig:
    """The innermost active config (the process default when no
    :func:`set_backend` scope is open)."""
    return _stack[-1]


def resolve(kind: str, explicit: str | None = None) -> str:
    """The backend for ``kind``: explicit argument > :func:`set_backend`
    scope > ``"auto"``.

    ``auto`` is returned as-is; mapping it to a concrete backend is the
    consumer's job (it may need the jax platform, which this module
    deliberately never touches).
    """
    if explicit is not None:
        return _check(kind, explicit, source="explicit backend")
    configured = getattr(current(), _check_kind(kind))
    if configured is not None:
        return configured
    return "auto"


def _check_kind(kind: str) -> str:
    if kind not in BACKEND_KINDS:
        raise KeyError(
            f"unknown backend kind {kind!r}; known: {sorted(BACKEND_KINDS)}"
        )
    return kind


@contextlib.contextmanager
def set_backend(**choices: str) -> Iterator[BackendConfig]:
    """Scope explicit backend choices, e.g.::

        with set_backend(waterlevel="jnp", rd="host"):
            engine.run(jobs)

    Nested scopes override only the kinds they name; everything else
    falls through to the enclosing scope.  Choices are validated at
    entry (unknown kinds and invalid names raise immediately).
    """
    for kind in choices:
        _check_kind(kind)
    cfg = dataclasses.replace(current(), **choices)
    _stack.append(cfg)
    try:
        yield cfg
    finally:
        _stack.pop()
