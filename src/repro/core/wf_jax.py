"""Vectorized water-filling in JAX — the TPU-native form of the paper's WF.

The heap/walk formulation of Alg. 2 is sequential and host-bound.  On TPU we
recast the water level as a sort + prefix-sum (DESIGN.md §3): with busy
levels sorted ascending, capacity is piecewise-linear in the level, so the
minimal integer level is a masked ceiling division — O(M log M), fully
vectorized, jit-able, and usable *inside* a training/serving step.

Used by :mod:`repro.serve.moe_balance` to pick which replica of each expert
serves which token group (experts-as-data-chunks; see DESIGN.md §2), and
exposed as a general on-device balanced-assignment primitive.

All functions are shape-polymorphic in the number of servers ``M`` and use
int32 throughout (token counts comfortably fit).

At large ``M`` the sort + prefix-sum + segment-search pipeline can run as
one fused Pallas kernel (:mod:`repro.kernels.waterlevel`): every
water-level entry point takes ``use_pallas`` (``None`` = auto — the
kernel on TPU, this jnp pipeline on CPU/interpret), and the two backends
are bit-identical by construction, which the parity suite asserts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import choice, contract, span
from repro.obs.session import span as _obs_span

from .dispatch import phased_call
from .instance import Assignment, AssignmentProblem

__all__ = [
    "water_level",
    "water_fill_alloc",
    "water_fill_groups",
    "water_fill_batch",
    "water_fill_chain",
    "water_filling_jax",
    "water_filling_jax_batch",
    "water_filling_jax_chain",
    "check_group_capacity",
]

_BIG = jnp.int32(2**30)


def _ceil_div(a: jax.Array, b: jax.Array) -> jax.Array:
    return -(-a // b)


def _resolve_pallas(use_pallas: bool | None, m: int) -> tuple[bool, bool]:
    """Static backend choice for an M-server water level: ``(use the
    kernel, downgraded)``.

    ``None`` → auto (Pallas on TPU, jnp elsewhere); ``downgraded`` marks
    a kernel request past its width bound, counted by the device
    profiler as a ``pallas_downgrade``.  See
    :func:`repro.kernels.waterlevel.pallas_dispatch`.  Imported lazily
    (and :mod:`repro.kernels` exports lazily) so the first call pays only
    the waterlevel-module import, not the whole kernels package.
    """
    from repro.kernels.waterlevel import pallas_dispatch

    return pallas_dispatch(use_pallas, m)


# ---------------------------------------------------------------------------
# kernelcheck geometry contract (verified by repro.analysis.kernelcheck).
#
# Mirrors of repro.kernels.waterlevel.{PALLAS_MAX_M, WL_M_MAX} — literal
# here so declaring the contract at import time does not force the
# kernels import this module defers on purpose; kept in sync by
# tests/test_kernelcheck.py.
_PALLAS_MAX_M = 1 << 15
_WL_M_MAX = 1 << 16


def _wf_dispatch(geom: dict) -> str:
    from repro import backend as backend_config

    with backend_config.set_backend(waterlevel=geom["requested"]):
        return "pallas" if _resolve_pallas(None, geom["m"])[0] else "jnp"


def _wf_vmem(geom: dict):
    from repro.kernels.waterlevel import wl_vmem_blocks

    return wl_vmem_blocks(geom)


def _wf_ranges(geom: dict) -> list:
    """The kernel's claims (the jnp path shares its int32 arithmetic)
    plus the adapter-level carry claims: evolved levels stay within the
    busy envelope (eq. 10 max / eq. 2 commit) and the burst preserves the
    kernel's Σ busy·μ precondition."""
    from repro.analysis.contracts import Interval, RangeClaim
    from repro.kernels.waterlevel import (
        WL_BUSY0_MAX,
        WL_LEVEL_MAX,
        WL_MU_MAX,
        WL_SUM_BMU_MAX,
        WL_TOTAL_DEMAND_MAX,
        wl_range_claims,
    )

    m = geom["m"]
    claims = wl_range_claims(m)
    claims.append(
        RangeClaim(
            "eq. 10 / eq. 2 busy carry (levels fed back as busy)",
            Interval(0, WL_BUSY0_MAX + WL_TOTAL_DEMAND_MAX),
            bound=WL_LEVEL_MAX,
        )
    )
    claims.append(
        RangeClaim(
            "Σ busy·μ preserved across the burst (kernel precondition)",
            Interval(
                0,
                WL_BUSY0_MAX * WL_MU_MAX * m
                + WL_TOTAL_DEMAND_MAX
                + m * WL_MU_MAX,
            ),
            bound=WL_SUM_BMU_MAX,
        )
    )
    return claims


def _wf_sig(geom: dict, kind: str) -> tuple:
    up = _wf_dispatch(geom) == "pallas"
    sig = (kind, geom["m"], _pad_k(geom["k"]), up)
    if kind == "wf-chain":
        sig += (_pad_k(geom["b"]),)
    elif kind == "wf-batch":
        sig += (geom["b"],)  # raw burst size — see the contract notes
    return sig


def _wf_abstract(geom: dict, kind: str):
    m, k_pad = geom["m"], _pad_k(geom["k"])
    up = _wf_dispatch(geom) == "pallas"
    i32, b8 = jnp.int32, jnp.bool_
    sd = jax.ShapeDtypeStruct
    if kind == "wf-groups":
        fn = functools.partial(_wf_groups_jit, use_pallas=up)
        return fn, (
            sd((m,), i32),
            sd((m,), i32),
            sd((k_pad, m), b8),
            sd((k_pad,), i32),
        )
    if kind == "wf-batch":
        b = geom["b"]
        fn = functools.partial(_wf_batch_jit, use_pallas=up)
        return fn, (
            sd((b, m), i32),
            sd((b, m), i32),
            sd((b, k_pad, m), b8),
            sd((b, k_pad), i32),
        )
    b_pad = _pad_k(geom["b"])
    fn = functools.partial(_wf_chain_jit, use_pallas=up)
    return fn, (
        sd((m,), i32),
        sd((b_pad, m), i32),
        sd((b_pad, k_pad, m), b8),
        sd((b_pad, k_pad), i32),
    )


def water_level(
    busy: jax.Array,
    mu: jax.Array,
    mask: jax.Array,
    demand: jax.Array,
    *,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Minimal integer ξ with ``Σ_m mask_m·max{ξ-busy_m,0}·μ_m ≥ demand``.

    Args:
      busy: (M,) int32 current levels.
      mu: (M,) int32 per-server widths (throughputs); must be >0 where mask.
      mask: (M,) bool availability (the group's ``S_c^k``).
      demand: scalar int32 number of tasks; if 0, returns min available busy.
      use_pallas: backend override — ``None`` auto-selects (Pallas kernel
        on TPU, this jnp path otherwise); both produce bit-identical
        levels.
    """
    if _resolve_pallas(use_pallas, busy.shape[-1])[0]:
        from repro.kernels.waterlevel import water_level_pallas

        return water_level_pallas(busy, mu, mask, demand)
    busy = busy.astype(jnp.int32)
    mu = mu.astype(jnp.int32)
    b = jnp.where(mask, busy, _BIG)
    w = jnp.where(mask, mu, 0)
    order = jnp.argsort(b)
    bs, ws = b[order], w[order]
    cw = jnp.cumsum(ws)
    cbw = jnp.cumsum(bs * ws)
    xi = _ceil_div(demand + cbw, jnp.maximum(cw, 1))
    next_b = jnp.concatenate([bs[1:], jnp.full((1,), _BIG, jnp.int32)])
    valid = (xi <= next_b) & (cw > 0)
    idx = jnp.argmax(valid)  # first valid segment
    level = jnp.maximum(xi[idx], bs[idx] + 1)
    # demand == 0 → stay at the lowest available level
    return jnp.where(demand > 0, level, jnp.where(mask, busy, _BIG).min())


def water_fill_alloc(
    busy: jax.Array,
    mu: jax.Array,
    mask: jax.Array,
    demand: jax.Array,
    *,
    use_pallas: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Water-level allocation: (alloc (M,) int32, ξ scalar int32).

    Mirrors Alg. 2 lines 7-13: participating servers take their full
    ``(ξ-b_m)·μ_m`` capacity in ascending-busy order and the boundary server
    absorbs the remainder, expressed as a prefix-sum clamp.  With
    ``use_pallas`` (auto on TPU) the sort + prefix sums + segment search
    run as one fused kernel; allocations are bit-identical either way.
    """
    if _resolve_pallas(use_pallas, busy.shape[-1])[0]:
        from repro.kernels.waterlevel import water_fill_alloc_pallas

        return water_fill_alloc_pallas(busy, mu, mask, demand)
    xi = water_level(busy, mu, mask, demand, use_pallas=False)
    b = jnp.where(mask, busy.astype(jnp.int32), _BIG)
    w = jnp.where(mask, mu.astype(jnp.int32), 0)
    order = jnp.argsort(b)
    caps = jnp.maximum(xi - b[order], 0) * w[order]
    prev = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(caps)[:-1]])
    take = jnp.clip(demand - prev, 0, caps)
    alloc = jnp.zeros_like(take).at[order].set(take)
    return alloc, xi


def water_fill_groups(
    busy: jax.Array,
    mu: jax.Array,
    group_mask: jax.Array,
    demands: jax.Array,
    *,
    use_pallas: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sequential WF over K task groups (lax.scan), carrying busy levels.

    Args:
      busy: (M,) int32 initial busy levels ``b_m^c(0)``.
      mu: (M,) int32 per-server throughputs.
      group_mask: (K, M) bool — availability matrix (``m ∈ S_c^k``).
      demands: (K,) int32 — ``|T_c^k|`` (0 demand → no-op group).
      use_pallas: water-level backend override (resolved once, outside
        the scan); ``None`` auto-selects per
        :func:`repro.kernels.waterlevel.resolve_use_pallas`.

    Returns:
      alloc: (K, M) int32 tasks per (group, server).
      levels: (K,) int32 water levels ``ξ_k``.
      phi: scalar int32 — ``max_k ξ_k`` over non-empty groups (WF's Φ_c).
    """
    up, _ = _resolve_pallas(use_pallas, busy.shape[-1])

    def step(b, inputs):
        m_k, d_k = inputs
        alloc_k, xi = water_fill_alloc(b, mu, m_k, d_k, use_pallas=up)
        b_next = jnp.where(m_k & (d_k > 0), jnp.maximum(b, xi), b)  # eq. 10
        return b_next, (alloc_k, xi)

    _, (alloc, levels) = jax.lax.scan(
        step, busy.astype(jnp.int32), (group_mask, demands.astype(jnp.int32))
    )
    phi = jnp.max(jnp.where(demands > 0, levels, 0))
    return alloc, levels, phi


def _water_fill_groups_jnp(busy, mu, group_mask, demands):
    return water_fill_groups(busy, mu, group_mask, demands, use_pallas=False)


# the jnp backend for B independent instances: plain vmap of the groups scan
_water_fill_batch_vmap = jax.vmap(_water_fill_groups_jnp, in_axes=(0, 0, 0, 0))


def _water_fill_groups_batch_pallas(busy, mu, group_mask, demands):
    """Pallas backend for B independent instances: one scan over the K
    groups whose per-step allocation is a single batched-grid kernel call
    (``water_fill_alloc_pallas_batch``) over all B rows.

    Row ``i`` evolves exactly like ``water_fill_groups(busy[i], …,
    use_pallas=True)`` — same eq. 10 busy carry, same Φ reduction — and
    the batched kernel is row-wise bit-identical to the single-problem
    kernel, so the whole thing is bit-identical to the vmapped jnp path.
    """
    from repro.kernels.waterlevel import water_fill_alloc_pallas_batch

    mu = mu.astype(jnp.int32)

    def step(b, inputs):
        m_k, d_k = inputs  # (B, M) mask, (B,) demand for group k
        alloc_k, xi = water_fill_alloc_pallas_batch(b, mu, m_k, d_k)
        b_next = jnp.where(
            m_k & (d_k > 0)[:, None], jnp.maximum(b, xi[:, None]), b
        )  # eq. 10
        return b_next, (alloc_k, xi)

    _, (alloc, levels) = jax.lax.scan(
        step,
        busy.astype(jnp.int32),
        (
            jnp.moveaxis(group_mask, 1, 0),
            jnp.moveaxis(demands.astype(jnp.int32), 1, 0),
        ),
    )
    alloc = jnp.moveaxis(alloc, 0, 1)  # (K, B, M) -> (B, K, M)
    levels = jnp.moveaxis(levels, 0, 1)  # (K, B) -> (B, K)
    phi = jnp.max(jnp.where(demands > 0, levels, 0), axis=1)
    return alloc, levels, phi


def water_fill_batch(
    busy: jax.Array,
    mu: jax.Array,
    group_mask: jax.Array,
    demands: jax.Array,
    *,
    use_pallas: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """WF over B *independent* arrival instances (per-problem busy
    snapshots): (B,M) busy/mu, (B,K,M) masks, (B,K) demands →
    ((B,K,M) alloc, (B,K) levels, (B,) Φ).

    NOTE: results are only mutually consistent if the problems target
    disjoint queues — same-slot admission must use
    :func:`water_fill_chain`, which commits eq. 2 between jobs.

    ``use_pallas`` picks the backend (``None`` = auto): the jnp path is
    a vmapped groups scan; the Pallas path runs each group step as one
    batched-grid kernel call over all B rows — bit-identical results.
    """
    if _resolve_pallas(use_pallas, busy.shape[-1])[0]:
        return _water_fill_groups_batch_pallas(busy, mu, group_mask, demands)
    return _water_fill_batch_vmap(busy, mu, group_mask, demands)


def water_fill_chain(
    busy: jax.Array,
    mu: jax.Array,
    group_mask: jax.Array,
    demands: jax.Array,
    *,
    use_pallas: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sequential admission of B jobs in one scan, carrying busy levels.

    Unlike :func:`water_fill_batch` (independent problems, shared stale
    busy snapshot), the chain commits eq. 2 *between* jobs: job ``i+1``
    sees ``b_m + ⌈load_m^i/μ_m^i⌉`` exactly as if the jobs were admitted
    one at a time — so a same-slot burst collapses to one device dispatch
    with bit-identical results to per-arrival admission.

    Args:
      busy: (M,) int32 busy levels before the first job of the burst.
      mu: (B, M) int32 per-job per-server throughputs.
      group_mask: (B, K, M) bool availability; padded jobs are all-False.
      demands: (B, K) int32 task counts; padded jobs/groups are 0.

    Returns:
      alloc: (B, K, M) int32, levels-free per-job allocations.
      phi: (B,) int32 per-job ``Φ_c`` (max water level over its groups).
      busy_out: (M,) int32 busy levels after the whole burst.
    """
    up, _ = _resolve_pallas(use_pallas, busy.shape[-1])

    def job_step(b, inputs):
        mu_j, mask_j, d_j = inputs
        alloc_j, _, phi_j = water_fill_groups(b, mu_j, mask_j, d_j, use_pallas=up)
        loads = alloc_j.sum(axis=0)
        b_next = b + jnp.where(loads > 0, _ceil_div(loads, mu_j), 0)  # eq. 2
        return b_next, (alloc_j, phi_j)

    busy_out, (alloc, phi) = jax.lax.scan(
        job_step,
        busy.astype(jnp.int32),
        (mu.astype(jnp.int32), group_mask, demands.astype(jnp.int32)),
    )
    return alloc, phi, busy_out


_wf_groups_jit = jax.jit(water_fill_groups, static_argnames="use_pallas")
_wf_batch_jit = jax.jit(water_fill_batch, static_argnames="use_pallas")
_wf_chain_jit = jax.jit(water_fill_chain, static_argnames="use_pallas")


def _pad_k(k: int) -> int:
    """Pad group count to a power of two so jit recompiles O(log K) times
    per cluster size instead of once per distinct K."""
    p = 1
    while p < k:
        p *= 2
    return p


def check_group_capacity(
    mu: np.ndarray, masks: np.ndarray, demands: np.ndarray
) -> None:
    """Host-path guard: a group with positive demand must have a non-empty
    mask and positive total capacity, otherwise the device water level
    would silently return a ``_BIG``-derived garbage value.

    ``mu`` is (M,) or (B, M); ``masks`` (K, M) or (B, K, M); ``demands``
    (K,) or (B, K) — raises :class:`ValueError` on the first violation.
    """
    mu = np.atleast_2d(np.asarray(mu))
    masks = np.asarray(masks)
    demands = np.atleast_2d(np.asarray(demands))
    masks = masks.reshape((demands.shape[0], demands.shape[1], -1))
    cap = (masks * mu[:, None, :]).sum(axis=-1)
    bad = (demands > 0) & (cap <= 0)
    if bad.any():
        i, k = map(int, np.argwhere(bad)[0])
        reason = (
            "an all-False availability mask"
            if not masks[i, k].any()
            else "zero total capacity on its available servers"
        )
        raise ValueError(
            f"infeasible water-fill group (problem {i}, group {k}): "
            f"demand {int(demands[i, k])} with {reason}"
        )


def _dense_inputs(
    problems: list[AssignmentProblem], k_pad: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(B,M) busy/mu, (B,K,M) masks, (B,K) demands; padded groups have
    demand 0 + empty mask, which the kernel treats as no-ops."""
    b = len(problems)
    m = problems[0].n_servers
    busy = np.stack([p.busy for p in problems]).astype(np.int32)
    mu = np.stack([p.mu for p in problems]).astype(np.int32)
    masks = np.zeros((b, k_pad, m), dtype=bool)
    demands = np.zeros((b, k_pad), dtype=np.int32)
    for i, prob in enumerate(problems):
        for k, g in enumerate(prob.groups):
            masks[i, k, list(g.servers)] = True
            demands[i, k] = g.size
    check_group_capacity(mu, masks, demands)
    return busy, mu, masks, demands


def _to_assignment(
    problem: AssignmentProblem, alloc: np.ndarray, phi: int
) -> Assignment:
    per_group: list[dict[int, int]] = []
    for k in range(len(problem.groups)):
        row = alloc[k]
        nz = np.flatnonzero(row)
        per_group.append({int(mm): int(row[mm]) for mm in nz})
    result = Assignment(alloc=per_group, phi=int(phi))
    result.validate(problem)
    return result


@contract(
    "wf_jax.groups",
    axes=(
        span("m", 1, _WL_M_MAX, boundaries=(128, _PALLAS_MAX_M)),
        choice("k", 1, 3, 16, 128),
        choice("requested", "jnp", "pallas"),
    ),
    backends=("jnp", "pallas"),
    dispatch=_wf_dispatch,
    vmem=_wf_vmem,
    ranges=_wf_ranges,
    signature=lambda geom: _wf_sig(geom, "wf-groups"),
    max_signatures=64,  # m points × pow2 K classes × backend
    abstract=lambda geom: _wf_abstract(geom, "wf-groups"),
    eval_points=4,
    notes="K-group scan adapter; widths past PALLAS_MAX_M are admissible "
    "and route to the jnp pipeline (no past probes needed)",
)
def water_filling_jax(
    problem: AssignmentProblem, *, use_pallas: bool | None = None
) -> Assignment:
    """Host-facing WF that runs the water level on device.

    Same allocation and ``Φ_c`` as :func:`repro.core.wf.water_filling`
    (both implement Alg. 2 exactly); registered as ``"wf_jax"`` so the
    scheduling engine can exercise the TPU-native path end-to-end.
    ``use_pallas`` picks the water-level backend (``None`` = auto); the
    realized schedule is bit-identical either way.
    """
    if not problem.groups:
        return Assignment(alloc=[], phi=0)  # parity with host water_filling
    k_pad = _pad_k(len(problem.groups))
    # resolve before the jit boundary so the cache keys on the
    # concrete backend (set_backend scopes stay effective per call)
    up, downgrade = _resolve_pallas(use_pallas, problem.n_servers)
    alloc, phi = phased_call(
        "wf",
        "wf-groups",
        (problem.n_servers, k_pad, up),  # the kernelcheck key
        lambda *a: _wf_groups_jit(*a, use_pallas=up)[::2],
        lambda: [x[0] for x in _dense_inputs([problem], k_pad)],
        downgrade=downgrade,
    )
    with _obs_span("wf.decode"):
        return _to_assignment(problem, alloc, int(phi))


@contract(
    "wf_jax.batch",
    axes=(
        choice("m", 1, 128, 4096, _PALLAS_MAX_M, _WL_M_MAX),
        choice("k", 1, 16),
        choice("b", 1, 2, 7, 32),
        choice("requested", "jnp", "pallas"),
    ),
    backends=("jnp", "pallas"),
    dispatch=_wf_dispatch,
    vmem=_wf_vmem,
    ranges=_wf_ranges,
    signature=lambda geom: _wf_sig(geom, "wf-batch"),
    max_signatures=80,
    abstract=lambda geom: _wf_abstract(geom, "wf-batch"),
    eval_points=3,
    notes="independent-problems batch; the burst size B enters the jit "
    "cache unpadded (unlike the chain adapter) — callers with unbounded "
    "burst-size diversity should chunk to fixed sizes",
)
def water_filling_jax_batch(
    problems: list[AssignmentProblem], *, use_pallas: bool | None = None
) -> list[Assignment]:
    """Batched WF over *independent* problems: one batched device call.

    All problems must share the same server count (one cluster); busy
    times are per-problem and are NOT carried across jobs, so the results
    are only mutually consistent if the problems target disjoint queues.
    For same-slot arrival bursts — where each job must see the busy times
    left by its predecessors — use :func:`water_filling_jax_chain`.

    ``use_pallas`` picks the water-level backend (``None`` = auto: the
    batched-grid Pallas kernel on TPU, the vmapped jnp pipeline
    elsewhere; ``set_backend(waterlevel=...)`` scopes override) —
    assignments are bit-identical either way.
    """
    if not problems:
        return []
    m = problems[0].n_servers
    if any(p.n_servers != m for p in problems):
        raise ValueError("batched WF requires a single cluster size")
    k_pad = _pad_k(max(len(p.groups) for p in problems))
    # resolve before the jit boundary so the cache keys on the
    # concrete backend (set_backend scopes stay effective per call)
    up, downgrade = _resolve_pallas(use_pallas, m)
    alloc, phi = phased_call(
        "wf",
        "wf-batch",
        (m, k_pad, up, len(problems)),  # the kernelcheck key
        lambda *a: _wf_batch_jit(*a, use_pallas=up)[::2],
        lambda: _dense_inputs(problems, k_pad),
        downgrade=downgrade,
    )
    with _obs_span("wf.decode"):
        return [
            _to_assignment(p, alloc[i], int(phi[i]))
            for i, p in enumerate(problems)
        ]


@contract(
    "wf_jax.chain",
    axes=(
        choice("m", 1, 128, _PALLAS_MAX_M, _WL_M_MAX),
        choice("k", 1, 16),
        choice("b", 1, 2, 7, 32, 64),
        choice("requested", "jnp", "pallas"),
    ),
    backends=("jnp", "pallas"),
    dispatch=_wf_dispatch,
    vmem=_wf_vmem,
    ranges=_wf_ranges,
    signature=lambda geom: _wf_sig(geom, "wf-chain"),
    max_signatures=96,  # m × pow2 K classes × pow2 B classes × backend
    abstract=lambda geom: _wf_abstract(geom, "wf-chain"),
    eval_points=3,
    notes="same-slot burst chain (eq. 2 committed between jobs in the "
    "scan); both K and B are pow2-padded before the jit boundary",
)
def water_filling_jax_chain(
    problems: list[AssignmentProblem], *, use_pallas: bool | None = None
) -> list[Assignment]:
    """Admit many same-slot arrivals in one chained device dispatch.

    Every problem must share one cluster (same server count) and carry the
    *same* pre-burst busy vector; the scan commits eq. 2 between jobs, so
    the returned assignments (and their ``Φ_c``) are bit-identical to
    calling :func:`water_filling_jax` per job with busy times re-read from
    the cluster after each enqueue — the engine's sequential admit path.
    ``use_pallas`` picks the water-level backend inside the scan (``None``
    = auto: the fused Pallas kernel on TPU, the jnp pipeline elsewhere).
    """
    if not problems:
        return []
    m = problems[0].n_servers
    if any(p.n_servers != m for p in problems):
        raise ValueError("chained WF requires a single cluster size")
    if any(not p.groups for p in problems):
        raise ValueError("chained WF requires non-empty problems")
    base = problems[0].busy
    if any(
        p.busy is not base and not np.array_equal(p.busy, base)
        for p in problems[1:]
    ):
        # the scan re-commits eq. 2 between jobs itself; a caller passing
        # per-job evolved busy vectors would get them double-counted
        raise ValueError(
            "chained WF requires every problem to carry the same pre-burst "
            "busy vector (eq. 2 is committed inside the scan)"
        )
    k_pad = _pad_k(max(len(p.groups) for p in problems))
    b_pad = _pad_k(len(problems))  # pad jobs too: O(log B) recompiles

    def build():
        busy, mu, masks, demands = _dense_inputs(problems, k_pad)
        if b_pad > len(problems):
            pad = b_pad - len(problems)
            mu = np.concatenate([mu, np.ones((pad, m), np.int32)])
            masks = np.concatenate([masks, np.zeros((pad, k_pad, m), bool)])
            demands = np.concatenate(
                [demands, np.zeros((pad, k_pad), np.int32)]
            )
        return busy[0], mu, masks, demands

    up, downgrade = _resolve_pallas(use_pallas, m)
    alloc, phi = phased_call(
        "wf",
        "wf-chain",
        (m, k_pad, up, b_pad),  # the kernelcheck key
        lambda *a: _wf_chain_jit(*a, use_pallas=up)[:2],
        build,
        downgrade=downgrade,
    )
    with _obs_span("wf.decode"):
        return [
            _to_assignment(p, alloc[i], int(phi[i]))
            for i, p in enumerate(problems)
        ]
