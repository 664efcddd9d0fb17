"""Pallas water-level kernel: fused sort + prefix-sum + segment search.

The integer water level (paper eqs. 7/9) is the inner loop of every
policy in the scheduling engine — WF, the OCWF/OCWF-ACC reordering scan,
and the chained same-slot burst admission (``water_fill_chain``) all
reduce to *sort busy levels, prefix-sum capacities, masked ceiling
division*.  The jnp path in :mod:`repro.core.wf_jax` materializes each
stage as a separate XLA op (sort, two cumsums, the division, the argmax,
the scatter); at large ``M`` that is several HBM round-trips per group.
This kernel fuses the whole pipeline into one VMEM-resident program:

- **sort**: a bitonic compare-exchange network over ``M`` padded to a
  power of two (lane-width 128 minimum), keyed lexicographically by
  ``(busy, original index)`` — exactly the order of jnp's stable
  ``argsort``, so tie-breaks (and therefore allocations) are
  bit-identical to the jnp path;
- **prefix sums**: Hillis–Steele log-step scans of ``μ`` and ``b·μ``;
- **segment search**: the masked ceiling division
  ``ξ_i = ⌈(T + Σb·μ)/Σμ⌉`` with the first-valid-segment selection and
  the ``ξ ≥ b+1`` clamp, all in-register;
- **allocation**: the prefix-sum clamp of Alg. 2 lines 7-13 (``take =
  clip(T − prev, 0, caps)``), emitted in sorted order together with the
  permutation so the caller scatters once.

Everything is int32 with the same arithmetic (including the same
overflow behavior) as the jnp path, so results are bit-identical — the
parity suite (``tests/test_waterlevel_parity.py``) asserts exact
equality of allocations and Φ across host, jnp, and Pallas.

Dispatch policy (:func:`resolve_use_pallas`): Pallas engages on TPU by
default and auto-falls back to the jnp path on CPU, where ``pallas_call``
would only run in (slow) interpret mode.  Tests and the benchmark sweep
force the kernel on CPU with ``use_pallas=True``, which runs it under
``interpret=True``; ``repro.backend.set_backend(waterlevel=...)`` scopes
override the default.  The single-block design keeps the padded arrays
(busy, μ, index, plus scan temporaries) in VMEM, which bounds the
supported width at ``PALLAS_MAX_M``; beyond that the dispatcher falls
back to jnp regardless of the override.

The geometry contract (VMEM blocks, int32 overflow envelope, dispatch
coverage, jit-cache surface) is declared on the entry points via
:func:`repro.analysis.contracts.contract` and verified without a device
by ``python -m repro.analysis.kernelcheck``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.contracts import (
    VMEM_LIMIT_BYTES,
    Interval,
    RangeClaim,
    choice,
    contract,
    span,
)

__all__ = [
    "PALLAS_MAX_M",
    "WL_BUSY0_MAX",
    "WL_DEMAND_MAX",
    "WL_LEVEL_MAX",
    "WL_M_MAX",
    "WL_MU_MAX",
    "WL_SUM_BMU_MAX",
    "WL_TOTAL_DEMAND_MAX",
    "pallas_dispatch",
    "resolve_use_pallas",
    "water_level_pallas",
    "water_fill_alloc_pallas",
    "water_fill_alloc_pallas_batch",
]

# must match repro.core.wf_jax._BIG: masked servers sort to this sentinel
_BIG = 2**30

_LANES = 128  # TPU lane width: minimum padded M

# Widest single-block kernel: its (1, M) int32 rows and their stage and
# scan temporaries must stay resident in VMEM (wl_vmem_blocks).
PALLAS_MAX_M = 1 << 15


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def resolve_use_pallas(explicit: bool | None, m: int) -> bool:
    """Decide the water-level backend for a width-``m`` problem.

    ``explicit`` wins when given; otherwise the choice comes from
    :func:`repro.backend.resolve` (``set_backend(waterlevel=...)``
    scopes), with ``auto`` choosing Pallas only on TPU.  Widths beyond
    :data:`PALLAS_MAX_M` always fall back to jnp (the single-block
    kernel would not fit VMEM).
    """
    return pallas_dispatch(explicit, m)[0]


def pallas_dispatch(explicit: bool | None, m: int) -> tuple[bool, bool]:
    """``(use the kernel, downgraded)`` for a width-``m`` problem, resolved
    once: ``downgraded`` is True when the request resolves to the kernel
    but ``m`` is past :data:`PALLAS_MAX_M`, so the jnp pipeline runs
    instead — the event the adapters count as
    ``device.<kind>.pallas_downgrade``."""
    from repro import backend as backend_config

    if explicit is not None:
        requested = bool(explicit)
    else:
        configured = backend_config.resolve("waterlevel")
        if configured == "auto":
            requested = jax.default_backend() == "tpu"
        else:
            requested = configured == "pallas"
    use = requested and m <= PALLAS_MAX_M
    return use, requested and not use


# ---------------------------------------------------------------------------
# kernelcheck geometry contract (verified by repro.analysis.kernelcheck).
#
# Admissible input envelope the int32 range proofs assume.  The engine's
# busy times, μ and demands are small integers (paper Sec. V uses μ ≤ 4
# and per-job task counts ≲ 10^4); these bounds leave orders of magnitude
# of headroom while keeping every claim provable:
#
# - WL_SUM_BMU_MAX bounds Σ busy·μ at kernel entry.  The water-fill
#   adapters *preserve* it: one burst raises Σ busy·μ by at most the
#   allocated demand plus one level step (Σ μ), so
#   Σ busy0·μ + total demand + Σ μ ≤ 2^30 + 2·2^20 < WL_SUM_BMU_MAX
#   even at the widest certified cluster (WL_M_MAX lanes).
# - WL_LEVEL_MAX bounds any evolved busy entry: the minimal water level
#   never exceeds the smallest available busy time plus the demand, so
#   levels fed back as busy stay ≤ WL_BUSY0_MAX + WL_TOTAL_DEMAND_MAX.

WL_BUSY0_MAX = 1 << 10  # initial (pre-burst) per-server busy time
WL_MU_MAX = 1 << 4  # per-server tasks/slot (μ)
WL_DEMAND_MAX = 1 << 20  # tasks per water-level call (one group)
WL_TOTAL_DEMAND_MAX = 1 << 20  # tasks per job/burst (Σ groups, Σ jobs)
WL_M_MAX = 1 << 16  # widest cluster the jnp fallback is certified for
WL_LEVEL_MAX = WL_BUSY0_MAX + WL_TOTAL_DEMAND_MAX
WL_SUM_BMU_MAX = (1 << 30) + (1 << 22)  # admissible Σ busy·μ at entry


def _wl_lanes(m: int) -> int:
    return max(_LANES, _next_pow2(m))


def _wl_dispatch(geom: dict) -> str:
    from repro import backend as backend_config

    with backend_config.set_backend(waterlevel=geom["requested"]):
        return "pallas" if resolve_use_pallas(None, geom["m"]) else "jnp"


def wl_range_claims(m: int) -> list[RangeClaim]:
    """Interval claims shared by the kernel and its jnp twin (identical
    int32 arithmetic).  ``m`` only enters through Σ μ; the Σ busy·μ
    prefix is bounded by the declared envelope, not busy_max·μ_max·m
    (which would be unachievable: raising every busy entry costs demand
    that the envelope also bounds)."""
    busy = Interval(0, WL_LEVEL_MAX)  # evolved levels feed back as busy
    mu = Interval(0, WL_MU_MAX)
    demand = Interval(0, WL_DEMAND_MAX)
    sum_bmu = Interval(0, WL_SUM_BMU_MAX)
    cw = mu * m  # inclusive prefix sum of μ
    xi_num = demand + sum_bmu  # ξ numerator: T + Σ busy·μ
    level = busy + demand + 1  # minimality + the ξ ≥ b+1 clamp
    caps = level * mu  # per-lane capacity at the level
    alloc_prefix = demand + cw  # Σ caps ≤ T + one level step of capacity
    return [
        RangeClaim(
            "sort sentinel headroom (_BIG - busy)",
            Interval.const(_BIG) - busy,
            positive=True,
        ),
        RangeClaim("cw prefix sum (Σ μ)", cw),
        RangeClaim("cbw prefix sum (Σ busy·μ)", sum_bmu),
        RangeClaim("ξ numerator (T + Σ busy·μ)", xi_num),
        RangeClaim("water level", level),
        RangeClaim("per-lane capacity at level", caps),
        RangeClaim("allocation prefix (Alg. 2 clamp)", alloc_prefix),
    ]


def wl_vmem_blocks(geom: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    """Per-invocation VMEM blocks at the padded lane count: kernel
    operands/outputs plus the live scan/sort temporaries (the batch grid
    hands each program the same one-row view).

    Each entry is a stack of separate ``(1, lanes)`` rows; kernelcheck
    pads every row to a whole (8, 128) tile as the TPU lays it out.  The
    stage temporaries (each carry's two lane rotations and their select)
    make the model an upper bound of what the TPU compiler asks for:
    ``tests/test_chip_compile.py`` compiles the kernel with exactly this
    much scoped VMEM."""
    lanes = _wl_lanes(geom["m"])
    row = ((1, lanes), 4)
    return {
        "busy/in": row,
        "mu/in": row,
        "take/out": row,
        "idx/out": row,
        "sort carries (b,w,idx)": ((3, 1, lanes), 4),
        "stage rotations + selects (b,w,idx)": ((9, 1, lanes), 4),
        "scan temporaries (cw,cbw,caps,prev)": ((4, 1, lanes), 4),
    }


def _wl_abstract(geom: dict):
    lanes = _wl_lanes(geom["m"])
    bsz = geom.get("b", 1)
    i32 = jnp.int32
    fn = functools.partial(_waterlevel_call_padded, interpret=True)
    return fn, (
        jax.ShapeDtypeStruct((bsz, lanes), i32),
        jax.ShapeDtypeStruct((bsz, lanes), i32),
        jax.ShapeDtypeStruct((bsz,), i32),
    )


def _scan_sum(x: jax.Array, lane: jax.Array, n: int) -> jax.Array:
    """Inclusive prefix sum along lanes (Hillis–Steele, log2(n) steps).

    The lane rotation wraps, but wrapped lanes (lane < d) are masked to
    0, so the scan is exact for any values.
    """
    d = 1
    while d < n:
        x = x + jnp.where(lane >= d, pltpu.roll(x, d, 1), 0)
        d *= 2
    return x


def _compiler_params() -> pltpu.CompilerParams:
    """Mosaic parameters shared by the single-block kernels (read at
    trace time, so the scoped VMEM follows :data:`VMEM_LIMIT_BYTES`)."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _butterfly(x: jax.Array, lower: jax.Array, j: jax.Array, n: int) -> jax.Array:
    """Partner lanes of one compare-exchange stage: ``x`` rotated ``j``
    lanes left where ``lower``, else ``j`` lanes right.  The TPU lane
    rotation takes only non-negative shifts, so left by ``j`` is right by
    ``n - j``."""
    return jnp.where(lower, pltpu.roll(x, n - j, 1), pltpu.roll(x, j, 1))


@functools.lru_cache(maxsize=None)
def _bitonic_stages(n: int) -> tuple[np.ndarray, np.ndarray]:
    """(K, J) parameters of the n-lane bitonic network's compare-exchange
    stages: merge size k ∈ {2,4,…,n}, butterfly stride j ∈ {k/2,…,1}."""
    ks, js = [], []
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            ks.append(k)
            js.append(j)
            j //= 2
        k *= 2
    return np.asarray(ks, np.int32), np.asarray(js, np.int32)


def _waterlevel_kernel(
    demand_ref, ktab_ref, jtab_ref, b_ref, w_ref, level_ref, take_ref, idx_ref,
    *, n_lanes: int, n_stages: int,
):
    """Fused water level + allocation over one (1, n_lanes) row block
    (grid program ``i`` reads ``demand_ref[i]`` and writes
    ``level_ref[i]``).

    Inputs are pre-masked: ``b = busy`` where available else ``_BIG``,
    ``w = μ`` where available else 0; padded lanes carry the same
    sentinels so they sort past every real lane and contribute zero
    capacity.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_lanes), 1)
    b = b_ref[...]
    w = w_ref[...]
    idx = lane

    # --- bitonic sort, ascending by (busy, original index) ---------------
    # Lexicographic keys are unique, so the network realizes exactly the
    # stable sort order of the jnp path's argsort.  Partner exchange is
    # two rolls + a select (the classic vectorized butterfly): for lanes
    # with bit j clear the partner sits j lanes right, else j lanes left.
    # The O(log²M) stages run as a fori_loop over the (k, j) tables in
    # SMEM — unrolling them makes XLA's CPU compile of the interpreted
    # kernel take ~100× longer for identical results.
    def stage(s, carry):
        b, w, idx = carry
        k, j = ktab_ref[s], jtab_ref[s]
        lower = (lane & j) == 0
        b_p = _butterfly(b, lower, j, n_lanes)
        w_p = _butterfly(w, lower, j, n_lanes)
        i_p = _butterfly(idx, lower, j, n_lanes)
        asc = (lane & k) == 0
        gt = (b > b_p) | ((b == b_p) & (idx > i_p))
        # a lane keeps the pair's min iff it is the lower lane of an
        # ascending block or the upper lane of a descending one
        take_partner = (lower == asc) == gt
        return (
            jnp.where(take_partner, b_p, b),
            jnp.where(take_partner, w_p, w),
            jnp.where(take_partner, i_p, idx),
        )

    b, w, idx = jax.lax.fori_loop(0, n_stages, stage, (b, w, idx))

    # --- prefix sums + masked ceiling-division segment search ------------
    row = pl.program_id(0)
    demand = demand_ref[row]
    cw = _scan_sum(w, lane, n_lanes)
    cbw = _scan_sum(b * w, lane, n_lanes)
    xi = -(-(demand + cbw) // jnp.maximum(cw, 1))
    next_b = jnp.where(lane == n_lanes - 1, _BIG, pltpu.roll(b, n_lanes - 1, 1))
    valid = (xi <= next_b) & (cw > 0)
    # first valid segment, with the jnp path's argmax convention (0 when
    # nothing is valid — the guarded-degenerate case)
    first = jnp.min(jnp.where(valid, lane, n_lanes))
    first = jnp.where(first == n_lanes, 0, first)
    sel = lane == first
    xi0 = jnp.sum(jnp.where(sel, xi, 0))  # exactly one selected lane
    b0 = jnp.sum(jnp.where(sel, b, 0))
    level = jnp.maximum(xi0, b0 + 1)
    level_ref[row] = level

    # --- allocation at the level (Alg. 2 lines 7-13, prefix-sum clamp) ---
    caps = jnp.maximum(level - b, 0) * w
    prev = _scan_sum(caps, lane, n_lanes) - caps  # exclusive prefix
    take_ref[...] = jnp.clip(demand - prev, 0, caps)
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("interpret",))
def _waterlevel_call_padded(
    b2: jax.Array, w2: jax.Array, d: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Invoke the kernel on already-padded ``(B, n_lanes)`` rows.

    ``d`` holds the ``(B,)`` demands.  The grid's ``B`` programs each see
    one ``(1, n_lanes)`` row block and read their demand (and write their
    level) at ``program_id`` in whole-array SMEM vectors, so every row is
    bit-identical to a one-row call (and hence to the jnp path).  The
    stage tables are whole-array SMEM inputs shared by all programs.

    Kept separate from the padding so the jit cache keys on the padded
    lane count, not the caller's ``M`` — every ``M ≤ 128`` shares one
    compile instead of recompiling the kernel per distinct width.
    """
    bsz, n_lanes = b2.shape
    ks, js = _bitonic_stages(n_lanes)
    # (B, 1, n_lanes) with the batch dim squeezed: each block's last two
    # dims equal the array's, which the TPU block-shape rule admits
    row_spec = pl.BlockSpec(
        (None, 1, n_lanes), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    level, take, idx = pl.pallas_call(
        functools.partial(
            _waterlevel_kernel, n_lanes=n_lanes, n_stages=len(ks)
        ),
        grid=(bsz,),
        out_shape=[
            jax.ShapeDtypeStruct((bsz,), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1, n_lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1, n_lanes), jnp.int32),
        ],
        in_specs=[smem, smem, smem, row_spec, row_spec],
        out_specs=[smem, row_spec, row_spec],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(
        d.astype(jnp.int32),
        jnp.asarray(ks),
        jnp.asarray(js),
        b2.reshape(bsz, 1, n_lanes),
        w2.reshape(bsz, 1, n_lanes),
    )
    return level, take[:, 0], idx[:, 0]


def _pad_lanes(b: jax.Array, w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pad the last axis to a power of two (≥ the 128-lane width) with the
    masked-lane sentinels."""
    m = b.shape[-1]
    pad = [(0, 0)] * (b.ndim - 1) + [(0, max(_LANES, _next_pow2(m)) - m)]
    return jnp.pad(b, pad, constant_values=_BIG), jnp.pad(w, pad)


def _waterlevel_call(
    b: jax.Array, w: jax.Array, demand: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pad one problem and invoke the kernel as a one-row grid.

    Returns ``(level, take_sorted, idx_sorted)``; the caller scatters the
    sorted takes back through the permutation (padded lanes carry
    out-of-range indices and zero takes, so a ``mode="drop"`` scatter
    ignores them).
    """
    b2, w2 = _pad_lanes(b[None], w[None])
    d = jnp.asarray(demand, jnp.int32).reshape(1)
    level, take, idx = _waterlevel_call_padded(b2, w2, d, interpret=interpret)
    return level[0], take[0], idx[0]


def _masked_inputs(
    busy: jax.Array, mu: jax.Array, mask: jax.Array
) -> tuple[jax.Array, jax.Array]:
    b = jnp.where(mask, busy.astype(jnp.int32), _BIG)
    w = jnp.where(mask, mu.astype(jnp.int32), 0)
    return b, w


def _interp(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def water_level_pallas(
    busy: jax.Array,
    mu: jax.Array,
    mask: jax.Array,
    demand: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Kernel-backed twin of :func:`repro.core.wf_jax.water_level`.

    Bit-identical to the jnp path, including the ``demand <= 0`` →
    minimum-available-busy convention (handled here, outside the kernel).
    """
    b, w = _masked_inputs(busy, mu, mask)
    demand = jnp.asarray(demand, jnp.int32)
    level, _, _ = _waterlevel_call(b, w, demand, interpret=_interp(interpret))
    return jnp.where(demand > 0, level, b.min())


@contract(
    "waterlevel.kernel",
    axes=(
        span(
            "m",
            1,
            PALLAS_MAX_M,
            boundaries=(_LANES, 1 << 12, PALLAS_MAX_M),
            past=(PALLAS_MAX_M + 1, PALLAS_MAX_M * 2),
        ),
        choice("requested", "jnp", "pallas"),
    ),
    backends=("jnp", "pallas"),
    device_backends=("pallas",),
    dispatch=_wl_dispatch,
    vmem=wl_vmem_blocks,
    ranges=lambda geom: wl_range_claims(geom["m"]),
    signature=lambda geom: ("waterlevel", _wl_lanes(geom["m"])),
    max_signatures=16,  # pow2 lane classes from 128 to PALLAS_MAX_M
    abstract=_wl_abstract,
    eval_points=3,
    notes="single-block fused sort+scan water level; widths past "
    "PALLAS_MAX_M must fall back to jnp even when pallas is forced",
)
def water_fill_alloc_pallas(
    busy: jax.Array,
    mu: jax.Array,
    mask: jax.Array,
    demand: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Kernel-backed twin of :func:`repro.core.wf_jax.water_fill_alloc`.

    One ``pallas_call`` computes the level and the sorted takes; the only
    op outside the kernel is the scatter through the sort permutation
    (and the ``demand <= 0`` level convention, which cannot affect the
    all-zero allocation).
    """
    b, w = _masked_inputs(busy, mu, mask)
    demand = jnp.asarray(demand, jnp.int32)
    level, take, idx = _waterlevel_call(b, w, demand, interpret=_interp(interpret))
    alloc = jnp.zeros(b.shape[0], jnp.int32).at[idx].set(take, mode="drop")
    return alloc, jnp.where(demand > 0, level, b.min())


@contract(
    "waterlevel.kernel-batch",
    axes=(
        span(
            "m",
            1,
            PALLAS_MAX_M,
            boundaries=(_LANES, PALLAS_MAX_M),
            past=(PALLAS_MAX_M + 1,),
        ),
        choice("b", 1, 2, 7, 32, 64),
        choice("requested", "jnp", "pallas"),
    ),
    backends=("jnp", "pallas"),
    device_backends=("pallas",),
    dispatch=_wl_dispatch,
    vmem=wl_vmem_blocks,  # the (B,) grid hands each program one row's blocks
    ranges=lambda geom: wl_range_claims(geom["m"]),
    signature=lambda geom: ("waterlevel-batch", geom["b"], _wl_lanes(geom["m"])),
    max_signatures=32,  # burst-size values × pow2 lane classes
    abstract=_wl_abstract,
    eval_points=3,
    notes="batched-grid twin; B enters the jit cache unpadded here — "
    "the wf_jax chain adapter pads it, the plain batch adapter keys "
    "on the caller's burst size",
)
def water_fill_alloc_pallas_batch(
    busy: jax.Array,
    mu: jax.Array,
    mask: jax.Array,
    demand: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched kernel twin of :func:`water_fill_alloc_pallas`.

    ``busy``/``mu``/``mask`` are ``(B, M)``, ``demand`` is ``(B,)``; one
    ``pallas_call`` over a ``(B,)`` grid computes every row's level and
    sorted takes, then a single scatter restores the per-row server
    order.  Row ``i`` is bit-identical to
    ``water_fill_alloc_pallas(busy[i], mu[i], mask[i], demand[i])``.
    """
    b, w = _masked_inputs(busy, mu, mask)
    demand = jnp.asarray(demand, jnp.int32)
    bsz, m = b.shape
    b2, w2 = _pad_lanes(b, w)
    level, take, idx = _waterlevel_call_padded(
        b2, w2, demand.reshape(bsz), interpret=_interp(interpret)
    )
    rows = jnp.arange(bsz)[:, None]
    alloc = jnp.zeros((bsz, m), jnp.int32).at[rows, idx].set(take, mode="drop")
    return alloc, jnp.where(demand > 0, level, b.min(axis=1))
