"""Pallas RD strip kernel: fused max-key scan + bucket walk.

The inner loop of device Replica-Deletion (:mod:`repro.core.rd_jax`) is
the *strip*: order every candidate class by the deletion key
``(-count, alt, surviving-server set, group, slot)`` and walk the prefix
until the strip quota is exhausted.  The jnp path materializes that as a
multi-key ``lexsort`` (one stable sort per key component) plus a cumsum
and a clip — several XLA ops over the ``(C,)`` slot arrays per strip,
and RD runs hundreds to thousands of strips per arrival.  This kernel
fuses the whole scan into one VMEM-resident program, reusing the
waterlevel kernel's recipe (:mod:`repro.kernels.waterlevel`):

- **sort**: the same bitonic compare-exchange network (stage tables in
  SMEM, ``fori_loop`` over them), except the key is *multi-row*: the
  ``(R, C)`` key block carries ``-count``, alt, the packed holder-row
  words (two 15-bit server ids per int32), and group as rows, compared
  lexicographically with the lane index as the final unique tie — the
  identical total order to ``jnp.lexsort`` on the same components, so
  both backends produce the same permutation bit-for-bit;
- **bucket walk**: a Hillis–Steele prefix sum of the sorted member
  counts and the quota clamp ``take = clip(quota - prev, 0, size)``
  emit every class's deletion in-register (non-candidates ride along
  with a ``_BIG`` primary key and zero size, exactly like the
  waterlevel kernel's masked lanes).

The caller scatters the sorted takes back through the returned
permutation and applies the delta updates in shared jnp, so jnp and
Pallas strips are interchangeable mid-run.

Dispatch: :func:`repro.core.rd.resolve_rd_backend` picks the backend
(TPU→``pallas``, CPU→``host`` under ``auto``;
``set_backend(rd=...)`` scopes override); geometries beyond the
single-block VMEM bounds (:func:`rd_pallas_fits`) fall back to the jnp
strip regardless, like ``PALLAS_MAX_M`` in the waterlevel kernel.
Off-TPU the kernel runs under ``interpret=True`` (tests and the
``--rd-sweep`` benchmark).  The geometry contract is declared below via
:func:`repro.analysis.contracts.contract` and verified by
``python -m repro.analysis.kernelcheck``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.contracts import Axis, contract

# shared plumbing: stage tables, prefix scan, interpret resolution
from .waterlevel import (
    _bitonic_stages,
    _butterfly,
    _compiler_params,
    _interp,
    _scan_sum,
)

__all__ = [
    "RD_PALLAS_MAX_C",
    "RD_PALLAS_MAX_KEY_ROWS",
    "rd_pallas_fits",
    "rd_strip_takes_pallas",
]

_BIG = 2**30  # must match repro.core.rd_jax._BIG (non-candidate sentinel)

# single-block VMEM bounds: the (R, C) key block plus sort temporaries
# must stay resident, so cap the slot lanes and the key rows (R = P + 3:
# -count, alt, the P packed holder words, group)
RD_PALLAS_MAX_C = 1 << 15
RD_PALLAS_MAX_KEY_ROWS = 24


def rd_pallas_fits(c_slots: int, n_key_rows: int) -> bool:
    """True when the slot geometry fits the single-block kernel."""
    return c_slots <= RD_PALLAS_MAX_C and n_key_rows <= RD_PALLAS_MAX_KEY_ROWS


# ---------------------------------------------------------------------------
# kernelcheck geometry contract (verified by repro.analysis.kernelcheck).
#
# Admissible input envelope for the strip key rows: replica counts (the
# ``-count`` primary key) come from per-task holder sets, member counts
# sum to the instance's task total, and the alt row carries busy values
# or the ``_BIG`` sole-copy sentinel.

RD_ENV_A_MAX = 1 << 6  # replication-factor bound (−count key row)
RD_ENV_TASKS_MAX = 1 << 20  # Σ member counts per instance (prefix sum)


def _rd_strip_dispatch(geom: dict) -> str:
    return "pallas" if rd_pallas_fits(geom["c"], geom["rows"]) else "jnp"


def _rd_strip_vmem(geom: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    """Per-invocation VMEM blocks, as :func:`repro.kernels.waterlevel.
    wl_vmem_blocks` counts them: kernelcheck pads each ``(rows, c)``
    block and each ``(1, c)`` row to whole (8, 128) tiles.  The key block
    is carried like the row arrays (two rotations and a select per
    stage), and the lexicographic compare slices one ``(1, c)`` row per
    key row out of the block and its partner."""
    c, rows = geom["c"], geom["rows"]
    keys = ((rows, c), 4)
    return {
        "keys/in": keys,
        "size/in": ((1, c), 4),
        "take/out": ((1, c), 4),
        "idx/out": ((1, c), 4),
        "sort carry (keys)": keys,
        "sort carries (size,idx)": ((2, 1, c), 4),
        "stage rotations + selects (keys)": ((3, rows, c), 4),
        "stage rotations + selects (size,idx)": ((6, 1, c), 4),
        "compare row slices": ((rows, 1, c), 4),
        "scan temporaries (prefix,prev)": ((2, 1, c), 4),
    }


def _rd_strip_ranges(geom: dict) -> list:
    from repro.analysis.contracts import Interval, RangeClaim

    neg_count = Interval(-RD_ENV_A_MAX, 0)
    tasks = Interval(0, RD_ENV_TASKS_MAX)
    # packed holder words: two 15-bit ids per int32, must match
    # repro.core.rd_jax._PACK_BITS (claimed precisely in that contract)
    packed = (Interval(0, (1 << 15) - 1) << 15) | Interval(0, (1 << 15) - 1)
    return [
        RangeClaim(
            "non-candidate sentinel headroom (_BIG − max real −count)",
            Interval.const(_BIG) - neg_count,
            positive=True,
        ),
        RangeClaim("alt key row (busy or _BIG sentinel)", Interval(0, _BIG)),
        RangeClaim("packed holder key word", packed, bits=30),
        RangeClaim("member-count prefix sum", tasks),
        RangeClaim("quota clamp (quota − prev)", Interval(-RD_ENV_TASKS_MAX, RD_ENV_TASKS_MAX)),
    ]


def _rd_strip_abstract(geom: dict):
    c, rows = geom["c"], geom["rows"]
    i32 = jnp.int32
    fn = functools.partial(_rd_strip_call, interpret=True)
    return fn, (
        jax.ShapeDtypeStruct((rows, c), i32),
        jax.ShapeDtypeStruct((c,), i32),
        jax.ShapeDtypeStruct((), i32),
    )


def _rd_strip_kernel(
    quota_ref, ktab_ref, jtab_ref, keys_ref, size_ref, take_ref, idx_ref,
    *, n_lanes: int, n_stages: int, n_rows: int,
):
    """One fused strip scan over a ``(n_rows, n_lanes)`` key block.

    Lanes are class slots; key rows are most-significant first and every
    component ascending (``-count`` realizes the descending count
    bucket order), with the lane index as the final tie — keys are
    therefore unique and the network realizes exactly the ``lexsort``
    order of the jnp strip.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_lanes), 1)
    kb = keys_ref[...]
    sz = size_ref[...]
    idx = lane

    def stage(s, carry):
        kb, sz, idx = carry
        k, j = ktab_ref[s], jtab_ref[s]
        lower = (lane & j) == 0
        kb_p = _butterfly(kb, lower, j, n_lanes)
        sz_p = _butterfly(sz, lower, j, n_lanes)
        i_p = _butterfly(idx, lower, j, n_lanes)
        # lexicographic compare over the key rows, lane index last
        gt = jnp.zeros((1, n_lanes), jnp.bool_)
        eq = jnp.ones((1, n_lanes), jnp.bool_)
        for r in range(n_rows):
            a, b = kb[r : r + 1], kb_p[r : r + 1]
            gt = gt | (eq & (a > b))
            eq = eq & (a == b)
        gt = gt | (eq & (idx > i_p))
        asc = (lane & k) == 0
        take_partner = (lower == asc) == gt
        return (
            jnp.where(take_partner, kb_p, kb),
            jnp.where(take_partner, sz_p, sz),
            jnp.where(take_partner, i_p, idx),
        )

    kb, sz, idx = jax.lax.fori_loop(0, n_stages, stage, (kb, sz, idx))

    # --- bucket walk: prefix-sum sizes against the quota -----------------
    cand = kb[0:1] != _BIG  # non-candidates carry the sentinel primary key
    s = jnp.where(cand, sz, 0)
    prev = _scan_sum(s, lane, n_lanes) - s  # exclusive prefix
    quota = quota_ref[0, 0]
    take_ref[...] = jnp.clip(quota - prev, 0, s)
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rd_strip_call(
    keys: jax.Array, size: jax.Array, quota: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    n_rows, n_lanes = keys.shape
    ks, js = _bitonic_stages(n_lanes)
    take, idx = pl.pallas_call(
        functools.partial(
            _rd_strip_kernel,
            n_lanes=n_lanes,
            n_stages=len(ks),
            n_rows=n_rows,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, n_lanes), jnp.int32),
            jax.ShapeDtypeStruct((1, n_lanes), jnp.int32),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(
        quota.astype(jnp.int32).reshape(1, 1),
        jnp.asarray(ks),
        jnp.asarray(js),
        keys.astype(jnp.int32),
        size.astype(jnp.int32).reshape(1, n_lanes),
    )
    return take[0], idx[0]


@contract(
    "rd.strip",
    axes=(
        Axis("c", (128, 256, 1024, 4096, RD_PALLAS_MAX_C), past=(RD_PALLAS_MAX_C * 2,)),
        Axis("rows", (4, 8, 23, RD_PALLAS_MAX_KEY_ROWS), past=(25, 32)),
    ),
    backends=("jnp", "pallas"),
    device_backends=("pallas",),
    dispatch=_rd_strip_dispatch,
    vmem=_rd_strip_vmem,
    ranges=_rd_strip_ranges,
    signature=lambda geom: ("rd-strip", geom["c"], geom["rows"]),
    max_signatures=24,  # pow2 slot classes × holder-row classes
    abstract=_rd_strip_abstract,
    eval_points=3,
    notes="single-block multi-row lexicographic strip scan; geometries "
    "past (RD_PALLAS_MAX_C, RD_PALLAS_MAX_KEY_ROWS) fall back to the "
    "jnp lexsort strip",
)
def rd_strip_takes_pallas(
    keys: jax.Array,
    size: jax.Array,
    quota: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Kernel-backed strip scan: ``(take_sorted, permutation)``.

    ``keys`` is the ``(P+3, C)`` key block (rows most-significant first:
    masked ``-count``, alt, the P packed holder words, group), ``size``
    the ``(C,)`` member counts, ``quota`` the strip's replica budget.
    ``C`` must be a power of two ≥ 128 (the caller's slot capacity
    already is).  The caller scatters ``take_sorted`` back through the
    returned permutation — bit-identical to the jnp ``lexsort`` strip.
    """
    n_rows, n_lanes = keys.shape
    if n_lanes & (n_lanes - 1) or n_lanes < 128:
        raise ValueError(
            f"slot lanes must be a power of two >= 128, got {n_lanes}"
        )
    if not rd_pallas_fits(n_lanes, n_rows):
        raise ValueError(
            f"slot geometry ({n_rows} rows, {n_lanes} lanes) exceeds the "
            "single-block kernel bounds"
        )
    return _rd_strip_call(keys, size, quota, interpret=_interp(interpret))
