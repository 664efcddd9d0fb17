"""Fixed-shape Replica-Deletion on device — the jnp/Pallas form of RD.

The class-compressed host RD (:mod:`repro.core.rd`) is the last
scheduling hot path living in per-strip CPython.  This module recasts it
as a fixed-shape array program driven by ``lax.while_loop`` so the whole
deletion + dedup pipeline runs as one device dispatch (and a same-slot
burst as one *chained* dispatch, the RD twin of ``water_fill_chain``).

State is the class-compressed state made dense.  A *slot* is one
equivalence class ``(group, surviving servers)``:

- ``holders``: ``(C, A)`` int32 — the class's server set, sorted
  ascending, padded with ``M`` (sorts after every real id); ``A`` is the
  maximum initial availability width, and a class's holder row is
  *static* for its lifetime (deletions spin members into a new slot).
- ``setkey``: ``(A/2, C)`` the holder rows packed two ids a word, one
  row per word, as the strip kernel's key block takes them.
- ``size``/``cnt``/``grp``: ``(C,)`` member count (0 = drained or
  unallocated), replica count, group id.
- ``m1``/``b1``/``b2``: the cheapest-alternative tie-break triple of
  :meth:`repro.core.rd._Cls._compute_alt`, computed once per slot.
- ``dest``: ``(C, A)`` spin-off pointer cache aligned with ``holders``
  (``dest[c, j]`` = slot holding members of ``c`` after a strip of
  ``holders[c, j]``; ``-1`` = not yet materialized).
- ``load``/``multi``/``busy_est``: ``(M,)`` delta-updated server state.
- ``hist``: active classes per (replica count, server), the device
  twin of the host's count buckets, flat: count ``k``'s row of ``M``
  servers starts at ``k·Mp`` (``Mp`` = ``M`` in whole 1,024-lane
  tiles, so rows slice on tile bounds and updates scatter 1-D).  The
  deletion phase's per-server peek (max active count) is a dense max
  over its ``A+1`` rows.

One *strip* of server ``m`` is a vectorized select-target →
bucket-walk → delta-update step: candidates (active, on ``m``, multi-
copy) sort by the strip key ``(-count, alt, holders-row, group, slot)``
— within one count bucket every class has the same cardinality, so
comparing holder rows lexicographically *is* the reference's sorted
server-tuple order — then a prefix-sum of member counts against the
quota ``((load-1) mod μ)+1`` yields every class's deletion in one shot.
Each moving class loses at least one member, so at most ``quota ≤ μ``
classes move, all in a prefix of the sorted order: the delta updates
read and write those ``_MOVERS`` rows only (spin-off slots are the
lowest empty slots, so drained classes are recycled; duplicate
``(group, set)`` slots reached via different strip paths are
exchangeable under the total key, so no global dict is needed).  With
``backend="pallas"`` the sort + prefix walk runs as the fused kernel in
:mod:`repro.kernels.rd` (bitonic network over the slot
lanes with the multi-row lexicographic key, Hillis–Steele prefix sums —
the waterlevel kernel's recipe); the surrounding delta updates are
shared jnp either way, so the two device backends are permutation-
identical by construction.

Slot capacity ``C`` is fixed per dispatch (power-of-two padded) and
sized so it cannot run out (:func:`rd_slot_capacity`).  Should a
smaller capacity ever be exceeded, or a strip move more than
``_MOVERS`` classes (μ past the contract's ``RD_ENV_MU_MAX``), the
program sets an ``overflow`` flag and the host adapter re-runs the
instance through host RD, so results stay correct for any input.

Every backend is *assignment-identical* to the executable specification
in :mod:`repro.core.rd_reference` under the documented deterministic
tie-breaks; ``tests/test_rd_parity.py`` asserts that (hypothesis +
deterministic twins) and the engine-level schedule equality of the
chained burst dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import Interval, RangeClaim, choice, contract, span
from repro.obs.session import active as _obs_active
from repro.obs.session import span as _obs_span

from .dispatch import phased_call
from .instance import Assignment, AssignmentProblem, TaskGroup
from .rd import RD_DEVICE_MAX_M, replica_deletion

__all__ = [
    "replica_deletion_jax",
    "replica_deletion_jax_chain",
    "rd_slot_capacity",
]

_BIG = 1 << 30  # matches repro.core.rd._BIG (sole-copy alt sentinel)

_MIN_LANES = 128  # TPU lane width: minimum padded slot capacity

# sort keys pack two 15-bit server ids per int32 word: lexicographic on
# the packed words == lexicographic on the sorted holder rows (fields are
# fixed-width and the pad id M sorts after every real id), at half the
# lexsort passes / kernel key rows.  Requires M <= RD_DEVICE_MAX_M.
_PACK_BITS = 15


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _ceil_div(a: jax.Array, b: jax.Array) -> jax.Array:
    return -(-a // b)


def rd_slot_capacity(problem: AssignmentProblem) -> int:
    """Slot capacity ``C`` for one instance (power of two, ≥128 lanes).

    Two bounds on the slots a strip can need, whichever is smaller:

    - every move event (one class losing members to one spin-off)
      creates at most one slot and deletes at least one replica, so
      ``K + Σ_k size_k·(|S_k|-1)`` slots are ever allocated;
    - drained slots are recycled, so the slots in use are the live
      classes — each holds at least one of the ``n`` tasks — plus the
      spin-offs of the strip in flight, at most one per moving class and
      so at most the quota ``≤ max μ``.

    The capacity therefore never overflows; the ``overflow`` flag and its
    host re-run only guard a smaller capacity forced from outside.
    """
    k = len(problem.groups)
    hard = k + sum(g.size * (len(g.servers) - 1) for g in problem.groups) + 1
    live = problem.n_tasks + int(np.max(problem.mu, initial=1))
    return max(_MIN_LANES, _next_pow2(min(hard, live)))


def _pack_setkey(holders: jax.Array) -> jax.Array:
    """(C, A) holder rows → (C, A/2) packed sort-key words."""
    c_slots, a_pad = holders.shape
    pairs = holders.reshape(c_slots, a_pad // 2, 2)
    return (pairs[:, :, 0] << _PACK_BITS) | pairs[:, :, 1]


class _RDDev(NamedTuple):
    """The dense class-compressed state carried through the while loops."""

    holders: jax.Array  # (C, A) i32, sorted asc, pad = M
    setkey: jax.Array  # (A/2, C) i32 packed holder rows (strip sort key)
    dest: jax.Array  # (C, A) i32 spin-off pointers, -1 = none
    size: jax.Array  # (C,) i32 members (0 = drained / unallocated)
    cnt: jax.Array  # (C,) i32 replica count (static per slot)
    grp: jax.Array  # (C,) i32 group id
    m1: jax.Array  # (C,) i32 cheapest holder
    b1: jax.Array  # (C,) i32 its initial busy time
    b2: jax.Array  # (C,) i32 second-cheapest initial busy time
    load: jax.Array  # (M,) i32
    multi: jax.Array  # (M,) i32 multi-copy population per server
    busy_est: jax.Array  # (M,) i32  b_m + ceil(load_m/mu_m)
    hist: jax.Array | None  # ((A+1)·Mp,) i32 classes per count; dedup: None
    overflow: jax.Array  # () bool — slots or movers exceeded, result invalid


def _alt_triple(
    holders: jax.Array, busy0: jax.Array, m_servers: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Vectorized :meth:`_Cls._compute_alt`: per-row ``(m1, b1, b2)``.

    Rows are sorted ascending by id, so ``argmin``'s first-occurrence
    convention reproduces the reference's first-strict-min holder.
    """
    real = holders < m_servers
    hb = jnp.where(real, busy0[jnp.where(real, holders, 0)], _BIG)  # pads: _BIG
    j1 = jnp.argmin(hb, axis=1)[:, None]
    b1 = jnp.take_along_axis(hb, j1, axis=1)[:, 0]
    m1 = jnp.take_along_axis(holders, j1, axis=1)[:, 0]
    col = jnp.arange(holders.shape[1], dtype=j1.dtype)
    b2 = jnp.min(jnp.where(col == j1, _BIG, hb), axis=1)
    return m1, b1, b2


def _strip_order_jnp(
    neg_key: jax.Array, altv: jax.Array, setkey: jax.Array, grp: jax.Array
) -> jax.Array:
    """Slot permutation realizing the strip key via ``jnp.lexsort``.

    Key (most significant first): masked ``-count`` (``_BIG`` parks
    non-candidates past every candidate), alt, the packed holder rows
    (ascending-lexicographic ≡ the reference's sorted server-tuple
    order within a count bucket, where cardinalities are equal), group,
    slot index — a total order, so the Pallas sorting network (same key,
    unique final tie) yields the identical permutation.
    """
    p_words, c_slots = setkey.shape
    keys = (jnp.arange(c_slots, dtype=jnp.int32), grp)
    keys += tuple(setkey[a] for a in range(p_words - 1, -1, -1))
    keys += (altv, neg_key)
    return jnp.lexsort(keys)


def _strip(
    st: _RDDev,
    m: jax.Array,
    busy0: jax.Array,
    mu: jax.Array,
    *,
    use_pallas: bool,
    interpret: bool,
) -> tuple[_RDDev, jax.Array, jax.Array]:
    """Delete up to ``((load-1) mod μ)+1`` multi-copy replicas from ``m``.

    The reference's sequential max-key pops collapse into one sort +
    prefix-sum (keys are static within a strip — deleted members leave
    ``m``).  The takes are a prefix of the sorted order and each moving
    class gives at least one member, so the movers are the first
    ``_MOVERS`` sorted slots; every delta update reads and writes their
    rows and their spin-offs' only.  Returns the state, the number of
    replicas removed and the number of classes moved.
    """
    c_slots = st.holders.shape[0]
    m_servers = st.load.shape[0]
    quota = ((st.load[m] - 1) % mu[m]) + 1

    onm = (st.holders == m).any(axis=1)
    cand = onm & (st.size > 0) & (st.cnt >= 2)
    altv = jnp.where(st.m1 == m, st.b2, st.b1)
    neg_key = jnp.where(cand, -st.cnt, _BIG)

    # --- bucket walk: sort by the strip key, prefix-sum sizes vs quota ---
    if use_pallas:
        from repro.kernels.rd import rd_strip_takes_pallas

        keyblock = jnp.concatenate(
            [neg_key[None], altv[None], st.setkey, st.grp[None]]
        )
        take_sorted, order = rd_strip_takes_pallas(
            keyblock, st.size, quota, interpret=interpret
        )
    else:
        order = _strip_order_jnp(neg_key, altv, st.setkey, st.grp)
        s_sorted = jnp.where(neg_key[order] != _BIG, st.size[order], 0)
        prev = jnp.cumsum(s_sorted) - s_sorted
        take_sorted = jnp.clip(quota - prev, 0, s_sorted)

    # --- the movers: the first q sorted slots ----------------------------
    q = min(_MOVERS, c_slots)
    src, take = order[:q], take_sorted[:q]
    mv = take > 0
    removed = take.sum()
    # a quota past _MOVERS (μ outside the contract) cannot be applied
    overflow = st.overflow | (take_sorted[q:] > 0).any()
    h = st.holders[src]  # (q, A)
    cnt_s = st.cnt[src]
    is_m = h == m
    jpos = jnp.argmax(is_m, axis=1)  # m's column

    # --- re-home the deleted members (spin-off slots, O(1) per class) ---
    # An existing destination still holds members, so it is never free:
    # it drains only in a strip of one of its holders, where its source
    # (a superset, so a higher count) sorts before it and drains first,
    # and a drained class refills only from its own source, up to an
    # initial class that nothing refills.
    d_exist = st.dest[src, jpos]
    need_new = mv & (d_exist < 0)
    # spin-offs take the lowest empty slots, in their sources' slot order
    n_free = jnp.cumsum(st.size == 0)
    rank = ((src[None, :] < src[:, None]) & need_new[None, :]).sum(axis=1)
    d_new = (n_free[None, :] <= rank[:, None]).sum(axis=1)  # C: none left
    d = jnp.where(need_new, d_new, d_exist)
    overflow = overflow | (need_new.sum() > n_free[-1])

    # spun holder row: drop the (unique) entry equal to m, shift left
    shifted = jnp.concatenate(
        [h[:, 1:], jnp.full((q, 1), m_servers, jnp.int32)], axis=1
    )
    spun = jnp.where(jnp.cumsum(is_m, axis=1) > 0, shifted, h)

    tgt_new = jnp.where(need_new, d, c_slots)  # OOB rows are dropped
    holders = st.holders.at[tgt_new].set(spun, mode="drop")
    setkey = st.setkey.at[:, tgt_new].set(_pack_setkey(spun).T, mode="drop")
    grp = st.grp.at[tgt_new].set(st.grp[src], mode="drop")
    cnt = st.cnt.at[tgt_new].set(cnt_s - 1, mode="drop")
    nm1, nb1, nb2 = _alt_triple(spun, busy0, m_servers)
    m1 = st.m1.at[tgt_new].set(nm1, mode="drop")
    b1 = st.b1.at[tgt_new].set(nb1, mode="drop")
    b2 = st.b2.at[tgt_new].set(nb2, mode="drop")
    # a recycled slot starts a new class: its own spin-off pointers are stale
    dest = st.dest.at[tgt_new].set(-1, mode="drop")
    dest = dest.at[jnp.where(mv, src, c_slots), jpos].set(d, mode="drop")

    tgt_mv = jnp.where(mv, d, c_slots)
    drained = mv & (st.size[src] == take)
    size = st.size.at[src].add(-take).at[tgt_mv].add(take, mode="drop")

    # --- delta-update the server vectors -------------------------------
    multi = st.multi.at[m].add(-removed)
    # members of a count-2 class became sole-copy on their last holder
    c2 = mv & (cnt_s == 2)
    multi = multi.at[jnp.where(c2, spun[:, 0], m_servers)].add(-take, mode="drop")
    load = st.load.at[m].add(-removed)
    busy_est = st.busy_est.at[m].set(busy0[m] + _ceil_div(load[m], mu[m]))

    hist = st.hist
    if hist is not None:
        # a drained mover leaves every holder's count bucket; a new
        # spin-off enters its holders' buckets one count lower
        rows = jnp.concatenate([h, spun])
        on = jnp.concatenate([drained, need_new])
        kcnt = jnp.concatenate([cnt_s, cnt_s - 1])
        delta = jnp.repeat(jnp.asarray([-1, 1], jnp.int32), q)
        hist = hist.at[_hist_index(hist, kcnt, rows, on, m_servers)].add(
            jnp.broadcast_to(delta[:, None], rows.shape), mode="drop"
        )

    return (
        _RDDev(
            holders=holders,
            setkey=setkey,
            dest=dest,
            size=size,
            cnt=cnt,
            grp=grp,
            m1=m1,
            b1=b1,
            b2=b2,
            load=load,
            multi=multi,
            busy_est=busy_est,
            hist=hist,
            overflow=overflow,
        ),
        removed,
        mv.sum(dtype=jnp.int32),
    )


def _hist_stride(m_servers: int) -> int:
    """Row stride of the flat count-bucket table: M in whole 1-D tiles."""
    return -(-m_servers // 1024) * 1024


def _hist_index(
    hist: jax.Array,
    cnt: jax.Array,
    holders: jax.Array,
    on: jax.Array,
    m_servers: int,
) -> jax.Array:
    """Flat bucket index of each (row's count, holder); pads and rows
    not ``on`` index past the table, so a ``drop`` scatter skips them."""
    idx = cnt[:, None] * _hist_stride(m_servers) + holders
    return jnp.where(on[:, None] & (holders < m_servers), idx, hist.shape[0])


def _peek_vec(hist: jax.Array, m_servers: int) -> jax.Array:
    """Max replica count among active classes, per server (0 = none)."""
    stride = _hist_stride(m_servers)
    peek = jnp.zeros(m_servers, jnp.int32)
    for k in range(1, hist.shape[0] // stride):
        peek = jnp.where(hist[k * stride : k * stride + m_servers] > 0, k, peek)
    return peek


def _refine_max(mask: jax.Array, key: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Narrow ``mask`` to the entries attaining ``max(key over mask)``."""
    best = jnp.max(jnp.where(mask, key, jnp.iinfo(jnp.int32).min))
    return mask & (key == best), best


def _init_state(
    busy0: jax.Array,
    mu: jax.Array,
    holders0: jax.Array,
    size0: jax.Array,
    cnt0: jax.Array,
    grp0: jax.Array,
) -> _RDDev:
    """The loops' starting state: one slot per task group (one pass of
    C × A scatters builds the per-server vectors and count buckets)."""
    c_slots, a_max = holders0.shape
    m_servers = busy0.shape[0]
    m1, b1, b2 = _alt_triple(holders0, busy0, m_servers)

    def per_server(vals):
        return jnp.zeros(m_servers, jnp.int32).at[holders0].add(
            jnp.broadcast_to(vals[:, None], holders0.shape), mode="drop"
        )

    load = per_server(size0)
    hist = jnp.zeros((a_max + 1) * _hist_stride(m_servers), jnp.int32)
    hist = hist.at[_hist_index(hist, cnt0, holders0, size0 > 0, m_servers)].add(
        1, mode="drop"
    )
    return _RDDev(
        holders=holders0,
        setkey=_pack_setkey(holders0).T,
        dest=jnp.full((c_slots, a_max), -1, jnp.int32),
        size=size0,
        cnt=cnt0,
        grp=grp0,
        m1=m1,
        b1=b1,
        b2=b2,
        load=load,
        multi=per_server(jnp.where(cnt0 >= 2, size0, 0)),
        busy_est=busy0 + _ceil_div(load, mu),
        hist=hist,
        overflow=jnp.asarray(False),
    )


def _rd_core(
    busy0: jax.Array,
    mu: jax.Array,
    holders0: jax.Array,
    size0: jax.Array,
    cnt0: jax.Array,
    grp0: jax.Array,
    *,
    use_pallas: bool,
    interpret: bool,
) -> tuple[_RDDev, jax.Array, jax.Array]:
    """Run the whole RD (deletion + dedup) for one instance on device;
    returns the final state, the iterations the two loops ran (deletion
    iterations, strip or not, plus dedup strips) and the classes their
    strips moved (int32 each)."""
    m_servers = busy0.shape[0]
    busy0 = busy0.astype(jnp.int32)
    mu = mu.astype(jnp.int32)
    st = _init_state(busy0, mu, holders0, size0, cnt0, grp0)
    strip = functools.partial(
        _strip, busy0=busy0, mu=mu, use_pallas=use_pallas, interpret=interpret
    )
    zero = jnp.asarray(0, jnp.int32)

    # ---- deletion phase --------------------------------------------------
    # One iteration = one strip, with the level sweep folded in: when the
    # previous sweep's target set is exhausted, the same iteration opens a
    # new sweep (recomputes the max busy level + its servers and applies
    # the sole-copy exit check) before selecting a target.  Target
    # selection is a fresh argmin of (-peek count, -busy0, id) over the
    # still-valid sweep targets — exactly what the host's lazy re-ranking
    # heap realizes (stale keys are optimistic and validated at pop).
    def del_cond(carry):
        st, targets0, best, done, _, _ = carry
        return ~done & ~st.overflow

    def del_body(carry):
        st, targets0, best, done, iters, moved = carry
        valid = targets0 & (st.busy_est == best) & (st.load > 0)
        new_sweep = ~valid.any()
        held = st.load > 0
        nbest = jnp.max(jnp.where(held, st.busy_est, -1))
        ntargets = held & (st.busy_est == nbest)
        best = jnp.where(new_sweep, nbest, best)
        targets0 = jnp.where(new_sweep, ntargets, targets0)
        valid = jnp.where(new_sweep, ntargets, valid)
        # sweep-entry exit: a target holding only sole-copy tasks means
        # the max busy level cannot drop any further
        done_now = new_sweep & (
            (nbest < 0) | (ntargets & (st.multi == 0)).any()
        )
        mask, p = _refine_max(valid, _peek_vec(st.hist, m_servers))
        mask, _ = _refine_max(mask, busy0)
        m = jnp.argmax(mask)  # ties fall to the smallest id
        do_strip = ~done_now & (p >= 2)
        st, removed, n_moved = jax.lax.cond(
            do_strip,
            lambda s: strip(s, m),
            lambda s: (s, zero, zero),
            st,
        )
        # a strip that ran out of quota drained m's multi-copy classes;
        # any still-max server with no multi-copy tasks ends the phase
        tmask = (st.load > 0) & (st.busy_est == best)
        done = (
            done_now
            | (~done_now & (p <= 1))
            | (do_strip & (removed == 0))
            | (do_strip & (tmask & (st.multi == 0)).any())
        )
        return st, targets0, best, done, iters + 1, moved + n_moved

    st, _, _, _, iters, moved = jax.lax.while_loop(
        del_cond,
        del_body,
        (st, jnp.zeros(m_servers, bool), jnp.asarray(-2, jnp.int32),
         jnp.asarray(False), zero, zero),
    )

    # ---- final dedup phase ----------------------------------------------
    # One strip per iteration from the busiest multi-copy holder,
    # (busy_est, busy0, id) descending — the reference's lexsort pick.
    # It reads no peek, so it carries no count buckets.
    def dd_cond(carry):
        st, _, _ = carry
        return (st.multi > 0).any() & ~st.overflow

    def dd_body(carry):
        st, iters, moved = carry
        mask = st.multi > 0
        mask, _ = _refine_max(mask, st.busy_est)
        mask, _ = _refine_max(mask, busy0)
        m = m_servers - 1 - jnp.argmax(mask[::-1])  # ties -> largest id
        st, _, n_moved = strip(st, m)
        return st, iters + 1, moved + n_moved

    return jax.lax.while_loop(
        dd_cond, dd_body, (st._replace(hist=None), iters, moved)
    )


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _rd_device(busy0, mu, holders0, size0, cnt0, grp0, *, use_pallas,
               interpret):
    st, iters, moved = _rd_core(
        busy0, mu, holders0, size0, cnt0, grp0,
        use_pallas=use_pallas, interpret=interpret,
    )
    return st.size, st.cnt, st.grp, st.holders[:, 0], st.overflow, iters, moved


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _rd_device_chain(busy0, mu, holders0, size0, cnt0, grp0, *,
                     use_pallas, interpret):
    """Sequential admission of B jobs in one scan, carrying busy levels.

    The RD twin of :func:`repro.core.wf_jax.water_fill_chain`: job ``i+1``
    sees ``b_m + ⌈load_m^i/μ_m^i⌉`` (eq. 2) exactly as if the burst were
    admitted one job at a time.  Padded jobs carry zero slots and commit
    nothing.  Outputs are per job, the loops' iteration and moved-class
    counts included.
    """
    m_servers = busy0.shape[0]

    def job_step(busy, inp):
        h0, s0, c0, g0, mu_j = inp
        st, iters, moved = _rd_core(
            busy, mu_j, h0, s0, c0, g0,
            use_pallas=use_pallas, interpret=interpret,
        )
        loads = (
            jnp.zeros(m_servers, jnp.int32)
            .at[st.holders[:, 0]]
            .add(st.size, mode="drop")
        )
        busy_next = busy + jnp.where(
            loads > 0, _ceil_div(loads, mu_j.astype(jnp.int32)), 0
        )
        return busy_next, (st.size, st.cnt, st.grp, st.holders[:, 0],
                           st.overflow, iters, moved)

    _, outs = jax.lax.scan(
        job_step,
        busy0.astype(jnp.int32),
        (holders0, size0, cnt0, grp0, mu),
    )
    return outs


def _dense_instance(
    problem: AssignmentProblem, c_cap: int, a_pad: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Initial slot arrays: one slot per task group, padded to (C, A)."""
    m = problem.n_servers
    holders = np.full((c_cap, a_pad), m, dtype=np.int32)
    size = np.zeros(c_cap, dtype=np.int32)
    cnt = np.zeros(c_cap, dtype=np.int32)
    grp = np.zeros(c_cap, dtype=np.int32)
    for k, g in enumerate(problem.groups):
        holders[k, : len(g.servers)] = g.servers
        size[k] = g.size
        cnt[k] = len(g.servers)
        grp[k] = k
    return holders, size, cnt, grp


def _decode(
    problem: AssignmentProblem,
    size: np.ndarray,
    cnt: np.ndarray,
    grp: np.ndarray,
    srv: np.ndarray,
) -> Assignment:
    act = np.flatnonzero(size > 0)
    if not (cnt[act] == 1).all():  # pragma: no cover - device invariant
        raise AssertionError("dedup must leave exactly one replica")
    dense = np.zeros((len(problem.groups), problem.n_servers), dtype=np.int64)
    np.add.at(dense, (grp[act], srv[act]), size[act])
    alloc: list[dict[int, int]] = [
        {int(m): int(row[m]) for m in np.flatnonzero(row)} for row in dense
    ]
    if int(size[act].sum()) != problem.n_tasks:  # pragma: no cover
        raise AssertionError("class bookkeeping lost tasks")
    result = Assignment(alloc=alloc, phi=0)
    result.phi = result.realized_phi(problem)
    result.validate(problem)
    return result


def _observe_loops(iters, moved) -> None:
    """One ``rd.iters`` and one ``rd.moved`` observation per job: the
    device loops' iterations and the classes their strips moved."""
    session = _obs_active()
    if session is not None:
        for n, k in zip(np.atleast_1d(iters), np.atleast_1d(moved)):
            session.metrics.observe("rd.iters", int(n))
            session.metrics.observe("rd.moved", int(k))


def _resolve_device(backend: str, c_cap: int, a_pad: int) -> tuple[bool, bool]:
    """(use_pallas, interpret) for a given slot geometry.

    Mirrors the waterlevel dispatcher: geometries past the kernel's
    single-block bounds fall back to jnp regardless of the request, and
    interpret mode engages automatically off-TPU.
    """
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"device RD backend must be jnp|pallas, got {backend!r}")
    use_pallas = backend == "pallas"
    if use_pallas:
        from repro.kernels.rd import rd_pallas_fits

        use_pallas = rd_pallas_fits(c_cap, 3 + a_pad // 2)
    interpret = jax.default_backend() != "tpu"
    return use_pallas, interpret


# ---------------------------------------------------------------------------
# kernelcheck geometry contract (verified by repro.analysis.kernelcheck).
#
# Admissible input envelope for the int32 range proofs: pre-burst busy
# times, per-job task totals and μ are bounded far above paper scale
# (Sec. V uses μ ≤ 4, thousands of tasks); within it every packed key,
# prefix sum and eq. 2 carry provably fits int32, and the sole-copy
# ``_BIG`` alt sentinel stays strictly above every real busy estimate.

RD_ENV_BUSY0_MAX = 1 << 20  # pre-burst busy time per server
RD_ENV_TASKS_MAX = 1 << 20  # tasks per job
RD_ENV_MU_MAX = 1 << 4  # per-server tasks/slot (μ)
# rows a strip's delta updates touch: a strip moves at most quota ≤ μ
# classes (a larger quota sets ``overflow`` and re-runs on the host)
_MOVERS = _next_pow2(RD_ENV_MU_MAX)
RD_ENV_CHAIN_JOBS_MAX = 64  # jobs per chained same-slot burst


@functools.lru_cache(maxsize=None)
def _rd_abstract_geometry(m: int, k: int, a: int, s: int) -> tuple[int, int]:
    """(c_cap, a_pad) for the representative instance of a lattice point,
    computed through the *real* sizing path (:func:`rd_slot_capacity`)."""
    a_eff = min(a, m)
    servers = tuple(range(a_eff))
    problem = AssignmentProblem(
        busy=np.zeros(m, np.int64),
        mu=np.ones(m, np.int64),
        groups=tuple(TaskGroup(s, servers) for _ in range(k)),
    )
    return rd_slot_capacity(problem), _next_pow2(max(2, a_eff))


def _rd_dispatch(geom: dict) -> str:
    if geom["requested"] == "host" or geom["m"] > RD_DEVICE_MAX_M:
        # explicit host request, or past the 15-bit packing ceiling: the
        # auto dispatcher (repro.core.rd.replica_deletion_auto) routes
        # these to host RD and replica_deletion_jax refuses them.
        return "host"
    c_cap, a_pad = _rd_abstract_geometry(
        geom["m"], geom["k"], geom["a"], geom["s"]
    )
    use_pallas, _ = _resolve_device(geom["requested"], c_cap, a_pad)
    return "pallas" if use_pallas else "jnp"


def _rd_range_claims(geom: dict, *, chain_jobs: int = 1) -> list[RangeClaim]:
    m = geom["m"]
    server_id = Interval(0, m)  # holder ids, pad id = M
    packed = (server_id << _PACK_BITS) | server_id
    tasks = Interval(0, RD_ENV_TASKS_MAX)
    busy0 = Interval(0, RD_ENV_BUSY0_MAX)
    # eq. 2 carry: each admitted job raises a server's busy estimate by
    # at most ⌈load/μ⌉ ≤ load ≤ its task total (members are homed at
    # exactly one primary holder, so per-server loads sum to ≤ tasks)
    busy_est = busy0 + Interval(0, chain_jobs) * tasks
    _, a_pad = _rd_abstract_geometry(geom["m"], geom["k"], geom["a"], geom["s"])
    return [
        RangeClaim(
            "holder id field (pad id = M)", server_id, bits=_PACK_BITS
        ),
        RangeClaim("packed setkey word ((id << 15) | id)", packed, bits=30),
        RangeClaim("per-server load scatter", tasks),
        RangeClaim(
            "count-bucket index (count · Mp + id)",
            Interval(0, (a_pad + 1) * _hist_stride(m)),
        ),
        RangeClaim("strip quota ((load-1) mod μ + 1)", Interval(1, RD_ENV_MU_MAX)),
        RangeClaim("eq. 2 busy estimate", busy_est),
        RangeClaim(
            "sole-copy alt sentinel headroom (_BIG − busy_est)",
            Interval.const(_BIG) - busy_est,
            positive=True,
        ),
    ]


def _rd_signature(geom: dict) -> tuple:
    c_cap, a_pad = _rd_abstract_geometry(
        geom["m"], geom["k"], geom["a"], geom["s"]
    )
    sig = ("rd-device", geom["m"], c_cap, a_pad)
    if "b" in geom:
        sig += (_next_pow2(geom["b"]),)
    return sig


def _rd_abstract(geom: dict):
    c_cap, a_pad = _rd_abstract_geometry(
        geom["m"], geom["k"], geom["a"], geom["s"]
    )
    m = geom["m"]
    i32 = jnp.int32
    sd = jax.ShapeDtypeStruct
    use_pallas = _rd_dispatch(geom) == "pallas"
    if "b" in geom:
        b_pad = _next_pow2(geom["b"])
        fn = functools.partial(
            _rd_device_chain, use_pallas=use_pallas, interpret=True
        )
        return fn, (
            sd((m,), i32),
            sd((b_pad, m), i32),
            sd((b_pad, c_cap, a_pad), i32),
            sd((b_pad, c_cap), i32),
            sd((b_pad, c_cap), i32),
            sd((b_pad, c_cap), i32),
        )
    fn = functools.partial(_rd_device, use_pallas=use_pallas, interpret=True)
    return fn, (
        sd((m,), i32),
        sd((m,), i32),
        sd((c_cap, a_pad), i32),
        sd((c_cap,), i32),
        sd((c_cap,), i32),
        sd((c_cap,), i32),
    )


@contract(
    "rd_jax.device",
    axes=(
        span(
            "m",
            2,
            RD_DEVICE_MAX_M,
            boundaries=(_MIN_LANES, RD_DEVICE_MAX_M),
            past=(RD_DEVICE_MAX_M + 1, 1 << 16),
        ),
        choice("k", 1, 4, 64, 256),
        choice("a", 2, 4, 8, 16),
        choice("s", 1, 32),
        choice("requested", "host", "jnp", "pallas"),
    ),
    backends=("host", "jnp", "pallas"),
    device_backends=("jnp", "pallas"),
    dispatch=_rd_dispatch,
    ranges=_rd_range_claims,
    signature=_rd_signature,
    max_signatures=256,  # m lattice points × pow2 (c_cap, a_pad) classes
    abstract=_rd_abstract,
    eval_points=2,  # tracing the deletion/dedup while_loops is costly
    notes="single-instance device RD; n_servers past RD_DEVICE_MAX_M "
    "must route to host (15-bit packed sort keys), slot-capacity "
    "overflow re-runs on host at runtime",
)
def replica_deletion_jax(
    problem: AssignmentProblem, seed: int = 0, *, backend: str = "jnp"
) -> Assignment:
    """Host-facing RD that runs the strip pipeline on device.

    Same assignment as :func:`repro.core.rd.replica_deletion` and the
    reference oracle (parity-tested); ``backend`` picks the strip
    engine (``jnp`` | ``pallas``).  An overflow (slot capacity, see
    :func:`rd_slot_capacity`, or a strip quota past ``_MOVERS``)
    transparently re-runs the instance on the host path.
    """
    del seed  # deterministic; retained for API compatibility
    if problem.n_servers > RD_DEVICE_MAX_M:
        raise ValueError(
            f"device RD supports at most {RD_DEVICE_MAX_M} servers "
            f"(15-bit packed sort keys), got {problem.n_servers} — use the "
            "host backend"
        )
    if problem.n_tasks == 0:
        result = Assignment(alloc=[], phi=0)
        result.phi = result.realized_phi(problem)
        return result
    c_cap = rd_slot_capacity(problem)
    a_pad = _next_pow2(
        max(2, max((len(g.servers) for g in problem.groups), default=1))
    )
    use_pallas, interpret = _resolve_device(backend, c_cap, a_pad)
    size_f, cnt_f, grp_f, srv_f, overflow, iters, moved = phased_call(
        "rd",
        "rd-device",
        (problem.n_servers, c_cap, a_pad),  # the kernelcheck key
        functools.partial(
            _rd_device, use_pallas=use_pallas, interpret=interpret
        ),
        lambda: (
            np.asarray(problem.busy, np.int32),
            np.asarray(problem.mu, np.int32),
            *_dense_instance(problem, c_cap, a_pad),
        ),
        downgrade=backend == "pallas" and not use_pallas,
        fallback=lambda outs: bool(outs[4]),
    )
    if overflow:  # a capacity forced smaller, or μ past the contract
        return replica_deletion(problem)
    _observe_loops(iters, moved)
    with _obs_span("rd.decode"):
        return _decode(problem, size_f, cnt_f, grp_f, srv_f)


@contract(
    "rd_jax.chain",
    axes=(
        span(
            "m",
            2,
            RD_DEVICE_MAX_M,
            boundaries=(RD_DEVICE_MAX_M,),
            past=(1 << 16,),
        ),
        choice("k", 1, 64),
        choice("a", 2, 16),
        choice("s", 1, 32),
        choice("b", 1, 2, 7, 32, RD_ENV_CHAIN_JOBS_MAX),
        choice("requested", "host", "jnp", "pallas"),
    ),
    backends=("host", "jnp", "pallas"),
    device_backends=("jnp", "pallas"),
    dispatch=_rd_dispatch,
    ranges=lambda geom: _rd_range_claims(geom, chain_jobs=geom["b"]),
    signature=_rd_signature,
    max_signatures=256,  # × pow2 burst-length classes
    abstract=_rd_abstract,
    eval_points=2,
    notes="chained same-slot RD burst (scan over jobs, eq. 2 committed "
    "between iterations); overflow of any job falls the whole burst "
    "back to the host commit walk",
)
def replica_deletion_jax_chain(
    problems: list[AssignmentProblem], *, backend: str = "jnp"
) -> list[Assignment]:
    """Admit a same-slot RD burst in one chained device dispatch.

    Every problem must share one cluster and carry the *same* pre-burst
    busy vector (eq. 2 is committed between jobs inside the scan) — the
    contract of :meth:`SchedulingPolicy.assign_batch`, identical to
    :func:`repro.core.wf_jax.water_filling_jax_chain`.  Assignments are
    bit-identical to sequential :func:`replica_deletion_jax` calls with
    busy re-read after each enqueue; any job overflowing the slot
    capacity falls the whole burst back to the host commit walk.
    """
    if not problems:
        return []
    m = problems[0].n_servers
    if any(p.n_servers != m for p in problems):
        raise ValueError("chained RD requires a single cluster size")
    if m > RD_DEVICE_MAX_M:
        raise ValueError(
            f"device RD supports at most {RD_DEVICE_MAX_M} servers "
            f"(15-bit packed sort keys), got {m} — use the host backend"
        )
    base = problems[0].busy
    if any(
        p.busy is not base and not np.array_equal(p.busy, base)
        for p in problems[1:]
    ):
        raise ValueError(
            "chained RD requires every problem to carry the same pre-burst "
            "busy vector (eq. 2 is committed inside the scan)"
        )
    c_cap = max(rd_slot_capacity(p) for p in problems)
    a_pad = _next_pow2(
        max(
            2,
            max(
                (len(g.servers) for p in problems for g in p.groups),
                default=1,
            ),
        )
    )
    use_pallas, interpret = _resolve_device(backend, c_cap, a_pad)
    b_pad = _next_pow2(len(problems))

    def build():
        holders = np.full((b_pad, c_cap, a_pad), m, dtype=np.int32)
        size = np.zeros((b_pad, c_cap), dtype=np.int32)
        cnt = np.zeros((b_pad, c_cap), dtype=np.int32)
        grp = np.zeros((b_pad, c_cap), dtype=np.int32)
        mu = np.ones((b_pad, m), dtype=np.int32)
        for i, p in enumerate(problems):
            holders[i], size[i], cnt[i], grp[i] = _dense_instance(
                p, c_cap, a_pad
            )
            mu[i] = p.mu
        return np.asarray(base, np.int32), mu, holders, size, cnt, grp

    size_f, cnt_f, grp_f, srv_f, overflow, iters, moved = phased_call(
        "rd",
        "rd-chain",
        (m, c_cap, a_pad, b_pad),  # the kernelcheck key
        functools.partial(
            _rd_device_chain, use_pallas=use_pallas, interpret=interpret
        ),
        build,
        downgrade=backend == "pallas" and not use_pallas,
        fallback=lambda outs: bool(outs[4].any()),
    )
    if overflow.any():
        # an overflowed job corrupts every later job's busy carry: discard
        # the device results and walk the burst on the host (identical
        # assignments — that is the parity guarantee)
        from .rd import host_commit_walk

        return host_commit_walk(problems)
    from .reorder import commit_busy

    _observe_loops(iters[: len(problems)], moved[: len(problems)])
    with _obs_span("rd.decode"):
        busy = np.asarray(base)
        out: list[Assignment] = []
        for i, p in enumerate(problems):
            prob_i = p if i == 0 else dataclasses.replace(p, busy=busy)
            a = _decode(prob_i, size_f[i], cnt_f[i], grp_f[i], srv_f[i])
            out.append(a)
            busy = commit_busy(busy, a, prob_i.mu, m)
    return out
