"""Host ↔ jnp ↔ Pallas Replica-Deletion parity suite.

Three implementations of RD (paper Sec. III-C) must produce the *same
assignment* on every instance, with :mod:`repro.core.rd_reference` as the
executable specification:

- host class-compressed (``repro.core.rd``, the CPU default),
- the fixed-shape jnp program (``repro.core.rd_jax``, ``lax.while_loop``
  over vectorized strips),
- the fused Pallas strip kernel (``repro.kernels.rd``, interpret mode on
  CPU) — permutation-identical to the jnp strip by construction.

Deterministic twins (no hypothesis needed) pin the edge cases the device
formulation has to get right — sole-copy termination of the deletion
phase, the dedup phase's busiest-holder walk, duplicate groups, strips
that exhaust their quota mid-class — and the hypothesis suite sweeps
seeded instances.  Engine-level tests assert schedule equality of the
chained ``rd_batch`` burst dispatch against sequential admission, and of
the jnp backend against host across trace scenarios and orderings.

Pallas cases run in interpret mode here, so instances stay tiny; the
kernel's sort order is already pinned to the jnp path by the shared key
construction (see ``test_kernels.py`` for the kernel-level twin).
"""

import functools
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

try:  # property tests engage when hypothesis is available (CI installs it)
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic twins below still run
    HAVE_HYPOTHESIS = False

from repro.backend import set_backend
from repro.core import AssignmentProblem, TaskGroup, commit_busy
from repro.core.rd import (
    replica_deletion,
    replica_deletion_auto,
    replica_deletion_batch,
    resolve_rd_backend,
)
from repro.core.rd_reference import replica_deletion_reference
from repro.runtime import SchedulingEngine, make_policy
from repro.traces import generate

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
)


def _random_instance(rng, m=8, k_hi=4, size_hi=12, avail_hi=4, busy_hi=8):
    """Seeded instance generator shared by the twins and the properties.

    Small μ and tight busy ranges force dense tie-breaking (equal busy
    levels, equal replica counts, equal alternatives) — the regime where
    a wrong sort key shows up as a different assignment.
    """
    k = int(rng.integers(1, k_hi + 1))
    groups = tuple(
        TaskGroup(
            int(rng.integers(1, size_hi)),
            tuple(
                sorted(
                    rng.choice(
                        m, size=int(rng.integers(1, avail_hi + 1)), replace=False
                    ).tolist()
                )
            ),
        )
        for _ in range(k)
    )
    return AssignmentProblem(
        busy=rng.integers(0, busy_hi, m),
        mu=rng.integers(1, 4, m),
        groups=groups,
    )


def _assert_device_matches_reference(problem, backend, monkeypatch=None):
    from repro.core import rd_jax

    ref = replica_deletion_reference(problem)
    if monkeypatch is not None:
        # prove the device path actually ran: a silent slot-capacity
        # overflow would fall back to host RD and hide device bugs
        def _no_fallback(*a, **k):
            raise AssertionError("device RD fell back to host unexpectedly")

        monkeypatch.setattr(rd_jax, "replica_deletion", _no_fallback)
    dev = rd_jax.replica_deletion_jax(problem, backend=backend)
    assert dev.alloc == ref.alloc
    assert dev.phi == ref.phi


# ---- deterministic twins (run without hypothesis) ---------------------------


def test_jnp_matches_reference_on_seeded_instances(rng, monkeypatch):
    for _ in range(12):
        _assert_device_matches_reference(_random_instance(rng), "jnp", monkeypatch)


def test_pallas_matches_reference_on_seeded_instances(rng, monkeypatch):
    for _ in range(3):
        problem = _random_instance(rng, m=6, k_hi=3, size_hi=8, avail_hi=3)
        _assert_device_matches_reference(problem, "pallas", monkeypatch)


def test_sole_copy_termination(monkeypatch):
    """Deletion must stop when a max-level server holds only sole-copy
    tasks — even though other servers still hold deletable replicas."""
    problem = AssignmentProblem(
        busy=np.array([9, 0, 0, 0]),
        mu=np.array([1, 1, 1, 1]),
        groups=(
            TaskGroup(3, (0,)),  # sole-copy backlog pins server 0 at max
            TaskGroup(6, (1, 2, 3)),
        ),
    )
    _assert_device_matches_reference(problem, "jnp", monkeypatch)
    _assert_device_matches_reference(problem, "pallas", monkeypatch)


def test_dedup_phase_busiest_holder_order(monkeypatch):
    """Instances whose deletion phase exits immediately exercise the pure
    dedup walk (strip order (busy_est, busy0, id) descending)."""
    problem = AssignmentProblem(
        busy=np.array([5, 5, 5]),
        mu=np.array([2, 2, 2]),
        groups=(
            TaskGroup(1, (0,)),  # sole-copy on a max-busy server
            TaskGroup(4, (0, 1, 2)),
            TaskGroup(2, (1, 2)),
        ),
    )
    _assert_device_matches_reference(problem, "jnp", monkeypatch)
    _assert_device_matches_reference(problem, "pallas", monkeypatch)


def test_duplicate_groups_and_quota_boundary(monkeypatch):
    """Two groups with identical server sets are distinct classes (the
    fixed order breaks their ties by group id), and a large group forces
    strips that exhaust the quota mid-class."""
    problem = AssignmentProblem(
        busy=np.array([2, 2, 0, 0]),
        mu=np.array([3, 3, 3, 3]),
        groups=(
            TaskGroup(7, (0, 1)),
            TaskGroup(7, (0, 1)),
            TaskGroup(11, (0, 2, 3)),
        ),
    )
    _assert_device_matches_reference(problem, "jnp", monkeypatch)


def test_single_server_and_single_task(monkeypatch):
    for groups in (
        (TaskGroup(5, (0,)),),
        (TaskGroup(1, (0, 1)),),
    ):
        problem = AssignmentProblem(
            busy=np.array([1, 0]), mu=np.array([1, 2]), groups=groups
        )
        _assert_device_matches_reference(problem, "jnp", monkeypatch)


def test_empty_problem_matches_host():
    problem = AssignmentProblem(
        busy=np.array([3, 1]), mu=np.array([1, 1]), groups=()
    )
    from repro.core.rd_jax import replica_deletion_jax

    host = replica_deletion(problem)
    dev = replica_deletion_jax(problem)
    assert dev.alloc == host.alloc == []
    assert dev.phi == host.phi


def test_overflow_falls_back_to_host(monkeypatch):
    """A slot capacity too small for the instance must flag overflow and
    re-run on the host path — counted as a host fallback — not return
    garbage."""
    from repro import obs
    from repro.core import rd_jax

    rng = np.random.default_rng(3)
    problem = _random_instance(rng, m=10, k_hi=4, size_hi=20, avail_hi=6)
    # one slot per initial class and none spare: the first spin-off
    # has nowhere to go
    monkeypatch.setattr(rd_jax, "rd_slot_capacity", lambda p: len(p.groups))
    host_calls = []

    def _host(p):
        host_calls.append(p)
        return replica_deletion(p)

    monkeypatch.setattr(rd_jax, "replica_deletion", _host)
    with obs.observe(trace=False) as session:
        dev = rd_jax.replica_deletion_jax(problem)
    ref = replica_deletion_reference(problem)
    assert dev.alloc == ref.alloc
    assert host_calls == [problem]
    assert session.metrics.counters["device.rd-device.host_fallback"] == 1


def test_recycled_slots_fit_a_heavily_replicated_instance(monkeypatch):
    """Drained slots are recycled, so the capacity (live tasks plus one
    strip's spin-offs, 512 here) holds an instance where a fresh slot
    per move would take 1,784: the device path must finish on its own,
    without the host re-run."""
    from repro.core import rd_jax

    problem = AssignmentProblem(
        busy=np.arange(16, dtype=np.int64) % 3,
        mu=np.full(16, 3),
        groups=(
            TaskGroup(120, tuple(range(12))),
            TaskGroup(80, tuple(range(4, 12))),
            TaskGroup(60, tuple(range(4, 16))),
        ),
    )
    assert rd_jax.rd_slot_capacity(problem) == 512
    _assert_device_matches_reference(problem, "jnp", monkeypatch)


def test_backend_resolution_scopes():
    with set_backend(rd="jnp"):
        assert resolve_rd_backend() == "jnp"
    with set_backend(rd="host"):
        assert resolve_rd_backend() == "host"
        assert resolve_rd_backend("pallas") == "pallas"  # explicit wins
    with pytest.raises(ValueError, match="explicit"):
        resolve_rd_backend("nope")
    # CPU container: auto must stay on the host path (never regress the
    # class-compressed per-arrival overhead)
    import jax

    expected = "pallas" if jax.default_backend() == "tpu" else "host"
    with set_backend(rd="auto"):
        assert resolve_rd_backend() == expected
    assert resolve_rd_backend() == expected  # no scope at all


def test_engine_resolves_auto_rd_before_the_first_arrival():
    """``auto`` RD imports jax to ask for the platform.  The engine makes
    that call at construction, so the import lands before any arrival's
    timed overhead; host-only policies and an explicit ``host`` scope
    stay jax-free.  Runs in a fresh interpreter, where jax is not yet
    loaded."""
    script = textwrap.dedent(
        """
        import sys
        from repro.backend import set_backend
        from repro.runtime import SchedulingEngine, make_policy

        SchedulingEngine(16, "wf")
        with set_backend(rd="host"):
            SchedulingEngine(16, "rd")
        assert "jax" not in sys.modules, "host-only engines imported jax"
        SchedulingEngine(16, make_policy("rd_plus", "ocwf"))
        assert "jax" in sys.modules, "auto RD was not resolved at construction"
        print("ok")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "PYTHONPATH": _SRC, "JAX_PLATFORMS": "cpu"},  # reprolint: disable=R002 passthrough to a subprocess, no backend choice read
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip() == "ok"


def test_device_rejects_oversized_cluster():
    from repro.core.rd import RD_DEVICE_MAX_M
    from repro.core.rd_jax import replica_deletion_jax

    problem = AssignmentProblem(
        busy=np.zeros(RD_DEVICE_MAX_M + 1, dtype=np.int64),
        mu=np.ones(RD_DEVICE_MAX_M + 1, dtype=np.int64),
        groups=(TaskGroup(1, (0, 1)),),
    )
    with pytest.raises(ValueError, match="at most"):
        replica_deletion_jax(problem)
    # the auto dispatcher silently stays on host instead
    host = replica_deletion(problem)
    assert replica_deletion_auto(problem).alloc == host.alloc


# ---- the compact strip: movers, recycled slots, the μ bound -----------------


def _one_member_classes(n, mu):
    """``n`` one-member classes, each on server 0 and one other server,
    with server 0 the busiest: its first strip (load ``n``) moves one
    member from each of ``((n-1) mod μ)+1`` classes."""
    m = 20
    return AssignmentProblem(
        busy=np.r_[9, np.zeros(m - 1, np.int64)],
        mu=np.full(m, mu),
        groups=tuple(TaskGroup(1, (0, 1 + g % (m - 1))) for g in range(n)),
    )


# Its early spin-offs drain and their slots are recycled while the
# recycled slot's row still holds the previous class's spin-off pointers
# (eleven such strips): a run that followed them misplaces members.
_RECYCLED = AssignmentProblem(
    busy=np.array([6, 0, 4, 0, 2, 3, 3, 3]),
    mu=np.array([1, 1, 1, 1, 3, 2, 2, 1]),
    groups=(
        TaskGroup(8, (0, 1, 2)),
        TaskGroup(2, (3, 4, 5, 6)),
        TaskGroup(6, (1, 5, 6)),
        TaskGroup(5, (0, 2, 5, 7)),
    ),
)

_COMPACT_CASES = {
    "many_movers": lambda: _one_member_classes(32, mu=16),
    "recycled_slot": lambda: _RECYCLED,
}


def _device_outputs(problem, backend="jnp"):
    """The single-instance device program's raw outputs for ``problem``."""
    from repro.core import rd_jax

    c_cap = rd_jax.rd_slot_capacity(problem)
    a_pad = rd_jax._next_pow2(max(2, max(len(g.servers) for g in problem.groups)))
    use_pallas, interpret = rd_jax._resolve_device(backend, c_cap, a_pad)
    return rd_jax._rd_device(
        np.asarray(problem.busy, np.int32),
        np.asarray(problem.mu, np.int32),
        *rd_jax._dense_instance(problem, c_cap, a_pad),
        use_pallas=use_pallas,
        interpret=interpret,
    )


def _assert_chain_matches_sequential_host(problems, backend):
    from repro.core.rd_jax import replica_deletion_jax_chain

    chained = replica_deletion_jax_chain(problems, backend=backend)
    busy = problems[0].busy.copy()
    for prob, got in zip(problems, chained):
        seq = AssignmentProblem(busy=busy, mu=prob.mu, groups=prob.groups)
        host = replica_deletion(seq)
        assert got.alloc == host.alloc
        busy = commit_busy(busy, host, seq.mu, len(busy))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_strip_moves_fill_the_mover_rows(backend):
    """One strip of server 0 moves 16 one-member classes, every mover row
    the compact strip has, and leaves the running count buckets equal to
    a rebuild from the slots."""
    import jax.numpy as jnp

    from repro.core import rd_jax

    problem = _one_member_classes(32, mu=16)
    c_cap = rd_jax.rd_slot_capacity(problem)
    busy0 = jnp.asarray(problem.busy, jnp.int32)
    mu = jnp.asarray(problem.mu, jnp.int32)
    slots = [jnp.asarray(a) for a in rd_jax._dense_instance(problem, c_cap, 2)]
    st, removed, moved = rd_jax._strip(
        rd_jax._init_state(busy0, mu, *slots),
        jnp.int32(0),
        busy0,
        mu,
        use_pallas=backend == "pallas",
        interpret=True,
    )
    assert int(moved) == int(removed) == rd_jax._MOVERS == 16
    assert not bool(st.overflow)
    rebuilt = rd_jax._init_state(busy0, mu, st.holders, st.size, st.cnt, st.grp)
    np.testing.assert_array_equal(st.hist, rebuilt.hist)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(_COMPACT_CASES))
def test_compact_strip_matches_reference(case, backend, monkeypatch):
    """Single and chained, with the host re-run forbidden: a strip that
    fills the mover rows, and recycled slots whose stale pointers must
    be ignored."""
    problem = _COMPACT_CASES[case]()

    def _no_walk(*a, **k):
        raise AssertionError("chained device RD fell back to host")

    monkeypatch.setattr("repro.core.rd.host_commit_walk", _no_walk)
    _assert_device_matches_reference(problem, backend, monkeypatch)
    other = _random_instance(np.random.default_rng(5), m=problem.n_servers)
    second = AssignmentProblem(busy=problem.busy, mu=other.mu, groups=other.groups)
    _assert_chain_matches_sequential_host([problem, second], backend)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("chained", [False, True], ids=["single", "chained"])
def test_quota_past_the_mover_rows_reruns_on_host(chained, backend, monkeypatch):
    """μ = 20 (past the contract's 16) lets a strip move 20 classes: the
    program flags overflow and the adapter re-runs on the host, with the
    reference's assignment."""
    from repro import obs
    from repro.core import rd as rd_host
    from repro.core import rd_jax

    problem = _one_member_classes(40, mu=20)
    assert bool(_device_outputs(problem, backend)[4])  # overflow flagged
    calls = []
    if chained:
        walk = rd_host.host_commit_walk
        monkeypatch.setattr(
            rd_host, "host_commit_walk", lambda ps: calls.append(ps) or walk(ps)
        )
        with obs.observe(trace=False) as session:
            _assert_chain_matches_sequential_host([problem, problem], backend)
        assert session.metrics.counters["device.rd-chain.host_fallback"] == 1
    else:
        monkeypatch.setattr(
            rd_jax, "replica_deletion", lambda p: calls.append(p) or replica_deletion(p)
        )
        with obs.observe(trace=False) as session:
            dev = rd_jax.replica_deletion_jax(problem, backend=backend)
        assert dev.alloc == replica_deletion_reference(problem).alloc
        assert session.metrics.counters["device.rd-device.host_fallback"] == 1
    assert len(calls) == 1


# Loop iterations of the earlier implementation (full C × A slot-lane
# updates) on the same instances: the compact strip does the same work.
_PINNED_ITERS = {
    "many_movers": (lambda: _one_member_classes(32, mu=16), 3),
    "recycled_slot": (lambda: _RECYCLED, 44),
    "seeded_0": (lambda: _seeded(0), 48),
    "seeded_1": (lambda: _seeded(1), 50),
    "seeded_2": (lambda: _seeded(2), 72),
    "seeded_3": (lambda: _seeded(3), 70),
    "seeded_wide": (
        lambda: _random_instance(
            np.random.default_rng(60), m=40, k_hi=30, size_hi=60, avail_hi=12, busy_hi=20
        ),
        432,
    ),
}


def _seeded(seed):
    return _random_instance(
        np.random.default_rng(seed), m=9, k_hi=6, size_hi=20, avail_hi=5
    )


@pytest.mark.parametrize("case", list(_PINNED_ITERS))
def test_loop_iterations_are_pinned(case):
    make, iters = _PINNED_ITERS[case]
    problem = make()
    outs = _device_outputs(problem)
    assert not bool(outs[4])
    assert int(outs[5]) == iters
    assert 0 < int(outs[6]) <= int(problem.mu.max()) * iters


def _loop_index_ops(hlo: str) -> dict[str, list[tuple[str, int, tuple[int, ...]]]]:
    """Per outermost ``while`` body of an HLO module: each gather and
    scatter reached from it, as (op, elements read or written, batch shape
    of its indices)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if cur is None:
            head = re.match(r"^(?:ENTRY\s+)?([\w.\-]+)\s.*\{\s*$", line)
            if head:
                cur = head.group(1)
                comps[cur] = []
        elif line.startswith("}"):
            cur = None
        else:
            comps[cur].append(line.strip())
    shapes = {}
    for lines in comps.values():
        for line in lines:
            d = re.match(r"(?:ROOT )?([\w.\-]+) = [a-z]+\d*\[([\d,]*)\]", line)
            if d:
                shapes[d.group(1)] = tuple(int(x) for x in d.group(2).split(",") if x)

    def callees(line):
        out = re.findall(
            r"\b(?:to_apply|condition|body|true_computation"
            r"|false_computation|calls)=([\w.\-]+)",
            line,
        )
        for grp in re.findall(r"branch_computations=\{([^}]*)\}", line):
            out += [c.strip() for c in grp.split(",")]
        return out

    def reach(root):
        seen, stack = set(), [root]
        while stack:
            comp = stack.pop()
            if comp not in seen:
                seen.add(comp)
                stack += [c for line in comps[comp] for c in callees(line)]
        return seen

    bodies = {
        b: reach(b)
        for ls in comps.values()
        for ln in ls
        for b in re.findall(r"\bbody=([\w.\-]+)", ln)
    }
    found = {}
    for body, seen in bodies.items():
        if any(body in other for b, other in bodies.items() if b != body):
            continue  # a loop nested in another, e.g. the interpreted kernel's
        ops = []
        for comp in seen:
            for line in comps[comp]:
                op = re.match(
                    r"(?:ROOT )?([\w.\-]+) = \S+ (gather|scatter)\(([^)]*)\)", line
                )
                if op is None:
                    continue
                args = [shapes[a.strip()] for a in op.group(3).split(",")]
                idx = args[1]
                ivd = int(re.search(r"index_vector_dim=(\d+)", line).group(1))
                batch = idx[:ivd] + idx[ivd + 1 :]
                data = shapes[op.group(1)] if op.group(2) == "gather" else args[2]
                ops.append((op.group(2), int(np.prod(data)), batch))
        found[body] = ops
    return found


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_loop_bodies_index_no_slot_lanes(backend):
    """Lowered at M 1,000, C 256, A 16, the two ``while`` bodies hold no
    gather or scatter that reads or writes C × A (or C × A/2) slot lanes,
    and the only ops indexed by a C-vector are the jnp sort path's own
    two gathers (``neg_key[order]``, ``size[order]``).

    The C × A implementation lowered each strip with 2 gathers of C × A
    elements (the alt triple's busy lookup and the stale-pointer reset),
    3 scatters of C × A or C × A/2 (``holders``, ``setkey`` and ``dest``
    rows) and 22 ops indexed by a C-vector (the jnp path; 20 under the
    kernel), and each deletion iteration one C × A scatter-max (the peek).
    """
    import jax

    from repro.core.rd_jax import _rd_device

    m, c, a = 1000, 256, 16
    shapes = [(m,), (m,), (c, a), (c,), (c,), (c,)]
    program = functools.partial(
        _rd_device, use_pallas=backend == "pallas", interpret=True
    )
    hlo = (
        jax.jit(program)
        .lower(*(jax.ShapeDtypeStruct(s, np.int32) for s in shapes))
        .as_text(dialect="hlo")
    )
    bodies = _loop_index_ops(hlo)
    assert len(bodies) == 2  # the deletion and the dedup loop
    for ops in bodies.values():
        assert ops, "the strip's delta updates are inside the loop bodies"
        assert not [o for o in ops if o[1] in (c * a, c * a // 2)]
        by_c = [o for o in ops if o[2] == (c,)]
        assert by_c == ([("gather", c, (c,))] * 2 if backend == "jnp" else [])


# ---- batched burst admission ------------------------------------------------


def test_rd_batch_chain_matches_sequential_host(rng):
    """One chained device dispatch ≡ per-arrival host RD with eq. 2
    commits — the burst-admission contract of BATCH_ALGORITHMS["rd"]."""
    m = 10
    base_busy = rng.integers(0, 6, m)
    probs = [
        AssignmentProblem(
            busy=base_busy,
            mu=rng.integers(1, 4, m),
            groups=_random_instance(rng, m=m).groups,
        )
        for _ in range(3)
    ]
    with set_backend(rd="jnp"):
        chained = replica_deletion_batch(probs)
    busy = base_busy.copy()
    for prob, got in zip(probs, chained):
        seq = AssignmentProblem(busy=busy, mu=prob.mu, groups=prob.groups)
        host = replica_deletion(seq)
        got.validate(seq)
        assert got.alloc == host.alloc
        assert got.phi == host.phi
        busy = commit_busy(busy, host, seq.mu, m)


def test_rd_batch_host_walk_matches_sequential(rng):
    m = 10
    base_busy = rng.integers(0, 6, m)
    probs = [
        AssignmentProblem(
            busy=base_busy,
            mu=rng.integers(1, 4, m),
            groups=_random_instance(rng, m=m).groups,
        )
        for _ in range(3)
    ]
    with set_backend(rd="host"):
        walked = replica_deletion_batch(probs)
    busy = base_busy.copy()
    for prob, got in zip(probs, walked):
        seq = AssignmentProblem(busy=busy, mu=prob.mu, groups=prob.groups)
        host = replica_deletion(seq)
        assert got.alloc == host.alloc
        busy = commit_busy(busy, host, seq.mu, m)


def test_chain_rejects_mismatched_busy(monkeypatch):
    from repro.core.rd_jax import replica_deletion_jax_chain

    g = (TaskGroup(2, (0, 1)),)
    p1 = AssignmentProblem(busy=np.array([0, 0]), mu=np.array([1, 1]), groups=g)
    p2 = AssignmentProblem(busy=np.array([1, 0]), mu=np.array([1, 1]), groups=g)
    with pytest.raises(ValueError, match="same pre-burst busy"):
        replica_deletion_jax_chain([p1, p2])


# ---- engine-level schedule equality -----------------------------------------

_SMALL_TRACE = dict(n_jobs=8, total_tasks=260, n_servers=10)


def _run(policy_name, ordering="fifo", **engine_kw):
    jobs = generate("bursty", seed=7, **_SMALL_TRACE)
    engine = SchedulingEngine(
        _SMALL_TRACE["n_servers"],
        make_policy(policy_name, ordering),
        debug=True,
        **engine_kw,
    )
    return engine.run(jobs)


def test_engine_rd_jnp_batched_matches_host_sequential():
    host = _run("rd")
    with set_backend(rd="jnp"):
        batched = _run("rd")
        sequential = _run("rd", batch_arrivals=False)
    assert batched.jct == host.jct and batched.makespan == host.makespan
    assert sequential.jct == host.jct


@pytest.mark.parametrize("scenario", ["bursty", "pareto_diurnal"])
@pytest.mark.parametrize("ordering", ["fifo", "ocwf-acc"])
def test_engine_rd_backends_schedule_identical(scenario, ordering):
    """The acceptance matrix: host ≡ jnp engine schedules on bursty +
    pareto_diurnal under fifo + ocwf-acc (rd and rd_plus)."""
    jobs = generate(scenario, n_jobs=6, total_tasks=200, n_servers=8, seed=11)
    for assign in ("rd", "rd_plus"):
        host = SchedulingEngine(8, make_policy(assign, ordering)).run(jobs)
        with set_backend(rd="jnp"):
            dev = SchedulingEngine(8, make_policy(assign, ordering)).run(jobs)
        assert dev.jct == host.jct
        assert dev.makespan == host.makespan


def test_engine_rd_pallas_matches_host_tiny():
    """End-to-end Pallas (interpret) engine run on a tiny trace."""
    jobs = generate("bursty", n_jobs=4, total_tasks=60, n_servers=6, seed=5)
    host = SchedulingEngine(6, make_policy("rd")).run(jobs)
    with set_backend(rd="pallas"):
        dev = SchedulingEngine(6, make_policy("rd")).run(jobs)
    assert dev.jct == host.jct
    assert dev.makespan == host.makespan


# ---- hypothesis properties --------------------------------------------------

if HAVE_HYPOTHESIS:

    @given(
        seed=st.integers(0, 100_000),
        m=st.sampled_from([2, 5, 9]),
        avail_hi=st.integers(1, 5),
    )
    @settings(max_examples=15, deadline=None)
    def test_jnp_assignment_identity_property(seed, m, avail_hi):
        rng = np.random.default_rng(seed)
        problem = _random_instance(rng, m=m, avail_hi=min(avail_hi, m))
        ref = replica_deletion_reference(problem)
        from repro.core.rd_jax import replica_deletion_jax

        dev = replica_deletion_jax(problem)
        assert dev.alloc == ref.alloc
        assert dev.phi == ref.phi

    @given(seed=st.integers(0, 100_000))
    @settings(max_examples=4, deadline=None)
    def test_pallas_assignment_identity_property(seed):
        rng = np.random.default_rng(seed)
        problem = _random_instance(rng, m=5, k_hi=2, size_hi=6, avail_hi=3)
        ref = replica_deletion_reference(problem)
        from repro.core.rd_jax import replica_deletion_jax

        dev = replica_deletion_jax(problem, backend="pallas")  # reprolint: disable=R007 parity property pins the kernel strip explicitly
        assert dev.alloc == ref.alloc

    @given(seed=st.integers(0, 100_000), n_jobs=st.integers(1, 4))
    @settings(max_examples=6, deadline=None)
    def test_chain_property_matches_sequential(seed, n_jobs):
        rng = np.random.default_rng(seed)
        m = 8
        base_busy = rng.integers(0, 6, m)
        probs = [
            AssignmentProblem(
                busy=base_busy,
                mu=rng.integers(1, 4, m),
                groups=_random_instance(rng, m=m).groups,
            )
            for _ in range(n_jobs)
        ]
        from repro.core.rd_jax import replica_deletion_jax_chain

        chained = replica_deletion_jax_chain(probs)
        busy = base_busy.copy()
        for prob, got in zip(probs, chained):
            seq = AssignmentProblem(busy=busy, mu=prob.mu, groups=prob.groups)
            host = replica_deletion(seq)
            assert got.alloc == host.alloc
            busy = commit_busy(busy, host, seq.mu, m)
