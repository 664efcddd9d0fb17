"""Overload-hardened control plane: admission control, budgeted
speculation, cost-based stealing, and correlated-fault survival.

Covers the resilience ladder end to end: rack failures with
retry-with-backoff complete strictly more jobs than without (the
acceptance drill), passive :class:`ResilienceConfig` preserves the
slot/event schedule equivalence and the obs on ≡ off contract, admission
keeps the event heap bounded at ρ > 1 while ``SimResult`` statistics
stay over completed jobs only, and the cancellation edge cases (clone
target faults, steals racing rack failures, retry exhaustion) run under
``debug=True`` invariant checking.
"""

import math

import numpy as np
import pytest

from repro import obs
from repro.core import Job, TaskGroup
from repro.runtime import (
    ControlPlane,
    RackEvent,
    ResilienceConfig,
    ResilienceState,
    SchedulingEngine,
    SimResult,
    ServerEvent,
    make_policy,
)
from repro.traces import (
    generate,
    overload_client,
    rack_failure_timeline,
    saturation_qps,
)


def _n_servers(jobs):
    return max(s for j in jobs for g in j.groups for s in g.servers) + 1


def _check_invariant(cluster, slot):
    cluster.assert_invariant()


RACK = (0, 1, 2, 3)


def _rack_trace():
    """Three jobs whose every replica lives on the rack, two outside."""
    mu = np.full(6, 2, np.int64)
    jobs = [
        Job(job_id=j, arrival=j, groups=(TaskGroup(60, RACK),), mu=mu)
        for j in range(3)
    ]
    jobs += [
        Job(job_id=3 + j, arrival=j, groups=(TaskGroup(10, (4, 5)),), mu=mu)
        for j in range(2)
    ]
    return jobs


# ---- correlated faults + retry-with-backoff (the acceptance drill) ---------


def test_rack_failure_with_retry_fails_strictly_fewer_jobs():
    jobs = _rack_trace()
    events = rack_failure_timeline(RACK, fail_at=4, recover_at=30)
    base = SchedulingEngine(
        6, make_policy("wf"), events=events, step_mode="event", debug=True
    ).run(jobs)
    retry = SchedulingEngine(
        6,
        make_policy("wf"),
        events=events,
        step_mode="event",
        resilience=ResilienceConfig(retry=True),
        debug=True,
    ).run(jobs)
    # without retry, losing the last replica is fatal
    assert sorted(base.failed_jobs) == [0, 1, 2]
    # with retry, the recovered rack serves every parked job
    assert retry.failed_jobs == []
    assert len(retry.failed_jobs) < len(base.failed_jobs)
    assert set(retry.jct) == {0, 1, 2, 3, 4}
    assert retry.retries > 0


def test_retry_exhaustion_fails_the_job_after_the_limit():
    jobs = _rack_trace()
    events = rack_failure_timeline(RACK, fail_at=4)  # never recovers
    res = SchedulingEngine(
        6,
        make_policy("wf"),
        events=events,
        step_mode="event",
        resilience=ResilienceConfig(retry=True),
        debug=True,
    ).run(jobs)
    assert sorted(res.failed_jobs) == [0, 1, 2]
    # each rack job burned the full retry budget before failing
    limit = ResilienceConfig().retry_limit
    assert res.retries == 3 * limit
    assert set(res.jct) == {3, 4}  # the off-rack jobs were untouched


def test_rack_event_validation():
    with pytest.raises(ValueError, match="non-empty"):
        RackEvent(0, "fail", ())
    with pytest.raises(ValueError, match="kind"):
        RackEvent(0, "melt", (0,))
    assert RackEvent(0, "fail", (3, 1, 1)).servers == (1, 3)
    with pytest.raises(ValueError, match="after"):
        rack_failure_timeline((0, 1), fail_at=5, recover_at=5)


# ---- equivalence + obs contracts stay intact --------------------------------


def test_passive_resilience_config_keeps_slot_event_equivalence():
    jobs = generate("bursty", n_jobs=25, seed=11)
    m = _n_servers(jobs)
    events = rack_failure_timeline((0, 1), fail_at=12, recover_at=40)
    cfg = ResilienceConfig()  # nothing gated on: schedules must not move
    slot = SchedulingEngine(
        m, make_policy("wf"), events=events, resilience=cfg
    ).run(jobs)
    event = SchedulingEngine(
        m,
        make_policy("wf"),
        events=events,
        step_mode="event",
        resilience=cfg,
        debug=True,
        on_slot=_check_invariant,
    ).run(jobs)
    assert event.jct == slot.jct
    assert event.makespan == slot.makespan
    assert event.failed_jobs == slot.failed_jobs
    assert event.reassignments == slot.reassignments


def test_admission_and_retry_require_event_mode():
    with pytest.raises(ValueError, match="event"):
        SchedulingEngine(
            4, resilience=ResilienceConfig(admission=True)
        )
    with pytest.raises(ValueError, match="event"):
        SchedulingEngine(4, resilience=ResilienceConfig(retry=True))


def _staggered_flood(n=20):
    mu = np.asarray([1], np.int64)
    return [
        Job(job_id=j, arrival=j, groups=(TaskGroup(10, (0,)),), mu=mu)
        for j in range(n)
    ]


def _tight_admission():
    return ResilienceConfig(
        admission=True,
        lag_defer_budget=15,
        lag_shed_budget=30,
        defer_queue_cap=4,
    )


def test_admission_schedule_is_obs_invariant():
    jobs = _staggered_flood()
    kw = dict(step_mode="event", resilience=_tight_admission())
    plain = SchedulingEngine(1, make_policy("wf"), **kw).run(jobs)
    with obs.observe() as session:
        observed = SchedulingEngine(1, make_policy("wf"), **kw).run(jobs)
    assert observed.jct == plain.jct
    assert observed.shed_jobs == plain.shed_jobs
    assert observed.deferred_peak == plain.deferred_peak
    assert observed.retries == plain.retries
    # the metrics-side hooks did fire under observation
    assert session.metrics.counter("jobs.shed") == len(plain.shed_jobs)
    assert session.metrics.counter("jobs.deferred") > 0


# ---- admission control / load shedding --------------------------------------


def test_admission_defers_then_sheds_and_stats_exclude_shed():
    jobs = _staggered_flood()
    res = SchedulingEngine(
        1,
        make_policy("wf"),
        step_mode="event",
        resilience=_tight_admission(),
        debug=True,
    ).run(jobs)
    assert res.n_shed > 0
    assert res.deferred_peak > 0
    # jobs partition cleanly: completed + shed, nothing failed or lost
    assert res.failed_jobs == []
    assert len(res.jct) + res.n_shed == len(jobs)
    assert not set(res.jct) & set(res.shed_jobs)
    # shed records carry the would-be arrival slot
    assert all(res.shed_jobs[j] == jobs[j].arrival for j in res.shed_jobs)
    # JCT statistics are over completed jobs only
    assert res.mean_jct == float(np.mean(list(res.jct.values())))


def test_simresult_stats_well_typed_when_every_job_is_shed():
    res = SimResult(
        jct={},
        overhead_s=[],
        makespan=7,
        failed_jobs=[],
        shed_jobs={0: 0, 1: 3},
    )
    assert res.n_shed == len(res.shed_jobs)
    assert math.isnan(res.mean_jct)
    assert math.isnan(res.jct_percentile(99))
    values, cdf = res.jct_cdf()
    assert values.dtype == np.int64 and values.size == 0
    assert cdf.dtype == np.float64 and cdf.size == 0


def test_overload_heap_stays_bounded_at_rho_1_5():
    base = generate("bursty", n_jobs=40, seed=1)
    m = _n_servers(base)
    jobs = overload_client(base, rho=1.5, n_servers=m)
    res = SchedulingEngine(
        m,
        make_policy("wf"),
        step_mode="event",
        resilience=ResilienceConfig(
            admission=True,
            lag_defer_budget=4,
            lag_shed_budget=12,
            defer_queue_cap=8,
        ),
    ).run(jobs)
    # every pushed occurrence is accounted: arrivals + a small constant
    # of self-scheduled service/heartbeat entries — never unbounded
    assert res.heap_peak <= len(jobs) + 16
    assert len(res.jct) + res.n_shed + len(res.failed_jobs) == len(jobs)


def test_overload_client_and_saturation_qps():
    base = generate("bursty", n_jobs=30, seed=2)
    m = _n_servers(base)
    assert saturation_qps(base, m) > 0
    slow = overload_client(base, rho=0.5, n_servers=m)
    fast = overload_client(base, rho=2.0, n_servers=m)
    assert len(slow) == len(fast) == len(base)
    # higher utilisation compresses the arrival span
    assert max(j.arrival for j in fast) < max(j.arrival for j in slow)
    with pytest.raises(ValueError, match="rho"):
        overload_client(base, rho=0.0, n_servers=m)


# ---- cost-based stealing ----------------------------------------------------


def _straggler_trace():
    jobs = generate("bursty", n_jobs=40, seed=5)
    m = _n_servers(jobs)
    events = tuple(
        ServerEvent(s, "slowdown", (s // 20) % m, factor=6.0)
        for s in range(5, 300, 20)
    )
    return jobs, m, events


def test_min_gain_threshold_blocks_worthless_steals():
    jobs, m, events = _straggler_trace()
    kw = dict(events=events, step_mode="event", stealing=True, debug=True)
    active = SchedulingEngine(m, make_policy("wf"), **kw).run(jobs)
    blocked = SchedulingEngine(
        m,
        make_policy("wf"),
        resilience=ResilienceConfig(steal_min_gain=10**6),
        **kw,
    ).run(jobs)
    assert active.steals > 0
    assert blocked.steals == 0
    # with or without stealing, all work completes
    assert len(blocked.jct) == len(jobs)


def test_steal_backoff_grows_exponentially_and_resets_on_win():
    st = ResilienceState(ResilienceConfig(), n_servers=4)
    base = ResilienceConfig().steal_backoff_base
    cap = ResilienceConfig().steal_backoff_max
    assert st.steal_ready(0, 0)
    waits = []
    for _ in range(7):
        st.steal_missed(0, 0)
        waits.append(int(st.steal_wait[0]))
    assert waits == [min(base << i, cap) for i in range(7)]
    assert not st.steal_ready(0, waits[-1] - 1)
    assert st.steal_ready(0, waits[-1])
    st.steal_won(0)
    assert st.steal_ready(0, 0)  # a win clears the backoff clock
    assert int(st.metrics.counter("steal.rejected")) == 7


# ---- budgeted speculation ---------------------------------------------------


def test_spec_budget_adapts_within_bounds():
    cfg = ResilienceConfig(spec_adapt_every=10, spec_adapt_samples=4)
    st = ResilienceState(cfg, n_servers=2)
    start = st.spec_budget
    # a winning streak grows the budget one step per adaptation window
    for _ in range(6):
        st.record_spec_outcome("spec.won_clone")
    st.ticks = cfg.spec_adapt_every
    assert st.adapted_spec_budget() == start + 1
    # a losing streak shrinks it, never below the floor
    for round_ in range(2, 40):
        for _ in range(6):
            st.record_spec_outcome("spec.won_original")
        st.ticks = round_ * cfg.spec_adapt_every
        st.adapted_spec_budget()
    assert st.spec_budget == cfg.spec_budget_min
    # and growth saturates at the ceiling
    for round_ in range(40, 120):
        for _ in range(6):
            st.record_spec_outcome("spec.won_clone")
        st.ticks = round_ * cfg.spec_adapt_every
        st.adapted_spec_budget()
    assert st.spec_budget == cfg.spec_budget_max


def test_speculation_respects_pair_budget_and_job_quota():
    jobs, m, events = _straggler_trace()
    plane = ControlPlane(
        m,
        policy="wf",
        events=events,
        speculation=True,
        resilience=ResilienceConfig(spec_budget=2, spec_job_quota=1),
        debug=True,
    )
    peak_pairs = 0
    orig = plane._spec_scan

    def watched():
        nonlocal peak_pairs
        orig()
        peak_pairs = max(peak_pairs, len(plane._pairs))

    plane._spec_scan = watched
    plane.submit_many(jobs)
    res = plane.drain()
    st = plane._res
    assert res.speculations > 0
    assert peak_pairs <= 2
    assert all(n <= 1 for n in st.spec_launched.values())


# ---- cancellation edge cases under sanitizers -------------------------------


def test_spec_pair_survives_clone_side_faults():
    """Server failures land between spec launches: every live pair is
    folded back before the fault machinery walks the queues, so no
    shadow segment ever leaks into stranding/reassignment."""
    jobs, m, events = _straggler_trace()
    fault = tuple(
        ServerEvent(s, "fail", (s // 7) % m) for s in range(20, 90, 7)
    ) + tuple(
        ServerEvent(s + 3, "recover", (s // 7) % m) for s in range(20, 90, 7)
    )
    res = SchedulingEngine(
        m,
        make_policy("wf"),
        events=tuple(sorted(events + fault, key=lambda e: e.slot)),
        step_mode="event",
        speculation=True,
        debug=True,
        on_slot=_check_invariant,
    ).run(jobs)
    # every job is accounted for: completed or failed, none lost
    assert len(res.jct) + len(res.failed_jobs) == len(jobs)


def test_steal_racing_rack_failure_conserves_jobs():
    jobs, m, events = _straggler_trace()
    rack = rack_failure_timeline(
        tuple(range(m // 2)), fail_at=25, recover_at=60
    )
    res = SchedulingEngine(
        m,
        make_policy("wf"),
        events=tuple(sorted(events + rack, key=lambda e: e.slot)),
        step_mode="event",
        stealing=True,
        resilience=ResilienceConfig(retry=True),
        debug=True,
        on_slot=_check_invariant,
    ).run(jobs)
    assert len(res.jct) + len(res.failed_jobs) == len(jobs)

