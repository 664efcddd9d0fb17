"""Traffic arithmetic from the seed: due times, burst sizes, and one
submit/step_until pair per job in the backlog."""

import numpy as np
import pytest

from bench import gen

from bench_tiny import TINY_CONFIG, TINY_TRAFFIC


def _steps(mix, seed, seconds=5.0):
    return gen.make_steps(TINY_CONFIG, TINY_TRAFFIC[mix], seconds, seed)


def test_same_seed_same_schedule_large_seed():
    a, b = _steps("tiny-burst", 2**31 + 77), _steps("tiny-burst", 2**31 + 77)
    assert [s.due_s for s in a] == [s.due_s for s in b]
    assert [[j.groups for j in s.jobs] for s in a] == [[j.groups for j in s.jobs] for s in b]
    c = _steps("tiny-burst", 2**31 + 78)
    assert [s.due_s for s in a] != [s.due_s for s in c]


def test_seeds_deal_the_same_work_in_another_order():
    a, c = _steps("tiny-burst", 5), _steps("tiny-burst", 6)
    work = lambda steps: sorted(tuple(j.groups for j in s.jobs) for s in steps)  # noqa: E731
    assert work(a) == work(c)
    assert [s.jobs[0].groups for s in a] != [s.jobs[0].groups for s in c]
    gaps = lambda steps: sorted(np.diff([0.0] + [s.due_s for s in steps]).round(9))  # noqa: E731
    assert gaps(a) == gaps(c)
    # each position keeps its slot; ids follow the dealt order
    assert [s.slot for s in a] == [s.slot for s in c]
    assert [j.job_id for s in a for j in s.jobs] == list(range(sum(len(s.jobs) for s in a)))


def test_bursts_share_a_slot_and_have_mean_six_jobs():
    steps = _steps("tiny-burst", 3, seconds=20.0)
    sizes = [len(s.jobs) for s in steps[:-1]]  # the last burst is cut to size
    assert min(sizes) >= 1
    assert 5.0 < np.mean(sizes) < 7.0
    for s in steps:
        assert {j.slot for j in s.jobs} == {s.slot}
    slots = [s.slot for s in steps]
    assert slots == sorted(slots)


def test_open_loop_due_times_follow_the_rate():
    rate = TINY_TRAFFIC["tiny-single"]["rate_jobs_per_s"]
    steps = _steps("tiny-single", 11, seconds=30.0)
    due = np.array([s.due_s for s in steps])
    assert all(len(s.jobs) == 1 for s in steps)
    assert np.all(np.diff(due) > 0)
    in_window = int((due < 30.0).sum())
    assert abs(in_window - rate * 30.0) < 4 * np.sqrt(rate * 30.0)
    assert due[-1] >= 30.0  # the schedule outlasts the window


def test_backlog_is_one_step_per_job_in_trace_order():
    steps = _steps("tiny-backlog", 5)
    assert len(steps) == TINY_TRAFFIC["tiny-backlog"]["backlog_jobs"]
    assert all(s.due_s == 0.0 and len(s.jobs) == 1 for s in steps)
    assert [s.jobs[0].job_id for s in steps] == list(range(len(steps)))
    assert all(s.slot == s.jobs[0].slot for s in steps)


def test_job_model_keeps_the_cell_statistics():
    bursts = gen.make_jobs(TINY_CONFIG, 1000, 9)
    jobs = [j for b in bursts for j in b]
    jm = TINY_CONFIG["job_model"]
    assert sum(j.n_tasks for j in jobs) == round(jm["trace_tasks"] * 1000 / jm["trace_jobs"])
    widths = [len(srv) for j in jobs for _, srv in j.groups]
    assert min(widths) >= jm["avail_lo"] and max(widths) <= jm["avail_hi"]
    big = [len(j.groups) for j in jobs if j.n_tasks >= 20]  # small jobs cap k
    assert 5.0 < np.mean(big) < 6.0
    mus = np.concatenate([j.mu for j in jobs[:10]])
    assert mus.min() >= jm["cap_lo"] and mus.max() <= jm["cap_hi"]


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        gen.make_steps(
            TINY_CONFIG, {"arrivals": "closed", "rate_jobs_per_s": 1.0, "work_seed": 0}, 1.0, 0
        )


def test_quantile_backlog_is_the_same_work_for_every_seed():
    mix = dict(TINY_TRAFFIC["tiny-backlog"], sizes="quantiles", max_job_tasks=200)
    a = gen.make_steps(TINY_CONFIG, mix, 1.0, 1)
    b = gen.make_steps(TINY_CONFIG, mix, 1.0, 2**33)
    sizes_a = [s.jobs[0].n_tasks for s in a]
    sizes_b = [s.jobs[0].n_tasks for s in b]
    assert sorted(sizes_a) == sorted(sizes_b) and sizes_a != sizes_b
    assert max(sizes_a) <= 200
    want = gen.quantile_sizes(TINY_CONFIG, mix["backlog_jobs"], 200)
    assert sorted(sizes_a) == sorted(want.tolist())
    assert list(want) == sorted(want)  # mid-quantiles, ascending


def test_without_a_work_seed_the_seed_draws_the_work():
    mix = {k: v for k, v in TINY_TRAFFIC["tiny-burst"].items() if k != "work_seed"}
    a = gen.make_steps(TINY_CONFIG, mix, 2.0, 5)
    b = gen.make_steps(TINY_CONFIG, mix, 2.0, 6)
    work = lambda steps: sorted(tuple(j.groups for j in s.jobs) for s in steps)  # noqa: E731
    assert work(a) != work(b)
    assert [s.due_s for s in a] == [s.due_s for s in gen.make_steps(TINY_CONFIG, mix, 2.0, 5)]
