"""Window arithmetic: the open loop times each job from its due time, so
a stall raises the tail of every job due behind it; the backlog counts
tasks over the whole span, so a stall lowers the rate; and the backlog
submits one job per submit/step_until pair."""

import time

from bench import gen, harness, spec
from bench_tiny import TINY_CONFIG, TINY_TRAFFIC


class FakePlane:
    """Records the calls; ``step_until`` takes ``base_s``, except the
    ``stall_at``-th call, which takes ``stall_s``."""

    def __init__(self, base_s=0.0005, stall_at=None, stall_s=0.0):
        self.calls = []
        self.base_s, self.stall_at, self.stall_s = base_s, stall_at, stall_s

    def submit(self, job):
        self.calls.append(("submit", job.job_id))

    def step_until(self, slot):
        self.calls.append(("step", slot))
        n = sum(1 for c in self.calls if c[0] == "step")
        time.sleep(self.stall_s if n == self.stall_at else self.base_s)


def _reader(name):
    return spec._load_reader(spec.BENCH_DIR / "end_to_end" / f"{name}.py")


def _ctx(rec, arrivals):
    return harness.Ctx(
        arrivals=arrivals, n_servers=64, setup_s=0.0,
        latencies_s=rec["latencies_s"], gen_lags_s=rec["gen_lags_s"],
        tasks_placed=rec["tasks"], span_s=rec["span_s"], window_compiles=0,
    )


def _drive(mix, plane, seconds):
    steps = gen.make_steps(TINY_CONFIG, TINY_TRAFFIC[mix], seconds, 21)
    jobs = [list(s.jobs) for s in steps]
    arrivals = TINY_TRAFFIC[mix]["arrivals"]
    return steps, harness.drive(plane, steps, jobs, arrivals, seconds, False)


def test_stall_raises_the_open_loop_tail():
    p95 = _reader("place_p95_ms")
    _, calm = _drive("tiny-single", FakePlane(), 1.0)
    _, stalled = _drive("tiny-single", FakePlane(stall_at=5, stall_s=0.4), 1.0)
    assert calm["unanswered"] == 0 and stalled["unanswered"] == 0
    assert p95(_ctx(calm, "open")) < 50.0
    assert p95(_ctx(stalled, "open")) > 150.0
    assert max(stalled["gen_lags_s"]) > 0.2  # jobs due in the stall went late


def test_open_loop_counts_the_jobs_due_in_the_window():
    steps, rec = _drive("tiny-burst", FakePlane(), 1.0)
    due = [s for s in steps if s.due_s < 1.0]
    assert rec["ran"] == due
    assert rec["attempted"] == len(rec["latencies_s"]) == sum(len(s.jobs) for s in due)
    assert len(rec["gen_lags_s"]) == len(due)


def test_stall_lowers_the_backlog_rate():
    rate = _reader("tasks_per_s")
    _, calm = _drive("tiny-backlog", FakePlane(base_s=0.004), 0.3)
    _, stalled = _drive("tiny-backlog", FakePlane(base_s=0.004, stall_at=3, stall_s=0.2), 0.3)
    assert rate(_ctx(stalled, "backlog")) < 0.7 * rate(_ctx(calm, "backlog"))
    assert stalled["span_s"] >= 0.3


def test_backlog_is_one_pair_per_job():
    plane = FakePlane()
    steps, rec = _drive("tiny-backlog", plane, 0.05)
    kinds = [c[0] for c in plane.calls]
    assert kinds == ["submit", "step"] * len(rec["ran"])
    ids = [c[1] for c in plane.calls if c[0] == "submit"]
    assert ids == [s.jobs[0].job_id for s in steps[: len(ids)]]
    assert rec["attempted"] == len(ids) and rec["unanswered"] == 0
