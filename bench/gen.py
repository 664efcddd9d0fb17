"""The one traffic generator: jobs and their submission schedule from a
configuration, a traffic mix and a seed.

Jobs follow the repo's ``bursty`` job model (a copy of
``repro.traces.bursty`` and ``repro.traces.placement``, kept here so the
yardstick cannot move with the program): heavy-tailed task counts,
shifted-Poisson groups per job with a Dirichlet split, ``p ~ U{lo..hi}``
consecutive eligible servers per group, ``μ ~ U{cap_lo..cap_hi}`` per
server, and Poisson bursts of same-slot arrivals whose slots put the
simulated cluster at the configured utilization.  The trace is cut to
the jobs a window can reach, with the per-job statistics of the full
cell trace (``total_tasks`` scales with the cut, and a window that
reaches past the trace's own length continues it at the same load).

The mix decides how the jobs meet the wall clock:

- ``open`` arrivals: each submission step has a due time; steps are
  the trace's bursts (``"submit": "burst"``) or single jobs
  (``"submit": "job"``) with exponential gaps at ``rate_jobs_per_s``;
- ``backlog`` arrivals: every job is due at the window's start and is
  submitted as a step of its own, in trace order.  With ``"sizes":
  "quantiles"`` its task counts are the mid-quantiles of the size law
  below ``max_job_tasks`` (:func:`quantile_sizes`).

The work is the same for every seed: jobs, bursts, slots and arrival
gaps come from the mix's ``work_seed``, and the run's seed deals the
bursts and the gaps in another order (each position keeps its slot).
Every job keeps a virtual slot, so the schedule depends on the seed
alone and never on timing.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class JobSpec:
    job_id: int
    slot: int
    groups: tuple[tuple[int, tuple[int, ...]], ...]  # (tasks, eligible servers)
    mu: np.ndarray  # (M,) tasks per slot on each server

    @property
    def n_tasks(self) -> int:
        return sum(size for size, _ in self.groups)


@dataclasses.dataclass(frozen=True)
class Step:
    """One ``submit``... ``step_until(slot)`` pair of the window."""

    due_s: float  # seconds after the window opens
    jobs: tuple[JobSpec, ...]
    slot: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def normalize_sizes(raw: np.ndarray, total_tasks: int) -> np.ndarray:
    """Integer sizes proportional to ``raw``, each ≥ 1, summing to
    ``total_tasks`` (``repro.traces.placement.normalize_sizes``)."""
    n = len(raw)
    if total_tasks < n:
        raise ValueError(f"cannot split {total_tasks} tasks into {n} jobs")
    sizes = np.maximum(1, np.round(raw / raw.sum() * total_tasks)).astype(int)
    sizes[np.argmax(sizes)] += total_tasks - int(sizes.sum())
    if sizes.min() < 1:
        sizes = np.maximum(sizes, 1)
        excess = int(sizes.sum()) - total_tasks
        for i in np.argsort(sizes, kind="stable")[::-1]:
            if excess <= 0:
                break
            take = min(excess, int(sizes[i]) - 1)
            sizes[i] -= take
            excess -= take
    return sizes


def group_split(n_tasks: int, mean_groups: float, rng) -> list[int]:
    """``repro.traces.placement.group_split``."""
    k = max(1, min(n_tasks, 1 + rng.poisson(mean_groups - 1.0)))
    if k == 1:
        return [n_tasks]
    w = rng.dirichlet(np.full(k, 0.8))
    sizes = np.maximum(1, np.round(w * n_tasks)).astype(int)
    sizes[np.argmax(sizes)] += n_tasks - int(sizes.sum())
    while sizes.min() < 1:
        i, j = np.argmin(sizes), np.argmax(sizes)
        sizes[j] += sizes[i] - 1
        sizes[i] = 1
    return [int(s) for s in sizes]


def group_servers(m: int, rng, lo: int, hi: int) -> tuple[int, ...]:
    """``p ~ U{lo..hi}`` consecutive servers (mod M) from an anchor.

    The repo's model draws the anchor as ``perm[zipf_rank]`` with a fresh
    random permutation per group, which makes the anchor uniform over
    the servers whatever the Zipf α; this draws that distribution
    directly (one integer instead of an M-wide permutation per group).
    """
    anchor = int(rng.integers(m))
    p = int(rng.integers(lo, hi + 1))
    return tuple(sorted({(anchor + i) % m for i in range(p)}))


def quantile_sizes(config: dict, n_jobs: int, max_tasks: int) -> np.ndarray:
    """The same ``n_jobs`` task counts for every seed: the mid-quantiles
    of the job model's size law (lognormal with the cell's mean job size)
    below ``max_tasks``, in ascending order."""
    jm = config["job_model"]
    sigma = float(jm["size_sigma"])
    scale = jm["trace_tasks"] / jm["trace_jobs"] / math.exp(sigma**2 / 2)
    law = statistics.NormalDist()
    top = law.cdf(math.log(max_tasks / scale) / sigma)
    q = (np.arange(n_jobs) + 0.5) / n_jobs * top
    z = np.array([law.inv_cdf(float(x)) for x in q])
    return np.maximum(1, np.round(scale * np.exp(sigma * z))).astype(int)


def make_jobs(
    config: dict, n_jobs: int, seed: int, sizes: np.ndarray | None = None
) -> list[list[JobSpec]]:
    """The first ``n_jobs`` jobs of the cell trace, as its bursts (the
    jobs of one burst share an arrival slot).  ``sizes``, when given,
    are the jobs' task counts (in an order drawn from the seed);
    otherwise they are drawn from the job model."""
    jm = config["job_model"]
    m = int(config["n_servers"])
    n_jobs = int(n_jobs)  # past the trace's own length, its statistics go on
    total = round(jm["trace_tasks"] * n_jobs / jm["trace_jobs"])
    rng = _rng(seed, 0)
    if sizes is None:
        sizes = normalize_sizes(
            rng.lognormal(0.0, jm["size_sigma"], size=n_jobs), total
        )
    else:
        sizes = rng.permutation(np.asarray(sizes)[:n_jobs])
    bursts: list[int] = []
    while sum(bursts) < n_jobs:
        b = 1 + int(rng.poisson(max(jm["mean_burst"] - 1.0, 0.0)))
        bursts.append(min(b, n_jobs - sum(bursts)))
    mean_mu = (jm["cap_lo"] + jm["cap_hi"]) / 2.0
    span = float((sizes / mean_mu).sum()) / (m * jm["utilization"])
    gaps = rng.exponential(1.0, size=len(bursts))
    epochs = np.floor(np.cumsum(gaps) / gaps.sum() * span).astype(int)
    out: list[list[JobSpec]] = []
    j = 0
    for epoch, b in zip(epochs, bursts):
        burst = []
        for _ in range(b):
            parts = group_split(int(sizes[j]), jm["mean_groups_per_job"], rng)
            groups = tuple(
                (s, group_servers(m, rng, jm["avail_lo"], jm["avail_hi"]))
                for s in parts
            )
            mu = rng.integers(jm["cap_lo"], jm["cap_hi"] + 1, size=m)
            burst.append(JobSpec(j, int(epoch), groups, mu))
            j += 1
        out.append(burst)
    return out


def jobs_needed(config: dict, traffic: dict, seconds: float) -> int:
    """Jobs the window can reach, with a margin."""
    if traffic["arrivals"] == "backlog":
        return int(traffic["backlog_jobs"])
    rate = float(traffic["rate_jobs_per_s"])
    return math.ceil(rate * seconds * 1.25) + 8 * math.ceil(
        config["job_model"]["mean_burst"]
    )


def _deal(bursts: list[list[JobSpec]], rng) -> list[list[JobSpec]]:
    """The same bursts in an order drawn from ``rng``: burst contents
    move between positions, each position keeps its arrival slot, and
    job ids follow the new order."""
    order = rng.permutation(len(bursts))
    out, j = [], 0
    for pos, src in enumerate(order):
        slot = bursts[pos][0].slot
        dealt = []
        for job in bursts[src]:
            dealt.append(JobSpec(j, slot, job.groups, job.mu))
            j += 1
        out.append(dealt)
    return out


def make_steps(config: dict, traffic: dict, seconds: float, seed: int) -> list[Step]:
    """The window's submission schedule, in trace order.

    With a ``work_seed`` in the mix, the work (every job, burst and
    arrival gap) is drawn from it, the same for every run, and ``seed``
    deals it in another order, so two seeds differ in order and never in
    the work offered; without one, ``seed`` draws the work itself."""
    n = jobs_needed(config, traffic, seconds)
    sizes = None
    if traffic.get("sizes") == "quantiles":
        sizes = quantile_sizes(config, n, int(traffic["max_job_tasks"]))
    if "work_seed" in traffic:
        work = int(traffic["work_seed"])
        bursts = _deal(make_jobs(config, n, work, sizes), _rng(seed, 2))
    else:  # the seed draws the work itself
        work = seed
        bursts = make_jobs(config, n, seed, sizes)
    if traffic["arrivals"] == "backlog":
        return [Step(0.0, (j,), j.slot) for b in bursts for j in b]
    if traffic["arrivals"] != "open":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    if traffic["submit"] == "burst":
        groups = [tuple(b) for b in bursts]
    elif traffic["submit"] == "job":
        groups = [(j,) for b in bursts for j in b]
    else:
        raise ValueError(f"unknown submit {traffic['submit']!r}")
    rate = float(traffic["rate_jobs_per_s"])
    # a Poisson process of steps whose mean job rate is `rate`; its gaps
    # are part of the work, dealt in the seed's order
    mean_jobs = sum(len(g) for g in groups) / len(groups)
    gaps = _rng(work, 1).exponential(mean_jobs / rate, size=len(groups))
    if work != seed:
        gaps = _rng(seed, 3).permutation(gaps)
    due = np.cumsum(gaps)
    return [
        Step(float(d), g, max(j.slot for j in g)) for d, g in zip(due, groups)
    ]
