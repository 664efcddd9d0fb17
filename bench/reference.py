"""Plain reference of what the window drives: the control plane's FIFO
admission, eq. 2 accounting and slotted service, with the paper's WF
and RD placing each job.

It imports nothing of the program and reads nothing the program made:
it replays the window's own sequence of ``submit``/``step_until`` steps
from the generated jobs and returns every job's placement, the JCT of
each finished job and the tasks left of each unfinished one.

- Time is slotted.  Within one slot the order is: the arrival batch
  (jobs sorted by ``(slot, id)``, each placed against the busy times
  left by its predecessors), then one service tick, in which every
  server takes up to ``μ`` tasks from the segment at the head of its
  FIFO queue.  A tick runs at a slot where some queue is non-empty, and
  the next one follows at the next slot while any queue stays non-empty.
- A job submitted at a slot whose tick has already run arrives at that
  slot and is served from the next tick on.
- ``busy[m]`` (eq. 2) is ``Σ ⌈o/μ⌉`` over the segments queued on ``m``.
- A finished job's JCT is ``t + 1 − arrival`` for the slot ``t`` of its
  last task.
"""

from __future__ import annotations

import heapq
from collections import deque

import numpy as np

_BIG = 1 << 30


# ---- WF (paper Alg. 2, eqs. 7, 9, 10) -----------------------------------


def water_level(busy: np.ndarray, mu: np.ndarray, demand: int) -> int:
    """Minimal integer ``ξ`` with ``Σ max(ξ − b, 0)·μ ≥ demand``."""
    order = np.argsort(busy, kind="stable")
    b = busy[order]
    w = mu[order]
    cum_w = np.cumsum(w)
    cum_bw = np.cumsum(b * w)
    n = len(b)
    for i in range(n):
        xi = -(-(demand + int(cum_bw[i])) // int(cum_w[i]))
        if i + 1 >= n or xi <= b[i + 1]:
            return int(max(xi, b[i] + 1))
    raise AssertionError("unreachable")


def wf_place(busy: np.ndarray, mu: np.ndarray, groups) -> list[dict[int, int]]:
    """WF over one job's groups; ``busy``/``mu`` are full (M,) vectors."""
    busy = busy.astype(np.int64).copy()
    alloc = []
    for size, servers in groups:
        srv = np.asarray(servers, dtype=np.int64)
        b, w = busy[srv], mu[srv].astype(np.int64)
        xi = water_level(b, w, size)
        part = np.flatnonzero(b < xi)
        part = part[np.argsort(b[part], kind="stable")]
        per: dict[int, int] = {}
        left = size
        for idx, p in enumerate(part):
            take = left if idx == len(part) - 1 else min(int((xi - b[p]) * w[p]), left)
            if take > 0:
                per[int(srv[p])] = take
            left -= take
            if left == 0:
                break
        alloc.append(per)
        busy[srv] = np.maximum(busy[srv], xi)  # eq. 10
    return alloc


# ---- RD (paper Sec. III-C) ----------------------------------------------
#
# The per-task executable specification with deterministic tie-breaks:
# targets by largest estimated busy time, ties by largest initial busy
# time then smallest id; tasks by most copies, then cheapest surviving
# alternative, surviving-server set, group, task index; the dedup phase
# strips the busiest multi-copy holder (ties to the largest id).


class _RD:
    def __init__(self, busy0, mu, groups):
        n_srv = len(busy0)
        self.busy0 = busy0.astype(np.int64)
        self.mu = mu.astype(np.int64)
        self.task_group: list[int] = []
        self.present: list[set[int]] = []
        for k, (size, servers) in enumerate(groups):
            for _ in range(size):
                self.task_group.append(k)
                self.present.append(set(servers))
        n = len(self.task_group)
        self.count = np.array([len(p) for p in self.present], dtype=np.int64)
        self.on_server: list[set[int]] = [set() for _ in range(n_srv)]
        for t, p in enumerate(self.present):
            for m in p:
                self.on_server[m].add(t)
        self.load = np.array([len(s) for s in self.on_server], dtype=np.int64)
        self.busy_est = self.busy0 + -(-self.load // self.mu)
        self.multi_on = np.array(
            [sum(1 for t in s if self.count[t] > 1) for s in self.on_server],
            dtype=np.int64,
        )
        self.alt_best = [self._alt_pair(t) for t in range(n)]
        self.heaps: list[list] = [[] for _ in range(n_srv)]
        for m in range(n_srv):
            for t in self.on_server[m]:
                heapq.heappush(self.heaps[m], (self._key(t, m), t))

    def _alt_pair(self, t):
        m1, b1, b2 = -1, _BIG, _BIG
        for m in self.present[t]:
            b = int(self.busy0[m])
            if b < b1:
                m1, b1, b2 = m, b, b1
            elif b < b2:
                b2 = b
        return m1, b1, b2

    def _alt(self, t, m):
        m1, b1, b2 = self.alt_best[t]
        return b2 if m == m1 else b1

    def _key(self, t, m):
        return (
            -int(self.count[t]),
            self._alt(t, m),
            tuple(sorted(self.present[t])),
            self.task_group[t],
            t,
        )

    def _settle(self, m):
        h = self.heaps[m]
        while h:
            key, t = h[0]
            if m not in self.present[t]:
                heapq.heappop(h)
            elif key != self._key(t, m):
                heapq.heapreplace(h, (self._key(t, m), t))
            else:
                return

    def max_count(self, m) -> int:
        self._settle(m)
        return int(self.count[self.heaps[m][0][1]]) if self.heaps[m] else 0

    def delete(self, t, m):
        was_multi = self.count[t] > 1
        self.present[t].discard(m)
        self.on_server[m].discard(t)
        self.load[m] -= 1
        self.count[t] -= 1
        self.alt_best[t] = self._alt_pair(t)
        if was_multi:
            self.multi_on[m] -= 1
        if self.count[t] == 1:
            (last,) = self.present[t]
            self.multi_on[last] -= 1

    def strip(self, m) -> int:
        """Delete up to one busy slot's worth of multi-copy replicas
        (``((load − 1) mod μ) + 1``) from ``m``."""
        quota = ((int(self.load[m]) - 1) % int(self.mu[m])) + 1
        removed = 0
        while removed < quota and self.max_count(m) >= 2:
            _, t = heapq.heappop(self.heaps[m])
            self.delete(t, m)
            removed += 1
        if removed:
            self.busy_est[m] = self.busy0[m] + -(-int(self.load[m]) // int(self.mu[m]))
        return removed


def rd_place(busy: np.ndarray, mu: np.ndarray, groups) -> list[dict[int, int]]:
    """RD over one job's groups; ``busy``/``mu`` are full (M,) vectors.
    Only the job's eligible servers matter, so it runs on those,
    renumbered in id order (which keeps every id tie-break)."""
    srv = sorted({m for _, servers in groups for m in servers})
    local = {m: i for i, m in enumerate(srv)}
    st = _RD(
        busy[srv],
        mu[srv],
        [(size, [local[m] for m in servers]) for size, servers in groups],
    )
    done = False
    while not done:
        held = st.load > 0
        best = int(st.busy_est[held].max())
        tmask = held & (st.busy_est == best)
        if bool((tmask & (st.multi_on == 0)).any()):
            break
        heap = [(-st.max_count(int(m)), -int(st.busy0[m]), int(m)) for m in np.flatnonzero(tmask)]
        heapq.heapify(heap)
        while heap:
            negc, negb0, m = heapq.heappop(heap)
            if st.load[m] <= 0 or int(st.busy_est[m]) != best:
                continue
            c = st.max_count(m)
            if -negc != c:
                heapq.heappush(heap, (-c, negb0, m))
                continue
            if c <= 1 or st.strip(m) == 0:
                done = True
                break
            tmask = (st.load > 0) & (st.busy_est == best)
            if bool((tmask & (st.multi_on == 0)).any()):
                done = True
                break
    while (st.multi_on > 0).any():
        cand = np.flatnonzero(st.multi_on > 0)
        order = np.lexsort((st.busy0[cand], st.busy_est[cand]))
        if st.strip(int(cand[order[-1]])) == 0:
            raise AssertionError("a multi-copy holder yielded nothing")
    alloc: list[dict[int, int]] = [{} for _ in groups]
    for t, p in enumerate(st.present):
        (m,) = p
        k = st.task_group[t]
        alloc[k][srv[m]] = alloc[k].get(srv[m], 0) + 1
    return alloc


PLACERS = {"wf": wf_place, "rd": rd_place}


# ---- the control plane --------------------------------------------------


class Plane:
    """The reference control plane, driven step by step like the real one."""

    def __init__(self, n_servers: int, place):
        self.place = place
        self.busy = np.zeros(n_servers, dtype=np.int64)
        self.queues: list[deque] = [deque() for _ in range(n_servers)]
        self.nonempty: set[int] = set()
        self.now = 0
        self.service_at: int | None = None
        self.pending: dict[int, list] = {}  # slot -> jobs due to arrive
        self.mu: dict[int, np.ndarray] = {}
        self.arrival: dict[int, int] = {}
        self.remaining: dict[int, int] = {}
        self.placement: dict[int, tuple] = {}
        self.jct: dict[int, int] = {}

    def submit(self, job) -> None:
        t = max(job.slot, 0, self.now)
        self.pending.setdefault(t, []).append(job)

    def step_until(self, t: int) -> None:
        while True:
            ta = min(self.pending, default=None)
            ts = self.service_at
            if ta is not None and ta <= t and (ts is None or ta <= ts):
                self.now = max(self.now, ta)
                self._admit(ta, self.pending.pop(ta))
            elif ts is not None and ts <= t:
                self.now = max(self.now, ts)
                self._service(ts)
            else:
                break
        self.now = max(self.now, t)

    def _admit(self, t: int, jobs: list) -> None:
        for job in sorted(jobs, key=lambda j: (j.slot, j.job_id)):
            alloc = self.place(self.busy, job.mu, job.groups)
            self.mu[job.job_id] = job.mu
            self.arrival[job.job_id] = job.slot
            self.remaining[job.job_id] = job.n_tasks
            self.placement[job.job_id] = tuple(
                sorted((g, m, c) for g, per in enumerate(alloc) for m, c in per.items())
            )
            load: dict[int, int] = {}
            for per in alloc:
                for m, c in per.items():
                    load[m] = load.get(m, 0) + c
            for m, c in load.items():
                self.queues[m].append([job.job_id, c])
                self.nonempty.add(m)
                self.busy[m] += -(-c // int(job.mu[m]))
        if self.service_at is None:
            self.service_at = t

    def _service(self, t: int) -> None:
        done: dict[int, int] = {}
        for m in list(self.nonempty):
            seg = self.queues[m][0]
            mu = int(self.mu[seg[0]][m])
            before = -(-seg[1] // mu)
            take = min(mu, seg[1])
            seg[1] -= take
            self.busy[m] -= before - (-(-seg[1] // mu))
            done[seg[0]] = done.get(seg[0], 0) + take
            if seg[1] == 0:
                self.queues[m].popleft()
                if not self.queues[m]:
                    self.nonempty.discard(m)
        for j, n in done.items():
            self.remaining[j] -= n
            if self.remaining[j] <= 0:
                del self.remaining[j]
                self.jct[j] = t + 1 - self.arrival[j]
        self.service_at = t + 1 if self.nonempty else None


def replay(n_servers: int, policy: str, steps) -> Plane:
    """Run the reference over the window's executed steps."""
    plane = Plane(n_servers, PLACERS[policy])
    for step in steps:
        for job in step.jobs:
            plane.submit(job)
        plane.step_until(step.slot)
    return plane
