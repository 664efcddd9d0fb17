"""Decide ``correct``: the window's placements and schedule against the
plain reference (:mod:`reference`), replayed over the same steps.

Every number compared is a count of jobs and its limit is 0: the
reference and the program must agree exactly.

- ``placement_mismatch``: jobs whose tasks per (group, server) differ
  from the reference's, or that one side placed and the other did not.
- ``schedule_mismatch``: jobs whose JCT differs, that finished on one
  side only, or whose tasks left at the last step differ.
- ``unanswered``: jobs due in the window that were never placed.
"""

from __future__ import annotations

LIMITS = {"placement_mismatch": 0, "schedule_mismatch": 0, "unanswered": 0}


def compare(program: dict, reference: dict, unanswered: int) -> dict[str, dict]:
    """``program`` and ``reference`` hold ``placement`` (job -> sorted
    ``(group, server, tasks)`` tuple), ``jct`` and ``remaining``."""
    pp, rp = program["placement"], reference["placement"]
    placement = sum(1 for j in set(pp) | set(rp) if pp.get(j) != rp.get(j))
    sched = 0
    for j in set(pp) | set(rp):
        a = (program["jct"].get(j), program["remaining"].get(j))
        b = (reference["jct"].get(j), reference["remaining"].get(j))
        sched += a != b
    values = {
        "placement_mismatch": placement,
        "schedule_mismatch": sched,
        "unanswered": unanswered,
    }
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def passed(checks: dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
