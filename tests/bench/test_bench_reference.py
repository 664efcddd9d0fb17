"""The benchmark's plain reference agrees with the program's own host
oracles on seeded instances (the reference imports nothing of the
program; these tests do, to hold the copy to the specification)."""

import numpy as np
import pytest

from bench import reference


@pytest.mark.parametrize("seed", range(12))
def test_wf_matches_host_wf(seed, random_problem):
    from repro.core import water_filling

    p = random_problem(np.random.default_rng(seed), n_servers=24, max_tasks=80)
    groups = tuple((g.size, g.servers) for g in p.groups)
    assert reference.wf_place(p.busy, p.mu, groups) == water_filling(p).alloc


@pytest.mark.parametrize("seed", range(12))
def test_rd_matches_rd_reference(seed, random_problem):
    from repro.core.rd_reference import replica_deletion_reference

    p = random_problem(np.random.default_rng(100 + seed), n_servers=24, max_tasks=80)
    groups = tuple((g.size, g.servers) for g in p.groups)
    assert reference.rd_place(p.busy, p.mu, groups) == replica_deletion_reference(p).alloc
