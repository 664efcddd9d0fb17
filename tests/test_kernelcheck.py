"""kernelcheck self-tests: registry, lattice, interval math, the repo
gate, and the negative fixture corpus.

The repo gate runs the real driver over the default contract modules
(every registered device entry point must verify), and each fixture
under ``tests/fixtures/kernelcheck`` must fail with exactly its
intended check — proving the checker actually fires on the bug classes
it claims to catch, not just passes on healthy contracts.
"""

import json
import pathlib

import pytest

from repro.analysis.contracts import (
    CONTRACTS,
    Axis,
    Interval,
    KernelContract,
    RangeClaim,
    lattice,
    register,
    span,
)
from repro.analysis.kernelcheck import DEFAULT_MODULES, main

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures" / "kernelcheck"


# ---- interval arithmetic ----------------------------------------------------


def test_interval_arithmetic_is_conservative():
    a = Interval(2, 5)
    b = Interval(-3, 4)
    assert a + b == Interval(-1, 9)
    assert a - b == Interval(-2, 8)
    assert a * b == Interval(-15, 20)
    assert -a == Interval(-5, -2)
    assert a + 1 == Interval(3, 6)
    assert Interval(0, 3) << 15 == Interval(0, 3 << 15)
    with pytest.raises(ValueError):
        Interval(3, 1)
    with pytest.raises(ValueError, match="negative"):
        _ = b << 2


def test_interval_or_is_a_packing_bound():
    # disjoint bit fields: the |-bound must contain the exact packing
    hi = Interval(0, (1 << 15) - 1) << 15
    lo = Interval(0, (1 << 15) - 1)
    packed = hi | lo
    assert packed.hi < (1 << 30)
    assert packed.lo == 0
    with pytest.raises(ValueError):
        _ = Interval(-1, 0) | Interval(0, 1)


def test_range_claim_checks():
    ok = RangeClaim("fits", Interval(0, 100))
    assert ok.check() is None
    assert "int32" in RangeClaim("over", Interval(0, 1 << 40)).check()
    assert "15-bit" in RangeClaim("wide", Interval(0, 1 << 15), bits=15).check()
    assert "bound" in RangeClaim("env", Interval(0, 11), bound=10).check()
    assert "positive" in RangeClaim("head", Interval(0, 5), positive=True).check()


# ---- registry + lattice -----------------------------------------------------


def _dummy_contract(name, entry="tests.dummy.fn"):
    return KernelContract(
        name=name,
        entry=entry,
        module="tests.dummy",
        axes=(Axis("m", (1, 2)),),
        backends=("jnp",),
        device_backends=("jnp",),
        dispatch=lambda geom: "jnp",
    )


def test_register_is_idempotent_but_rejects_name_collisions():
    register(_dummy_contract("test.dummy"))
    try:
        register(_dummy_contract("test.dummy"))  # same entry: idempotent
        with pytest.raises(ValueError, match="already registered"):
            register(_dummy_contract("test.dummy", entry="tests.other.fn"))
    finally:
        del CONTRACTS["test.dummy"]


def test_span_is_boundary_focused():
    ax = span("m", 1, 100, boundaries=(32,), past=(101, 200))
    assert ax.points == (1, 31, 32, 33, 100)
    assert ax.past == (101, 200)
    # boundary values outside [lo, hi] are clipped away
    assert span("m", 1, 10, boundaries=(10,)).points == (1, 9, 10)


def test_lattice_marks_past_points_inadmissible():
    c = KernelContract(
        name="test.lattice",
        entry="tests.dummy.fn",
        module="tests.dummy",
        axes=(Axis("m", (1, 2), past=(3,)), Axis("b", (10,))),
        backends=("jnp",),
        device_backends=("jnp",),
        dispatch=lambda geom: "jnp",
    )
    pts = list(lattice(c))
    assert ({"m": 1, "b": 10}, True) in pts
    assert ({"m": 2, "b": 10}, True) in pts
    assert ({"m": 3, "b": 10}, False) in pts
    assert len(pts) == 3


# ---- the repo gate ----------------------------------------------------------


def test_repo_contracts_all_verify(tmp_path):
    """The CI gate: every registered device entry point's contract holds
    over its boundary lattice."""
    report_path = tmp_path / "KERNELCHECK.json"
    rc = main(["--report", str(report_path), "--max-eval", "1"])
    assert rc == 0
    report = json.loads(report_path.read_text())
    names = {entry["contract"] for entry in report["contracts"]}
    assert {
        "waterlevel.kernel",
        "waterlevel.kernel-batch",
        "rd.strip",
        "rd_jax.device",
        "rd_jax.chain",
        "wf_jax.groups",
        "wf_jax.batch",
        "wf_jax.chain",
    } <= names
    assert report["total_violations"] == 0
    for entry in report["contracts"]:
        assert entry["lattice_points"] > 0
        assert "violated" not in entry["checks"].values()
        # every lattice point routed to a declared backend
        assert sum(entry["backends"].values()) == entry["lattice_points"]


def test_unknown_module_selection_exits_2(tmp_path):
    rc = main(
        ["--modules", "repro.analysis.contracts", "--report", str(tmp_path / "r.json")]
    )
    assert rc == 2


# ---- the negative fixture corpus --------------------------------------------


@pytest.mark.parametrize(
    "fixture, check",
    [
        ("vmem_blowup.py", "memory"),
        ("range_overflow.py", "range"),
        ("coverage_gap.py", "coverage"),
        ("recompile_blowup.py", "recompile"),
    ],
)
def test_fixture_violations_fire(tmp_path, fixture, check):
    report_path = tmp_path / "report.json"
    rc = main(["--modules", str(FIXTURES / fixture), "--report", str(report_path)])
    assert rc == 1
    report = json.loads(report_path.read_text())
    assert report["total_violations"] > 0
    checks_hit = {
        v["check"] for entry in report["contracts"] for v in entry["violations"]
    }
    assert check in checks_hit, (
        f"{fixture} was built to violate the {check} check, got {checks_hit}"
    )
    # fixture contracts are selected by module, so the repo's own
    # contracts must not appear in the fixture report
    assert all(e["contract"].startswith("fixture.") for e in report["contracts"])


def test_fixture_selection_does_not_leak_into_default_run():
    """Importing a fixture registers its contract globally, but the
    driver's module filter must keep it out of default-module runs."""
    import repro.analysis.kernelcheck as kc

    kc._import_module(str(FIXTURES / "coverage_gap.py"))
    assert any(name.startswith("fixture.") for name in CONTRACTS)
    default_modules = set(DEFAULT_MODULES)
    for name, c in CONTRACTS.items():
        if name.startswith("fixture."):
            assert c.module not in default_modules


# ---- cross-module constant sync ---------------------------------------------


def test_wf_jax_mirror_constants_match_kernels():
    """wf_jax keeps its kernels import lazy by design, so it mirrors the
    geometry constants as literals — they must stay in sync."""
    from repro.core import wf_jax
    from repro.kernels import waterlevel

    assert wf_jax._PALLAS_MAX_M == waterlevel.PALLAS_MAX_M
    assert wf_jax._WL_M_MAX == waterlevel.WL_M_MAX


def test_block_bytes_pad_to_vmem_tiles():
    from repro.analysis.kernelcheck import _block_bytes

    total, per = _block_bytes(
        {
            "row": ((1, 1000), 4),  # one int32 row: 8 sublanes × 1024 lanes
            "rows": ((3, 1, 128), 4),  # three separate rows
            "block": ((11, 256), 4),  # 11 sublanes round up to 16
            "vector": ((128,), 4),  # a 1-D block is one row
            "bf16": ((9, 128), 2),  # 16 sublanes per bf16 tile
        }
    )
    assert per == {
        "row": 8 * 1024 * 4,
        "rows": 3 * 8 * 128 * 4,
        "block": 16 * 256 * 4,
        "vector": 8 * 128 * 4,
        "bf16": 16 * 128 * 2,
    }
    assert total == sum(per.values())


def test_rd_strip_constants_match_rd_jax():
    """The strip kernel's sentinel and packing width are claimed in both
    contracts; the underlying constants must agree."""
    from repro.core import rd_jax
    from repro.kernels import rd as rd_kernel

    assert rd_kernel._BIG == rd_jax._BIG
    assert rd_jax._PACK_BITS == 15
