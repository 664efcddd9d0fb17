"""Fixtures for the benchmark's own tests: the harness on the CPU at a
tiny size, from a copy of the benchmark in a temporary directory."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench_tiny import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture
def chipless_harness(monkeypatch):
    """The harness with its look for a chip skipped: the CPU stands in.
    The warm-up's look for device programs stays on."""
    import jax

    from bench import harness

    monkeypatch.setattr(harness, "check_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    return harness


@pytest.fixture
def cpu_harness(chipless_harness, monkeypatch):
    """The chipless harness with the warm-up's look for device programs
    skipped too, so a policy the CPU runs on the host (RD under ``auto``)
    runs through."""
    monkeypatch.setattr(chipless_harness, "check_on_chip", lambda cell, dispatched, m: None)
    return chipless_harness
