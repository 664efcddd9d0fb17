"""Where the persistent compilation cache goes: the configured directory
when there is one (``JAX_COMPILATION_CACHE_DIR``), else a fixed path
inside the checkout."""

import pathlib

import jax
import pytest

from repro.launch.cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_config():
    """Restore ``jax_compilation_cache_dir`` after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_configured_cache_dir_is_kept(cache_dir_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_unset_cache_dir_goes_to_the_checkout(cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    path = enable_compile_cache()
    assert path == CHECKOUT_CACHE_DIR == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: it is part of the cache key
    assert enable_compile_cache() == path
