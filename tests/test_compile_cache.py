"""The persistent compilation cache helper (repro.launch.cache): where
``JAX_COMPILATION_CACHE_DIR`` is set it changes nothing, otherwise it
points JAX at the fixed ``<checkout>/.jax_cache``; and importing an entry
point never turns the cache on."""
import importlib
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.cache import enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHECKOUT))     # benchmarks/ and chip_smoke.py


@pytest.fixture
def saved_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    try:
        yield was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_env_var_set_changes_nothing(monkeypatch, tmp_path,
                                     saved_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == saved_cache_dir


def test_env_var_unset_uses_fixed_checkout_path(monkeypatch,
                                                saved_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path every time: no temp name, pid or clock in it
    assert enable_compile_cache() == want


@pytest.mark.parametrize("module", [
    "repro.launch.run", "repro.launch.train", "repro.launch.serve",
    "repro.launch.pool", "benchmarks.run", "chip_smoke"])
def test_entry_point_import_leaves_cache_off(module, saved_cache_dir):
    importlib.import_module(module)
    assert jax.config.jax_compilation_cache_dir == saved_cache_dir
