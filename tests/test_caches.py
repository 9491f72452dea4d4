"""On-disk caches stay inside the checkout (repro/caches.py): JAX's
persistent compilation cache and the kernel autotuner's tile choices."""
from pathlib import Path

import jax
import pytest

from repro.caches import CACHE_ROOT, use_compile_cache
from repro.kernels import tune

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


def test_cache_root_is_in_the_checkout():
    assert CACHE_ROOT == CHECKOUT / ".cache"
    assert (CHECKOUT / "chip_smoke.py").is_file()


def test_compile_cache_env_dir_stands(monkeypatch, tmp_path,
                                      cache_dir_restored):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands:
    the function reports it and sets no other directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".cache" / "jax")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert use_compile_cache() == want        # same path every call


def test_tune_cache_defaults_inside_checkout(monkeypatch):
    monkeypatch.delenv(tune.CACHE_ENV, raising=False)
    assert tune.cache_path() == str(CHECKOUT / ".cache" / "tune.json")
