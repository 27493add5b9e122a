"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, or to the fixed <checkout>/.jax_cache."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import ENV_VAR, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config(monkeypatch):
    """Restores jax's cache-dir setting (and, through monkeypatch, the
    environment) after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(cache_dir_config, tmp_path):
    cache_dir_config.setenv(ENV_VAR, str(tmp_path / "placed"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path / "placed")
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(cache_dir_config):
    cache_dir_config.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: a moving directory never hits
    assert enable_compile_cache() == path
