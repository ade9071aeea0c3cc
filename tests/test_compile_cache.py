"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_env_setting_stands(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_unset_env_uses_fixed_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == path
