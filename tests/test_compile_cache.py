"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after the test (nothing compiles in
    between, so the cache itself is never opened)."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert (compile_cache.REPO_CACHE_DIR.parent / "pyproject.toml").is_file()
    assert compile_cache.use_compile_cache() == got
