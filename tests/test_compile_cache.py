"""``enable_compile_cache``: JAX_COMPILATION_CACHE_DIR wins, else .jax_cache/."""
import jax
import pytest
from jax.experimental.compilation_cache.compilation_cache import reset_cache

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    reset_cache()


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_enable_compile_cache(cache_config, monkeypatch, env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env is None:
        assert got == str(compile_cache.CACHE_DIR)
        assert compile_cache.CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CACHE_DIR.parent / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == got
    else:
        # JAX reads the variable itself: nothing is set in code
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before
