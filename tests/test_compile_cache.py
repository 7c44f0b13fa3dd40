"""The persistent compilation cache's directory: the environment's when
``JAX_COMPILATION_CACHE_DIR`` is set, else a fixed one in the checkout."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_is_left_to_jax(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_is_fixed_inside_the_checkout(monkeypatch,
                                                        restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == jax.config.jax_compilation_cache_dir
    assert path.endswith("/.jax_cache")
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src" / "repro"
            / "utils" / "compile_cache.py").is_file()
    assert compile_cache.enable_compile_cache() == path   # never moves
