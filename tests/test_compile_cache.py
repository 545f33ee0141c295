"""The entry points' persistent compile cache: the environment's
directory when ``JAX_COMPILATION_CACHE_DIR`` is set (nothing set in
code), else a fixed directory of the checkout."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_environment_directory_wins(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_directory_is_fixed(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.setup_compile_cache()
    assert first == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.setup_compile_cache() == first
