"""The persistent compile cache follows JAX_COMPILATION_CACHE_DIR, else a
fixed directory inside the checkout."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cc"))
    d = compile_cache.enable_compile_cache()
    assert d == tmp_path / "cc" and d.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(d)


def test_unset_uses_one_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == compile_cache.DEFAULT_DIR
    root = compile_cache.DEFAULT_DIR.parent
    assert (root / "src" / "repro").is_dir()    # inside the checkout
    assert jax.config.jax_compilation_cache_dir == str(first)
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
