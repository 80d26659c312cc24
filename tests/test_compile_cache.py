"""Where the entry points put JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_is_fixed_dir_at_repo_root(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert Path(path) == root / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == path
