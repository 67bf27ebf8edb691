"""repro.compile_cache: the persistent compilation cache lives at
$JAX_COMPILATION_CACHE_DIR when that is set, else at a fixed path inside
the checkout."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable() == want
    assert compile_cache.enable() == want          # no pid, time or temp
    assert jax.config.jax_compilation_cache_dir == want
