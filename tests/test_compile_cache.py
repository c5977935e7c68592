"""The launchers' persistent compilation cache: the directory the
environment names, else a fixed directory in the checkout."""

import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_cache_follows_the_environment(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # jax reads the variable itself; the helper sets nothing
    assert jax.config.jax_compilation_cache_dir == was


def test_cache_defaults_to_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # fixed: the same directory on every call, in every process
    assert compile_cache.enable_compile_cache() == path
