"""The entry points' compile-cache helper honours JAX_COMPILATION_CACHE_DIR
and otherwise uses one fixed, gitignored path inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_dir_is_fixed_and_ignored(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored, ".jax_cache/ is not gitignored"


def test_compile_counters_count_executables():
    import numpy as np

    f = jax.jit(lambda x: x * 3 - 1)
    x = jax.device_put(np.ones(37, np.float32))
    before = compile_cache.compile_stats()
    f(x).block_until_ready()
    once = compile_cache.compile_stats()
    assert once["executables"] == before["executables"] + 1
    assert once["seconds"] > before["seconds"]
    # the same shape again is the executable JAX already holds
    f(x).block_until_ready()
    assert compile_cache.compile_stats()["executables"] == once["executables"]
