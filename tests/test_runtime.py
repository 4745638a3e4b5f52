"""configure_compile_cache: one fixed cache directory, placed from outside."""

import pathlib

import jax
import pytest

from repro import runtime

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_dir_is_fixed_inside_the_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.configure_compile_cache()
    second = runtime.configure_compile_cache()
    assert first == second == jax.config.jax_compilation_cache_dir
    path = pathlib.Path(first)
    assert path.is_relative_to(REPO) and path != REPO
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_counter_sees_lookups_hits_and_writes(tmp_path, restore_cache_dir):
    """A compile is written once, and read back after the in-memory
    caches are dropped: the counter sees both."""
    from jax.experimental.compilation_cache import compilation_cache

    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    x = jax.numpy.ones((7, 5))
    try:
        with runtime.CompileCacheCounter() as counter:
            f = jax.jit(lambda x: x * 3.0 + 1.0)
            f(x).block_until_ready()
            jax.clear_caches()
            f(x).block_until_ready()
        seen = counter.counts()
        jax.jit(lambda x: x - 2.0)(x).block_until_ready()   # after close
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    assert seen == {"requests": 2, "hits": 1, "writes": 1}
    assert counter.counts() == seen
