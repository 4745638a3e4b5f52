"""Process setup shared by the entry points (launchers, benchmarks,
chip_smoke.py): where JAX keeps its persistent compilation cache.

Entry points call :func:`configure_compile_cache` first thing in
``main()``; nothing calls it at import time. A cache entry's key holds
its directory's path, so the directory never moves between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself) and otherwise ``<checkout>/.jax_cache``, which ``.gitignore``
lists.
"""

from __future__ import annotations

import collections
import os
import pathlib

import jax

__all__ = ["DEFAULT_CACHE_DIR", "configure_compile_cache",
           "CompileCacheCounter"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own setting is left
    alone. Calling it again is harmless."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # a cache JAX already opened keeps its old directory otherwise
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return path


class CompileCacheCounter:
    """Counts persistent-cache lookups from creation until ``close()``:
    ``requests`` (compiles that consulted the cache), ``hits``
    (executables read back instead of compiled) and ``writes`` (fresh
    compiles stored for the next run). Usable as a context manager."""

    _PREFIX = "/jax/compilation_cache/"

    def __init__(self):
        self._counts = collections.Counter()
        jax.monitoring.register_event_listener(self._count)

    def _count(self, event: str, **_kwargs) -> None:
        if event.startswith(self._PREFIX):
            self._counts[event[len(self._PREFIX):]] += 1

    def counts(self) -> dict:
        return {"requests": self._counts["compile_requests_use_cache"],
                "hits": self._counts["cache_hits"],
                "writes": self._counts["cache_misses"]}

    def close(self) -> None:
        jax.monitoring.unregister_event_listener(self._count)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
