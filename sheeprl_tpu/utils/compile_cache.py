"""Persistent XLA compilation cache: the one resolver train, serve, ``bench.py``,
``chip_smoke.py`` and the benchmark scripts share.

Where the cache lives is decided in :func:`resolve_cache_dir` and nowhere else:

* ``JAX_COMPILATION_CACHE_DIR`` set — that directory is the cache.  JAX reads the
  variable itself, so this module sets no directory in code and neither
  ``compile_cache.dir`` nor a benchmark can move it: whoever runs the program
  (a scheduler that keeps one cache across runs, the chip tool) places it.
* unset — ``compile_cache.dir`` when given, else :data:`DEFAULT_CACHE_DIR`, one
  fixed git-ignored directory inside the checkout, resolved from this file's
  location.  The path is part of every cache key's lookup, so it never comes
  from ``~``, the working directory, ``tempfile``, a pid or the clock: a
  directory that moves between runs never hits.

``compile_cache.enabled=True`` additionally zeroes the min-compile-time /
entry-size floors so even small programs cache — a cold start wants the WHOLE
program set warm, not just the multi-second dispatches.  The cache initializes
lazily on the first compile and then ignores config updates, so
:func:`enable_compile_cache` also resets it: back-to-back runs in one process
still land in the requested dir.
"""

from __future__ import annotations

import os
import shutil
import threading
from pathlib import Path
from typing import Any, Dict, Optional

CACHE_DIR_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.xla_cache`` (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"

_HITS_EVENT = "/jax/compilation_cache/cache_hits"
_MISSES_EVENT = "/jax/compilation_cache/cache_misses"
_REQUESTS_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


def resolve_cache_dir(configured: Optional[str] = None) -> str:
    """The directory the persistent cache uses (see the module docstring)."""
    return os.environ.get(CACHE_DIR_ENV_VAR) or str(configured or DEFAULT_CACHE_DIR)


def enable_compile_cache(compile_cache_cfg: Optional[Dict[str, Any]]) -> Optional[str]:
    """Wire the persistent cache when ``enabled``; returns the cache dir used."""
    compile_cache = compile_cache_cfg or {}
    if not compile_cache.get("enabled", False):
        return None
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = resolve_cache_dir(compile_cache.get("dir"))
    if not os.environ.get(CACHE_DIR_ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    return cache_dir


def empty_cold_start_dir(name: str) -> str:
    """A fixed sub-directory of the resolved cache, emptied: what the cold-vs-warm
    benchmark rows (``anakin_compile_seconds``, ``serve_startup_seconds``) hand
    their first child as ``compile_cache.dir`` so it starts with no compiled
    code, and their second child so it finds exactly what the first one wrote.
    (Where ``JAX_COMPILATION_CACHE_DIR`` is set the children use that directory
    instead and the "cold" child is only as cold as the cache it was given.)"""
    path = Path(resolve_cache_dir()) / "cold_start" / name
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True, exist_ok=True)
    return str(path)


class CacheStats:
    """Counts JAX's own persistent-cache events for this process: compile
    requests that consulted the cache, hits (deserialized) and misses (compiled
    and written).  ``misses == 0`` after a run is "compiled nothing new"."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.requests = 0
        self.hits = 0
        self.misses = 0
        from jax import monitoring

        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs: Any) -> None:
        with self._lock:
            if event == _REQUESTS_EVENT:
                self.requests += 1
            elif event == _HITS_EVENT:
                self.hits += 1
            elif event == _MISSES_EVENT:
                self.misses += 1

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"requests": self.requests, "hits": self.hits, "misses": self.misses}

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_listener(self._on_event)
