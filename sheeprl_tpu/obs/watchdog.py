"""Recompilation watchdog → ``Compile/*`` metrics.

A silently recompiling jitted train step is the single worst throughput bug on TPU: one
leaked python scalar in a carry (or a shape that varies with episode length) turns a
30µs cache hit into a multi-second XLA compile *every update*.  The watchdog counts
backend compiles through ``jax.monitoring``'s ``backend_compile`` duration event,
splits them at ``mark_warm()`` (end of the first update = expected warmup compiles),
and flags every post-warmup compile as a recompile.
"""

from __future__ import annotations

import threading
from typing import Dict

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileWarning(UserWarning):
    """Raised (via ``warnings.warn``) when a jitted function recompiles after warmup."""


class RecompileError(RuntimeError):
    """Hard-error form of :class:`RecompileWarning`, raised instead of warning when
    runtime strict mode (``analysis.strict=True``) is enabled."""


class RecompileWatchdog:
    def __init__(self):
        self._lock = threading.Lock()
        self._total = 0
        self._post_warmup = 0
        self._unseen = 0  # post-warmup compiles not yet drained by poll_new()
        self._compile_seconds = 0.0  # cumulative backend-compile wall clock
        self._unseen_seconds = 0.0  # compile seconds not yet drained (goodput ledger)
        self._warm = False
        self._active = True

        def _listener(event: str, duration_secs: float, **kwargs) -> None:
            if not self._active or event != _BACKEND_COMPILE_EVENT:
                return
            with self._lock:
                self._total += 1
                self._compile_seconds += float(duration_secs or 0.0)
                self._unseen_seconds += float(duration_secs or 0.0)
                if self._warm:
                    self._post_warmup += 1
                    self._unseen += 1

        self._listener = _listener
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_listener)

    def mark_warm(self) -> None:
        """Everything compiled so far was warmup; anything after this is a recompile."""
        with self._lock:
            self._warm = True

    @property
    def total_compiles(self) -> int:
        return self._total

    @property
    def recompiles(self) -> int:
        return self._post_warmup

    def poll_new(self) -> int:
        """Post-warmup recompiles since the last poll (drains the unseen counter)."""
        with self._lock:
            n = self._unseen
            self._unseen = 0
        return n

    @property
    def compile_seconds(self) -> float:
        return self._compile_seconds

    def drain_compile_seconds(self) -> float:
        """Backend-compile seconds since the last drain (goodput ledger input)."""
        with self._lock:
            s = self._unseen_seconds
            self._unseen_seconds = 0.0
        return s

    def metrics(self) -> Dict[str, float]:
        return {
            "Compile/total_compiles": float(self._total),
            "Compile/recompiles": float(self._post_warmup),
            "Compile/compile_seconds": float(self._compile_seconds),
        }

    def close(self) -> None:
        if not self._active:
            return
        self._active = False
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._listener)
