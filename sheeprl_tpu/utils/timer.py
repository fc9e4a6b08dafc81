"""Named wall-clock timers (reference: ``sheeprl/utils/timer.py:16-83``).

Class-level registry of named accumulating timers usable as context managers; drives the
``Time/sps_train`` / ``Time/sps_env_interaction`` throughput metrics.

Every timed block is also a *span* (``obs/tracer.py``: ``begin`` / ``end``): an
annotation in the host plane of whichever XProf capture is running, under the
timer's name and on the profiler's clock, and a slice of the ``sheeprl_tpu.obs``
tracer when one is active.  With neither, the hook is the annotation's constructor
and two calls.
"""

from __future__ import annotations

import time
from typing import Dict

from sheeprl_tpu.obs import tracer as _tracer


class timer:
    disabled: bool = False
    _registry: Dict[str, float] = {}

    def __init__(self, name: str):
        self.name = name
        self._start = 0.0
        self._span = None

    def __enter__(self):
        if not timer.disabled:
            self._span = _tracer.begin(self.name)
            self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._span is not None:
            elapsed = time.perf_counter() - self._start
            timer._registry[self.name] = timer._registry.get(self.name, 0.0) + elapsed
            _tracer.end(self.name, self._span)
            self._span = None
        return False

    @classmethod
    def to_dict(cls, reset: bool = True) -> Dict[str, float]:
        out = dict(cls._registry)
        if reset:
            cls._registry.clear()
        return out

    @classmethod
    def reset(cls) -> None:
        cls._registry.clear()
