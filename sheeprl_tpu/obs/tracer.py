"""Hierarchical span tracer: Chrome-trace/Perfetto export + per-span latency histograms.

The tracer is the measurement core of ``sheeprl_tpu.obs`` (see Podracer,
arXiv:2104.06272 §4: per-phase dataflow telemetry is what makes actor/learner
pipelines tunable).  Spans nest through a per-thread stack, so a ``with``-block
inside another ``with``-block shows up as a child slice in Perfetto; every
completed span also feeds a ``HistogramMetric`` so p50/p95/p99 latencies flow
into the existing metric/logger pipeline.

Design constraints:

* stdlib + numpy only at import time — ``utils.timer`` hooks into this module, and the
  CLI imports the timer before JAX may touch a backend (``jax.profiler`` is imported
  at the first span, which touches none);
* ONE begin/end pair (:func:`begin` / :func:`end`) under ``timer``, :func:`span` and
  :func:`trace_span`: it writes the span into the profiler's trace as a
  ``jax.profiler.TraceAnnotation`` (recorded by whichever XProf capture is running,
  on the profiler's own clock; with none running, the annotation's constructor and
  two calls) and feeds the module-level *active* tracer (``None`` when
  observability is off: one global load + ``is None`` check);
* thread-safe — decoupled algorithms run player/trainer phases from worker threads, and
  the Chrome trace keeps per-thread tracks via ``tid``.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from sheeprl_tpu.obs import flight_recorder as _flight_recorder
from sheeprl_tpu.utils.metric import HistogramMetric

# (name, ts_us, dur_us, tid, depth) — kept as a flat tuple to stay allocation-light.
_Event = Tuple[str, float, float, int, int]

_ACTIVE: Optional["SpanTracer"] = None


def get_active() -> Optional["SpanTracer"]:
    return _ACTIVE


def set_active(tracer: Optional["SpanTracer"]) -> Optional["SpanTracer"]:
    """Install ``tracer`` as the process-global tracer; returns the previous one."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    return prev


_annotation = None  # jax.profiler.TraceAnnotation, bound at the first span


def begin(name: str, tracer: Optional["SpanTracer"] = None):
    """Open the span ``name`` on this thread; returns the handle :func:`end` takes.

    The span is a ``TraceAnnotation`` (an event named ``name`` in the host plane of
    any capture that runs from before its begin to after its end; a span that
    straddles ``start_trace`` or ``stop_trace`` is dropped by the profiler) and a
    slice of ``tracer`` (default: the active one, if any)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    annotation = _annotation(name)
    annotation.__enter__()
    if tracer is None:
        tracer = _ACTIVE
    if tracer is not None:
        tracer.begin(name)
    return annotation


def end(name: str, annotation, tracer: Optional["SpanTracer"] = None) -> None:
    if tracer is None:
        tracer = _ACTIVE
    if tracer is not None:
        tracer.end(name)
    annotation.__exit__(None, None, None)


class _SpanContext:
    """Re-usable context manager handed out by ``TrainingMonitor.span`` / module ``span``."""

    __slots__ = ("_name", "_tracer", "_annotation")

    def __init__(self, name: str, tracer: Optional["SpanTracer"]):
        self._name = name
        self._tracer = tracer
        self._annotation = None

    def __enter__(self):
        self._annotation = begin(self._name, self._tracer)
        return self

    def __exit__(self, *exc):
        end(self._name, self._annotation, self._tracer)
        return False


def span(name: str) -> _SpanContext:
    """``with span("Time/phase"):`` — records on whichever tracer is active at entry."""
    return _SpanContext(name, None)


def trace_span(name: str) -> Callable:
    """Decorator form: the wrapped call becomes one span."""

    def deco(fn: Callable) -> Callable:
        def wrapper(*args, **kwargs):
            annotation = begin(name)
            try:
                return fn(*args, **kwargs)
            finally:
                end(name, annotation)

        wrapper.__name__ = getattr(fn, "__name__", "wrapped")
        wrapper.__doc__ = fn.__doc__
        wrapper.__wrapped__ = fn
        return wrapper

    return deco


class SpanTracer:
    """Collects nested spans into (a) a bounded Chrome-trace event list and (b) per-name
    latency histograms.

    ``rank`` becomes the Chrome-trace ``pid`` so multi-host traces merge into one
    Perfetto timeline with one process track per host.
    """

    def __init__(self, rank: int = 0, max_events: int = 100_000):
        self.rank = int(rank)
        self.max_events = int(max_events)
        self.dropped_events = 0
        self._events: List[_Event] = []
        self._histograms: Dict[str, HistogramMetric] = {}
        self._lock = threading.Lock()
        self._local = threading.local()
        # One origin for all ranks' clocks is not required: Perfetto aligns tracks per
        # pid; within a process perf_counter is monotonic and free of NTP jumps.
        self._origin = time.perf_counter()

    # ------------------------------------------------------------------ recording
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def begin(self, name: str) -> None:
        self._stack().append((name, time.perf_counter()))

    def end(self, name: str) -> None:
        now = time.perf_counter()
        stack = self._stack()
        if not stack:
            return  # unbalanced end: tracer was activated mid-span; drop silently
        # Unwind to the matching name so a timer disabled/enabled mid-block can't
        # permanently skew nesting depth.
        while stack:
            top_name, start = stack.pop()
            if top_name == name:
                break
        else:
            return
        dur_us = (now - start) * 1e6
        ts_us = (start - self._origin) * 1e6
        depth = len(stack)
        tid = threading.get_ident()
        with self._lock:
            hist = self._histograms.get(name)
            if hist is None:
                hist = self._histograms[name] = HistogramMetric()
            hist.update(dur_us / 1e3)  # histograms in milliseconds
            if len(self._events) < self.max_events:
                self._events.append((name, ts_us, dur_us, tid, depth))
            else:
                self.dropped_events += 1
        # Span closures also feed the flight recorder's bounded event ring (one
        # global load when no recorder is armed) — the dump's timeline context.
        _flight_recorder.record_span(name, dur_us / 1e3, depth)

    # ------------------------------------------------------------------ export
    def percentiles(self, reset: bool = True) -> Dict[str, Dict[str, float]]:
        """Per-span ``{name: {p50, p95, p99, mean, count}}`` in milliseconds."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for name, hist in self._histograms.items():
                v = hist.compute()
                if v:
                    out[name] = v
                if reset:
                    hist.reset()
        return out

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome Trace Event Format dict — loadable by Perfetto / chrome://tracing."""
        with self._lock:
            events = list(self._events)
        tids = sorted({e[3] for e in events})
        trace_events: List[Dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": self.rank,
                "args": {"name": f"rank{self.rank}"},
            }
        ]
        for tid in tids:
            trace_events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": self.rank,
                    "tid": tid,
                    "args": {"name": f"thread-{tid}"},
                }
            )
        for name, ts_us, dur_us, tid, depth in events:
            trace_events.append(
                {
                    "name": name,
                    "cat": "sheeprl_tpu",
                    "ph": "X",
                    "ts": round(ts_us, 3),
                    "dur": round(dur_us, 3),
                    "pid": self.rank,
                    "tid": tid,
                    "args": {"depth": depth},
                }
            )
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._histograms.clear()
            self.dropped_events = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)
