"""``TrainingMonitor``: one object that wires the whole observability stack into an
algorithm loop with a ~3-line change.

    monitor = TrainingMonitor(cfg, log_dir)          # after get_logger(...)
    ...
    for update in ...:
        monitor.advance(policy_step)                 # top of every update
        ...
        monitor.log_metrics(logger, metrics, step)   # instead of logger.log_metrics
    ...
    monitor.close()                                  # before the loop's teardown

Per update, ``advance`` (a) rolls the ``jax.profiler.StepTraceAnnotation`` so XProf
traces show one slice per training update, (b) drives the programmatic XProf capture
window (``obs.capture_steps=[N, M]`` → ``<log_dir>/xprof``), (c) polls device/host
memory telemetry, and (d) after warmup arms the recompile watchdog and warns loudly on
every post-warmup jit cache miss.  The span tracer itself is fed by the ``timer``
context managers already present in every loop (see ``utils/timer.py``), so phase spans
(env interaction, h2d transfer, train step, logging) need no extra per-algo code.

``obs.enabled=false`` short-circuits every method on its first line: the monitor adds
one attribute check per update and nothing else.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict, Optional

from sheeprl_tpu.obs import flight_recorder as _flight_recorder
from sheeprl_tpu.obs import tracer as _tracer
from sheeprl_tpu.obs.telemetry import DeviceTelemetry
from sheeprl_tpu.obs.tracer import SpanTracer
from sheeprl_tpu.obs.watchdog import RecompileError, RecompileWarning, RecompileWatchdog

_UPDATE_SPAN = "Time/update"
_LOG_SPAN = "Time/log"


class TrainingMonitor:
    def __init__(self, cfg: Dict[str, Any], log_dir: str, rank: Optional[int] = None):
        obs_cfg = dict(cfg.get("obs", {}) or {})
        self.enabled: bool = bool(obs_cfg.get("enabled", False))
        # analysis.strict upgrades the recompile watchdog from warning to hard error
        # and arms NaN/Inf checks at the update boundary (sheeprl_tpu/analysis).
        from sheeprl_tpu.analysis.strict import strict_enabled

        self.strict: bool = strict_enabled(cfg)
        self.log_dir = log_dir
        self._updates = 0
        self._closed = False
        self.tracer: Optional[SpanTracer] = None
        self._telemetry: Optional[DeviceTelemetry] = None
        self._watchdog: Optional[RecompileWatchdog] = None
        # The flight recorder is INDEPENDENT of obs.enabled: crash forensics must
        # work on runs that never turned the tracer on.  It stays installed after
        # close() — cli.run_algorithm dumps it on crash and clears it afterwards.
        self.recorder = None
        if bool(obs_cfg.get("flight_recorder", True)):
            self.recorder = _flight_recorder.FlightRecorder(
                log_dir=log_dir,
                capacity=int(obs_cfg.get("flight_recorder_capacity", 4096)),
                keep_events=int(obs_cfg.get("flight_recorder_keep_events", 512)),
                algo=(cfg.get("algo", {}) or {}).get("name"),
                cfg=cfg,
            )
            _flight_recorder.install(self.recorder)
        # The performance-attribution plane (obs/perf.py) is likewise independent
        # of obs.enabled: MFU/goodput gauges and perf_report.json must exist on
        # runs that never turned the tracer on.
        from sheeprl_tpu.obs.perf import PerfPlane

        self.perf = PerfPlane(cfg, log_dir=log_dir)
        # Capture machinery lives in the common path (not behind obs.enabled) so
        # the perf watchdog's anomaly auto-capture can open an XProf window on an
        # otherwise-untraced run.
        self._capture = None
        self._capturing = False
        self._annotation = None
        self._host_tracer_level = int(obs_cfg.get("host_tracer_level", 0))
        self._perf_capture_remaining = 0
        if not self.enabled:
            return

        if rank is None:
            import jax

            rank = jax.process_index()
        self.rank = int(rank)

        # Validate everything that can raise BEFORE taking side effects (installing the
        # global tracer, registering the jax.monitoring listener) so a bad config
        # cannot leak process-global state.
        capture = obs_cfg.get("capture_steps")
        if capture:
            start, end = int(capture[0]), int(capture[1])
            if start < 1 or end < start:
                raise ValueError(f"obs.capture_steps must be [start>=1, end>=start]; got {capture!r}")
            self._capture = (start, end)

        self._xprof = bool(obs_cfg.get("xprof_annotations", True))
        self._warmup_updates = max(int(obs_cfg.get("warmup_updates", 1)), 0)
        self._telemetry_latest: Dict[str, float] = {}

        self._trace = bool(obs_cfg.get("trace", True))
        self._prev_tracer = None
        if self._trace:
            self.tracer = SpanTracer(rank=self.rank, max_events=int(obs_cfg.get("max_events", 100_000)))
            self._prev_tracer = _tracer.set_active(self.tracer)

        if bool(obs_cfg.get("telemetry", True)):
            self._telemetry = DeviceTelemetry(interval_s=float(obs_cfg.get("telemetry_interval", 10.0)))

        if bool(obs_cfg.get("watchdog", True)):
            self._watchdog = RecompileWatchdog()

    # ------------------------------------------------------------------ per update
    def advance(self, policy_step: Optional[int] = None) -> None:
        """Call once at the top of every training update."""
        self._updates += 1
        # Perf regression watchdog runs in the common path: a sustained step-time
        # degradation fires one perf_regression event + one bounded auto-capture
        # even when the tracer stack is off.
        event = self.perf.observe_step()
        if event is not None:
            _flight_recorder.record_event(
                "perf_regression",
                update=self._updates - 1,
                baseline_s=event["baseline_s"],
                ewma_s=event["ewma_s"],
                degradation=event["degradation"],
                capture=bool(event.get("capture")),
            )
        if not self.enabled:
            self._perf_capture_tick(event)
            return
        if self.strict:
            # update boundary: surface any NaN/Inf the in-jit nan_scan callbacks saw
            from sheeprl_tpu.analysis.strict import raise_pending

            raise_pending()
        update = self._updates

        if self.tracer is not None:
            if update > 1:
                self.tracer.end(_UPDATE_SPAN)
            self.tracer.begin(_UPDATE_SPAN)

        # Close the previous update's StepTraceAnnotation BEFORE moving the capture
        # window, and open the next one AFTER: every annotation must nest strictly
        # inside the profiler session (TraceMe handles straddling a start_trace/
        # stop_trace boundary poorly — observed as a native crash when third-party
        # render threads are alive).
        if self._xprof and self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

        self._perf_capture_tick(event)
        if self._capture is not None:
            start, end = self._capture
            if update == start and not self._capturing:
                self._start_capture()
            elif update == end + 1 and self._capturing:
                self._stop_capture()

        if self._xprof:
            import jax

            self._annotation = jax.profiler.StepTraceAnnotation("train", step_num=update)
            self._annotation.__enter__()

        if self._watchdog is not None:
            if update == self._warmup_updates + 1:
                self._watchdog.mark_warm()
            elif update > self._warmup_updates + 1:
                n = self._watchdog.poll_new()
                if n:
                    _flight_recorder.record_event(
                        "recompile",
                        update=update - 1,
                        count=n,
                        total=self._watchdog.total_compiles,
                    )
                    msg = (
                        f"{n} post-warmup XLA recompilation(s) detected at update {update - 1} "
                        f"(total={self._watchdog.total_compiles}): a jitted function's input "
                        "shapes/dtypes or captured constants are changing between updates, which "
                        "silently destroys throughput. Check Compile/recompiles and capture an "
                        "XProf window (obs.capture_steps) around this update."
                    )
                    if self.strict:
                        raise RecompileError(f"analysis.strict: {msg}")
                    warnings.warn(msg, RecompileWarning, stacklevel=2)

        if self._telemetry is not None:
            polled = self._telemetry.poll()
            if polled:
                self._telemetry_latest = polled

    def _perf_capture_tick(self, event: Optional[Dict[str, float]]) -> None:
        """Drive the watchdog's bounded auto-capture window (obs.perf.capture_updates)."""
        if event is not None and event.get("capture") and not self._capturing:
            self._perf_capture_remaining = max(1, self.perf.capture_updates)
            self._start_capture()
        elif self._perf_capture_remaining > 0:
            self._perf_capture_remaining -= 1
            if self._perf_capture_remaining <= 0 and self._capturing:
                self._stop_capture()

    # ------------------------------------------------------------------ metrics/logging
    def span(self, name: str):
        """Extra phase span, e.g. ``with monitor.span("Time/replay_ratio_wait"):``."""
        return _tracer._SpanContext(name, self.tracer)

    @staticmethod
    def phase(name: str):
        """Named wall-clock phase: ``with monitor.phase("env_step"):`` accumulates
        ``Time/phase_env_step`` seconds in the timer registry (and a span when the
        tracer is on).  :meth:`log_metrics` folds the registry into every flush, so
        any loop instrumented with phases gets the per-phase wall-clock breakdown
        the DreamerV3 loop pioneered — independent of ``obs.enabled``, at the cost
        of one ``perf_counter`` pair per block."""
        from sheeprl_tpu.utils.timer import timer

        return timer(f"Time/phase_{name}")

    def metrics(self) -> Dict[str, float]:
        """Span percentiles + memory/compile gauges, flattened for the logger."""
        if not self.enabled:
            return {}
        out: Dict[str, float] = {}
        if self.tracer is not None:
            for name, stats in self.tracer.percentiles(reset=True).items():
                for k, v in stats.items():
                    out[f"{name}/{k}"] = v
        out.update(self._telemetry_latest)
        if self._watchdog is not None:
            out.update(self._watchdog.metrics())
        return out

    def log_metrics(self, logger, metrics: Dict[str, float], step: int) -> None:
        """Merge the monitor's metrics and forward to the logger inside a log span.

        Runs three things regardless of ``obs.enabled``: (a) folds the named-timer
        registry into the flush, so every loop instrumented with ``monitor.phase``
        / ``with timer(...)`` reports the ``Time/phase_*`` wall-clock breakdown for
        free, (b) folds in the ``Fault/*`` counters (``sheeprl_tpu/fault``) — empty
        for a healthy run, the preemption/restart/fallback trail for a supervised
        one — and (c) records a ``metric_flush`` event (with a Health/Loss
        snapshot) on the flight recorder — the learning-dynamics trail a blackbox
        dump is read by.
        """
        from sheeprl_tpu.fault.counters import fault_metrics
        from sheeprl_tpu.utils.timer import timer as _timer

        metrics.update(_timer.to_dict(reset=True))
        metrics.update(fault_metrics())
        # Perf gauges fold in AFTER the timer drain (the goodput ledger reads the
        # Time/* keys straight out of the flush) and run regardless of obs.enabled.
        recompile_s = self._watchdog.drain_compile_seconds() if self._watchdog is not None else 0.0
        self.perf.flush(metrics, recompile_s=recompile_s)
        if _flight_recorder.get_active() is not None:
            snapshot = {
                k: metrics[k]
                for k in metrics
                if k.startswith(("Health/", "Loss/", "Compile/", "Rollout/", "Perf/"))
            }
            _flight_recorder.record_event(
                "metric_flush", step=step, n_metrics=len(metrics), values=snapshot
            )
        if not self.enabled:
            if logger is not None:
                logger.log_metrics(metrics, step)
            return
        metrics.update(self.metrics())
        if logger is None:
            return
        if self.tracer is not None:
            self.tracer.begin(_LOG_SPAN)
            try:
                logger.log_metrics(metrics, step)
            finally:
                self.tracer.end(_LOG_SPAN)
        else:
            logger.log_metrics(metrics, step)

    # ------------------------------------------------------------------ capture window
    def _start_capture(self) -> None:
        """Open an XProf trace writing to ``<log_dir>/xprof`` (``.xplane.pb`` under
        ``plugins/profile/<stamp>/``).

        ``jax.profiler.ProfileOptions`` carries the TSL *host* tracer level: at its
        default level the host tracer installs thread hooks that SEGFAULT when
        certain third-party threads are alive (observed with dm_control/glfw render
        threads + a SummaryWriter event thread).  ``obs.host_tracer_level=0`` (the
        default) skips host tracing entirely — device/XLA events, the part the span
        tracer cannot see, are still captured — and is the only level safe
        everywhere.  A trace that cannot start (one is already running) warns and
        leaves training alone."""
        import jax

        path = os.path.join(self.log_dir, "xprof")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = self._host_tracer_level
        opts.python_tracer_level = 0
        try:
            jax.profiler.start_trace(path, profiler_options=opts)
        except RuntimeError as e:
            warnings.warn(f"obs.capture_steps: could not start XProf trace at {path}: {e}")
            return
        self._capturing = True

    def _stop_capture(self) -> None:
        import jax

        self._capturing = False
        try:
            jax.profiler.stop_trace()
        except RuntimeError as e:
            warnings.warn(f"obs.capture_steps: could not export XProf trace: {e}")

    # ------------------------------------------------------------------ teardown
    def trace_path(self) -> str:
        name = "trace.json" if self.rank == 0 else f"trace_rank{self.rank}.json"
        return os.path.join(self.log_dir, name)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.enabled:
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
            if self._capturing:
                self._stop_capture()
            if self._watchdog is not None:
                self._watchdog.close()
            if self.tracer is not None:
                self.tracer.end(_UPDATE_SPAN)
                try:
                    self.tracer.export_chrome_trace(self.trace_path())
                except OSError as e:
                    warnings.warn(f"could not export Chrome trace: {e}")
                _tracer.set_active(self._prev_tracer)
        elif self._capturing:
            # an anomaly auto-capture may be open on an otherwise-untraced run
            self._stop_capture()
        from sheeprl_tpu.obs.perf import report_path

        path = report_path(self.log_dir)
        if path:
            self.perf.write_report(path)
        # Strict runs drain outstanding in-jit nan_scan callbacks one last time
        # AFTER teardown: a NaN in the final update (no later advance() to surface
        # it) must still crash the run — and therefore trigger the blackbox dump —
        # instead of exiting zero.
        if self.strict:
            from sheeprl_tpu.analysis.strict import raise_pending

            raise_pending()
