"""Performance attribution plane (PR-19): cost-model registry + instrument
wrapper, goodput ledger on synthetic timelines, EWMA regression watchdog
exactly-once semantics, MFU agreement with ``bench.py``, and the monitor e2e
(perf_report.json + forced slowdown -> ONE auto-capture + ONE perf_regression
flight-recorder event)."""

import json
import math
import time

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.obs import flight_recorder as flight_recorder_mod
from sheeprl_tpu.obs import perf
from sheeprl_tpu.obs.monitor import TrainingMonitor
from sheeprl_tpu.obs.perf import (
    GOODPUT_CATEGORIES,
    GoodputLedger,
    PerfPlane,
    StepTimeWatchdog,
)


@pytest.fixture(autouse=True)
def _clean_registry():
    perf.reset()
    yield
    perf.reset()
    flight_recorder_mod.install(None)


def _assert_sums_to_one(fractions):
    assert set(fractions) == set(GOODPUT_CATEGORIES)
    assert math.isclose(sum(fractions.values()), 1.0, abs_tol=1e-9), fractions
    assert all(f >= 0.0 for f in fractions.values()), fractions


# ------------------------------------------------------------- goodput ledger
def test_goodput_clean_run():
    """Compute-dominated window: goodput ~= compute + env, remainder -> other."""
    with jax.transfer_guard("disallow"):  # pure host accounting, no device traffic
        ledger = GoodputLedger()
        fractions = ledger.classify(
            {"Time/train_time": 0.8, "Time/env_interaction_time": 0.15}, elapsed_s=1.0
        )
    _assert_sums_to_one(fractions)
    assert math.isclose(fractions["compute"], 0.8)
    assert math.isclose(fractions["env"], 0.15)
    assert math.isclose(fractions["other"], 0.05)
    assert math.isclose(ledger.goodput(), 0.95)


def test_goodput_recompile_storm():
    """A recompile storm (watchdog-drained compile seconds) eats the window."""
    with jax.transfer_guard("disallow"):
        ledger = GoodputLedger()
        fractions = ledger.classify({"Time/train_time": 0.2}, elapsed_s=1.0, recompile_s=0.7)
    _assert_sums_to_one(fractions)
    assert math.isclose(fractions["recompile"], 0.7)
    assert ledger.goodput() < 0.3


def test_goodput_checkpoint_stall():
    with jax.transfer_guard("disallow"):
        ledger = GoodputLedger()
        fractions = ledger.classify(
            {"Time/train_time": 0.3, "Time/phase_checkpoint": 0.6}, elapsed_s=1.0
        )
    _assert_sums_to_one(fractions)
    assert math.isclose(fractions["checkpoint"], 0.6)


def test_goodput_actor_restart_downtime():
    """Supervisor-attributed downtime (actor restart) lands in its own bucket."""
    with jax.transfer_guard("disallow"):
        ledger = GoodputLedger()
        fractions = ledger.classify({"Time/train_time": 0.5}, elapsed_s=2.0, downtime_s=1.0)
    _assert_sums_to_one(fractions)
    assert math.isclose(fractions["downtime"], 0.5)
    assert math.isclose(fractions["compute"], 0.25)


def test_goodput_overlap_clamps_proportionally():
    """Overlapping timers classify more seconds than the wall clock: every
    category scales down so the fractions still sum to exactly 1.0."""
    ledger = GoodputLedger()
    fractions = ledger.classify(
        {"Time/train_time": 1.5, "Time/env_interaction_time": 1.5}, elapsed_s=1.0
    )
    _assert_sums_to_one(fractions)
    assert math.isclose(fractions["compute"], 0.5)
    assert math.isclose(fractions["env"], 0.5)
    assert fractions["other"] == 0.0


def test_goodput_no_double_count_anakin_aliases():
    """Anakin stamps the SAME dispatch block as both Time/phase_dispatch and
    Time/train_time: only the first-present key may count as compute."""
    ledger = GoodputLedger()
    fractions = ledger.classify(
        {"Time/phase_dispatch": 0.6, "Time/train_time": 0.6}, elapsed_s=1.0
    )
    assert math.isclose(fractions["compute"], 0.6), "aliased timers double-counted"
    _assert_sums_to_one(fractions)


def test_goodput_empty_window_is_other():
    ledger = GoodputLedger()
    fractions = ledger.classify({}, elapsed_s=0.0)
    _assert_sums_to_one(fractions)
    assert fractions["other"] == 1.0


def test_goodput_cumulative_fractions():
    ledger = GoodputLedger()
    ledger.classify({"Time/train_time": 1.0}, elapsed_s=1.0)
    ledger.classify({"Time/train_time": 0.0}, elapsed_s=1.0)
    _assert_sums_to_one(ledger.fractions())
    assert math.isclose(ledger.fractions()["compute"], 0.5)
    assert math.isclose(ledger.goodput(), 0.5)


# -------------------------------------------------------- regression watchdog
def test_watchdog_fires_exactly_once_per_sustained_episode():
    dog = StepTimeWatchdog(regress_pct=0.5, warmup_steps=3, sustain_steps=2, alpha=1.0)
    for _ in range(3):
        assert dog.observe(0.01) is None  # warmup builds the baseline
    events = [dog.observe(0.05) for _ in range(6)]  # sustained 5x degradation
    fired = [e for e in events if e is not None]
    assert len(fired) == 1, "one event per sustained episode, no flapping"
    assert fired[0]["capture"] is True
    assert fired[0]["degradation"] > 0.5
    assert dog.anomalies == 1


def test_watchdog_rearms_after_recovery_but_capture_budget_is_spent():
    dog = StepTimeWatchdog(
        regress_pct=0.5, warmup_steps=3, sustain_steps=2, alpha=1.0, max_captures=1
    )
    for _ in range(3):
        dog.observe(0.01)
    first = [dog.observe(0.05) for _ in range(3)]
    assert sum(e is not None for e in first) == 1
    for _ in range(3):
        assert dog.observe(0.01) is None  # recovery re-arms
    second = [dog.observe(0.05) for _ in range(3)]
    fired = [e for e in second if e is not None]
    assert len(fired) == 1, "recovered episode must be able to fire again"
    assert fired[0]["capture"] is False, "capture budget (1) already spent"
    assert dog.anomalies == 2


def test_watchdog_silent_during_warmup_and_transient_blips():
    dog = StepTimeWatchdog(regress_pct=0.5, warmup_steps=3, sustain_steps=3, alpha=1.0)
    assert dog.observe(10.0) is None  # compile-dominated warmup step
    for _ in range(2):
        assert dog.observe(0.01) is None
    # two degraded steps < sustain_steps=3, then recovery: never fires
    assert dog.observe(0.05) is None
    assert dog.observe(0.05) is None
    assert dog.observe(0.01) is None
    assert dog.anomalies == 0


# ------------------------------------------------- cost-model registry + MFU
def test_instrument_registers_cost_model_and_counts_calls():
    """E2E under transfer_guard('disallow'): registration must be a pure
    abstract lowering — no device transfer, no extra sync."""
    cfg = {"obs": {"perf": {"enabled": True}}}

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x)

    wrapped = perf.instrument(cfg, "test/step", step)
    x = jnp.ones((16, 16), jnp.float32)
    wrapped(x)  # first call compiles outside the guard
    with jax.transfer_guard("disallow"):
        out = wrapped(x)
        out = wrapped(out)
    jax.block_until_ready(out)

    models = perf.registered_cost_models()
    assert "test/step" in models
    entry = models["test/step"]
    assert entry["flops"] > 0
    assert entry["calls"] == 3
    # wrapper result identical to the bare fn
    assert jnp.allclose(out, step(step(step(x))))


def test_instrument_disabled_is_identity():
    cfg = {"obs": {"perf": {"enabled": False}}}

    def fn(x):
        return x

    assert perf.instrument(cfg, "test/identity", fn) is fn
    assert perf.registered_cost_models() == {}


def test_register_compiled_from_aot_executable():
    @jax.jit
    def act(x):
        return x @ x

    exe = act.lower(jnp.ones((8, 8), jnp.float32)).compile()
    perf.register_compiled("serve/test/b8", exe)
    models = perf.registered_cost_models()
    assert models["serve/test/b8"]["flops"] > 0
    perf.record_call("serve/test/b8", 5)
    assert perf.registered_cost_models()["serve/test/b8"]["calls"] == 5


def test_mfu_from_flops_divides_by_the_device_peak():
    """The one MFU definition ``bench.py`` (offline) and ``Perf/mfu`` (in-run) share."""
    device = jax.devices()[0]
    flops, steps_per_sec = 4.2e9, 12.5
    expected = flops * steps_per_sec / perf.peak_flops(device)
    assert math.isclose(perf.mfu_from_flops(flops, steps_per_sec, device), expected)
    assert perf.peak_flops(device) > 0 and perf.peak_hbm_bw(device) > 0


def test_instrument_compiles_once_and_reports_a_failed_registration(tmp_path, caplog):
    """Registration reads ``Compiled.cost_analysis()`` (the form every backend
    has) from the executable the first call reuses — ONE backend compile — and a
    hot path whose cost model cannot be read is named in the log and in
    ``perf_report.json`` instead of silently dropping out of ``Perf/mfu``."""
    from sheeprl_tpu.obs.watchdog import RecompileWatchdog

    cfg = {"obs": {"perf": {"enabled": True}}}
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 3.0)
    x = jnp.ones((24, 24), jnp.float32)
    jax.block_until_ready(x)
    dog = RecompileWatchdog()
    try:
        jax.block_until_ready(perf.instrument(cfg, "once/step", step)(x))
        assert dog.total_compiles == 1
    finally:
        dog.close()
    assert perf.registered_cost_models()["once/step"]["flops"] > 0
    assert perf.registration_failures() == {}

    class _NoCostModel:
        def lower(self, *args):
            raise NotImplementedError("no cost model on this backend")

        def __call__(self, x):
            return x

    with caplog.at_level("WARNING", logger="sheeprl_tpu.obs.perf"):
        wrapped = perf.instrument(cfg, "broken/step", _NoCostModel())
        assert wrapped(x) is x and wrapped(x) is x
    assert len([r for r in caplog.records if "broken/step" in r.getMessage()]) == 1
    assert "NotImplementedError" in perf.registration_failures()["broken/step"]
    plane = PerfPlane(cfg)
    path = str(tmp_path / "perf_report.json")
    plane.write_report(path)
    assert "broken/step" in json.load(open(path))["registration_failures"]


def test_peak_flops_table_device_kinds():
    class _Dev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    assert perf.peak_flops(_Dev("TPU v4")) == perf.PEAK_FLOPS["TPU v4"]
    assert perf.peak_flops(_Dev("TPU v5 lite")) == perf.PEAK_FLOPS["TPU v5 lite"]
    # an accelerator the table does not know is an error, never a guessed peak
    with pytest.raises(perf.UnknownDeviceError, match="TPU v9"):
        perf.peak_flops(_Dev("TPU v9"))
    with pytest.raises(perf.UnknownDeviceError, match="TPU v9"):
        perf.peak_hbm_bw(_Dev("TPU v9"))
    assert 0 < perf.peak_flops(_Dev("cpu", platform="cpu")) < 1e12


# ------------------------------------------------------------ PerfPlane flush
def test_perf_plane_flush_emits_gauges_and_report(tmp_path):
    cfg = {"obs": {"perf": {"enabled": True}}}

    @jax.jit
    def step(x):
        return x @ x

    wrapped = perf.instrument(cfg, "plane/step", step)
    plane = PerfPlane(cfg)
    x = jnp.ones((32, 32), jnp.float32)
    jax.block_until_ready(wrapped(x))
    time.sleep(0.01)
    metrics = {"Time/train_time": 0.01}
    plane.flush(metrics)
    assert metrics["Perf/achieved_flops_per_sec"] > 0
    assert metrics["Perf/mfu"] > 0
    assert "Perf/goodput" in metrics and "Perf/anomalies" in metrics
    _assert_sums_to_one({c: metrics[f"Perf/goodput_{c}"] for c in GOODPUT_CATEGORIES})

    path = str(tmp_path / "perf_report.json")
    assert plane.write_report(path) == path
    report = json.load(open(path))
    assert report["mfu"] > 0
    assert report["total_flops"] > 0
    assert "plane/step" in report["cost_models"]
    _assert_sums_to_one(report["goodput_fractions"])


def test_perf_plane_disabled_is_inert(tmp_path):
    plane = PerfPlane({"obs": {"perf": {"enabled": False}}})
    assert plane.observe_step() is None
    metrics = {}
    plane.flush(metrics)
    assert metrics == {}
    assert plane.write_report(str(tmp_path / "nope.json")) is None
    assert not (tmp_path / "nope.json").exists()


# ----------------------------------------------------------------- monitor e2e
def test_monitor_forced_slowdown_one_capture_and_report(tmp_path):
    """The acceptance scenario: a post-warmup slowdown sustained past
    ``sustain_steps`` fires EXACTLY ONE auto-capture and one ``perf_regression``
    flight-recorder event; close() writes perf_report.json with nonzero MFU and
    goodput fractions summing to 1.0."""
    cfg = {
        "algo": {"name": "test"},
        "obs": {
            "enabled": False,
            "flight_recorder": False,
            "perf": {
                "enabled": True,
                "regress_pct": 0.5,
                "warmup_steps": 3,
                "sustain_steps": 2,
                "ewma_alpha": 1.0,
                "max_captures": 1,
                "capture_updates": 2,
            },
        },
    }
    recorder = flight_recorder_mod.FlightRecorder(str(tmp_path))
    flight_recorder_mod.install(recorder)
    monitor = TrainingMonitor(cfg, log_dir=str(tmp_path))
    starts, stops = [], []
    monitor._start_capture = lambda: (starts.append(1), setattr(monitor, "_capturing", True))
    monitor._stop_capture = lambda: (stops.append(1), setattr(monitor, "_capturing", False))

    @jax.jit
    def step(x):
        return x @ x

    wrapped = perf.instrument(cfg, "monitor/step", step)
    x = jnp.ones((16, 16), jnp.float32)
    for _ in range(4):  # warmup: fast steps establish the baseline
        jax.block_until_ready(wrapped(x))
        monitor.advance()
        time.sleep(0.002)
    for _ in range(6):  # sustained ~25x degradation
        jax.block_until_ready(wrapped(x))
        monitor.advance()
        time.sleep(0.05)

    assert len(starts) == 1, "exactly one auto-capture per run"
    assert len(stops) == 1, "capture window must close after capture_updates"
    events = [e for e in recorder.events() if e.get("kind") == "perf_regression"]
    assert len(events) == 1
    assert events[0]["capture"] is True
    assert events[0]["degradation"] > 0.5

    metrics = {"Time/train_time": 0.3}
    monitor.log_metrics(None, metrics, step=1)
    assert "Perf/goodput" in metrics

    monitor.close()
    report_file = tmp_path / "perf_report.json"
    assert report_file.exists()
    report = json.load(open(report_file))
    assert report["mfu"] > 0
    assert report["anomalies"] == 1
    assert len(report["anomaly_events"]) == 1
    _assert_sums_to_one(report["goodput_fractions"])
    assert report["cost_models"]["monitor/step"]["calls"] == 10
