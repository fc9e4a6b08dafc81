"""TrainingMonitor: no-op fast path, capture window, and the PPO end-to-end smoke run
from the acceptance criteria (Chrome trace + Time/Memory/Compile metrics, no recompile
warnings)."""

import json
import pathlib
import warnings

import pytest

from sheeprl_tpu.obs import TrainingMonitor, tracer
from sheeprl_tpu.obs.watchdog import RecompileWarning
from sheeprl_tpu.utils.logger import TensorBoardLogger
from sheeprl_tpu.utils.timer import timer


def test_disabled_monitor_is_noop(tmp_path):
    timer.reset()  # the registry is process-wide: a run in an earlier file of this worker may have left a phase in it
    m = TrainingMonitor({"obs": {"enabled": False}}, str(tmp_path))
    assert not m.enabled
    assert tracer.get_active() is None  # no global tracer installed
    m.advance()
    assert m.metrics() == {}
    m.close()
    assert not list(tmp_path.iterdir())  # no trace export, no xprof dir

    class _Rec:
        def __init__(self):
            self.calls = []

        def log_metrics(self, metrics, step):
            self.calls.append((metrics, step))

    rec = _Rec()
    m.log_metrics(rec, {"a": 1.0}, 7)  # disabled monitor still forwards to the logger
    assert rec.calls == [({"a": 1.0}, 7)]


def test_enabled_monitor_spans_and_close(tmp_path):
    m = TrainingMonitor({"obs": {"enabled": True, "xprof_annotations": False}}, str(tmp_path), rank=0)
    try:
        assert tracer.get_active() is m.tracer
        m.advance()
        with m.span("Time/phase"):
            pass
        m.advance()
        out = m.metrics()
        assert "Time/phase/p50" in out
        assert "Compile/recompiles" in out
    finally:
        m.close()
    assert tracer.get_active() is None
    doc = json.load(open(tmp_path / "trace.json"))
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "Time/phase" in names
    assert "Time/update" in names  # advance() brackets each update in a top-level span
    m.close()  # idempotent


def test_rank_nonzero_trace_filename(tmp_path):
    m = TrainingMonitor({"obs": {"enabled": True, "xprof_annotations": False, "watchdog": False}}, str(tmp_path), rank=3)
    m.close()
    assert (tmp_path / "trace_rank3.json").is_file()


def test_capture_steps_validation(tmp_path):
    with pytest.raises(ValueError, match="capture_steps"):
        TrainingMonitor({"obs": {"enabled": True, "capture_steps": [3, 1]}}, str(tmp_path), rank=0)


def _tiny_ppo_args(tmp_path, extra=()):
    return [
        "exp=ppo",
        "env=discrete_dummy",
        "algo.mlp_keys.encoder=[state]",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.encoder.mlp_features_dim=8",
        "algo.total_steps=64",
        "algo.run_test=False",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "metric.log_every=1",
        f"log_root={tmp_path}",
        "buffer.memmap=False",
        *extra,
    ]


def test_ppo_smoke_with_observability(tmp_path, monkeypatch):
    from sheeprl_tpu.cli import run

    captured = []
    orig = TensorBoardLogger.log_metrics

    def _rec(self, metrics, step):
        captured.append(dict(metrics))
        orig(self, metrics, step)

    monkeypatch.setattr(TensorBoardLogger, "log_metrics", _rec)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run(
            _tiny_ppo_args(
                tmp_path,
                ["obs.enabled=True", "obs.telemetry_interval=0.0", "obs.capture_steps=[2,3]"],
            )
        )

    # (c) zero post-warmup recompile warnings
    assert not [w for w in caught if issubclass(w.category, RecompileWarning)]

    # (b) per-phase histogram metrics + memory/compile scalars reached the logger
    keys = set().union(*captured)
    assert "Time/env_interaction_time/p50" in keys
    assert "Time/train_time/p95" in keys
    assert "Time/h2d_transfer/p99" in keys
    assert any(k.startswith("Memory/") for k in keys)
    assert "Compile/recompiles" in keys and "Compile/total_compiles" in keys
    assert captured[-1]["Compile/recompiles"] == 0.0

    # (a) a valid Chrome-trace JSON in the run's version_* dir
    traces = list(pathlib.Path(tmp_path).rglob("version_*/trace.json"))
    assert len(traces) == 1
    doc = json.load(open(traces[0]))
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert events and all("ts" in e and "dur" in e and "pid" in e for e in events)
    names = {e["name"] for e in events}
    assert {"Time/env_interaction_time", "Time/train_time", "Time/h2d_transfer", "Time/update"} <= names

    # the programmatic capture window wrote an XProf trace
    assert list(pathlib.Path(tmp_path).rglob("xprof/**/*.xplane.pb"))

    # the monitor deactivated its tracer on close
    assert tracer.get_active() is None


def test_ppo_smoke_observability_disabled_leaves_no_artifacts(tmp_path):
    from sheeprl_tpu.cli import run

    run(_tiny_ppo_args(tmp_path))
    assert not list(pathlib.Path(tmp_path).rglob("trace.json"))
    assert not list(pathlib.Path(tmp_path).rglob("xprof"))
    assert tracer.get_active() is None
