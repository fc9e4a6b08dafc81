"""Logging: versioned run directories + TensorBoard writer.

Reference behavior (``sheeprl/utils/logger.py:12-89``): rank-0 creates a versioned log
dir ``logs/runs/<algo>/<env>/<timestamp>/version_N`` and broadcasts it to all ranks.  In
single-controller JAX there is one python process per host; the dir is created by
process 0 and shared via ``multihost_utils`` when running multi-host.
"""

from __future__ import annotations

import json
import os
import pathlib
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np


def get_log_dir(cfg: Dict[str, Any], root_dir: Optional[str] = None, run_name: Optional[str] = None) -> str:
    root_dir = root_dir if root_dir is not None else cfg["root_dir"]
    run_name = run_name if run_name is not None else cfg["run_name"]
    base = pathlib.Path(cfg.get("log_root", "logs")) / "runs" / root_dir / run_name
    if jax.process_index() == 0:
        base.mkdir(parents=True, exist_ok=True)
        versions = [int(p.name.split("_")[1]) for p in base.glob("version_*") if p.name.split("_")[-1].isdigit()]
        version = max(versions) + 1 if versions else 0
        log_dir = base / f"version_{version}"
        log_dir.mkdir(parents=True, exist_ok=True)
        path = str(log_dir)
    else:
        path = ""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        path = multihost_utils.broadcast_one_to_all(
            np.frombuffer(path.ljust(512).encode(), dtype=np.uint8)
        )
        path = bytes(np.asarray(path)).decode().rstrip()
    return path


class TensorBoardLogger:
    """Minimal TB scalar writer; uses tensorboard's SummaryWriter when available and
    falls back to JSONL so logging never becomes a hard dependency."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._writer = None
        self._jsonl = None
        self._closed = False
        if jax.process_index() != 0:
            return
        try:
            # tensorboardX first: pure-python writer.  torch.utils.tensorboard pulls
            # in a TensorFlow runtime whose GL-adjacent symbols segfault MuJoCo's
            # EGL renderer in-process (dm_control pixel envs).
            from tensorboardX import SummaryWriter

            self._writer = SummaryWriter(log_dir=log_dir)
        except Exception:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._writer = SummaryWriter(log_dir=log_dir)
            except Exception:
                self._jsonl = open(os.path.join(log_dir, "metrics.jsonl"), "a")

    def log_metrics(self, metrics: Dict[str, float], step: int) -> None:
        if jax.process_index() != 0 or self._closed:
            return
        if self._writer is not None:
            for k, v in metrics.items():
                self._writer.add_scalar(k, float(v), global_step=step)
        elif self._jsonl is not None:
            self._jsonl.write(json.dumps({"step": step, "time": time.time(), **metrics}) + "\n")
            self._jsonl.flush()

    def log_hyperparams(self, cfg: Dict[str, Any]) -> None:
        if self._writer is not None:
            try:
                self._writer.add_text("config", "```yaml\n" + json.dumps(cfg, default=str, indent=2) + "\n```")
            except Exception:
                pass

    def close(self) -> None:
        self._closed = True
        if self._writer is not None:
            self._writer.close()
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None


def read_scalars(log_dir: os.PathLike) -> Dict[str, List[float]]:
    """``{tag: [values in write order]}`` from the TensorBoard event files
    :class:`TensorBoardLogger` wrote into ``log_dir``.  Parsed with the protobuf
    schema of the library that wrote them (tensorboardX), so a process that
    holds the chip reads its own metrics back without importing TensorFlow (what
    ``tensorboard``'s ``EventAccumulator`` does)."""
    import struct

    from tensorboardX.proto import event_pb2

    out: Dict[str, List[float]] = {}
    for path in sorted(pathlib.Path(log_dir).glob("events.out.tfevents.*")):
        data = path.read_bytes()
        pos = 0
        # TFRecord framing: u64 length, u32 crc, payload, u32 crc
        while pos + 12 <= len(data):
            (length,) = struct.unpack("<Q", data[pos : pos + 8])
            event = event_pb2.Event()
            event.ParseFromString(data[pos + 12 : pos + 12 + length])
            pos += 12 + length + 4
            for value in event.summary.value:
                if value.HasField("simple_value"):
                    out.setdefault(value.tag, []).append(float(value.simple_value))
    return out


class MlflowLogger:
    """MLflow experiment-tracking logger (reference ``utils/logger.py:12-36`` +
    ``configs/logger/mlflow.yaml:1``), sharing the run-dir contract with the TB
    logger: the versioned ``log_dir`` still holds config.yaml/checkpoints; metrics
    additionally stream to the MLflow tracking server.  Rank-0 only, like the
    reference's rank-zero-experiment guard."""

    def __init__(
        self,
        log_dir: str,
        tracking_uri: Optional[str] = None,
        experiment_name: Optional[str] = None,
        run_name: Optional[str] = None,
        run_id: Optional[str] = None,
    ):
        self.log_dir = log_dir
        self._run = None
        if jax.process_index() != 0:
            return
        import mlflow  # guarded by get_logger

        self._mlflow = mlflow
        if tracking_uri or os.environ.get("MLFLOW_TRACKING_URI"):
            mlflow.set_tracking_uri(tracking_uri or os.environ["MLFLOW_TRACKING_URI"])
        if experiment_name:
            mlflow.set_experiment(experiment_name)
        self._run = mlflow.start_run(run_id=run_id, run_name=run_name)
        self.run_id = self._run.info.run_id

    def log_metrics(self, metrics: Dict[str, float], step: int) -> None:
        if self._run is None:
            return
        self._mlflow.log_metrics({k: float(v) for k, v in metrics.items()}, step=int(step))

    def log_hyperparams(self, cfg: Dict[str, Any]) -> None:
        if self._run is None:
            return

        def _flatten(d, prefix=""):
            out = {}
            for k, v in d.items():
                key = f"{prefix}{k}"
                if isinstance(v, dict):
                    out.update(_flatten(v, key + "."))
                else:
                    out[key] = str(v)[:500]  # mlflow param value limit
            return out

        try:
            self._mlflow.log_params(_flatten(dict(cfg)))
        except Exception:
            pass  # params exceeding server limits must not kill the run

    def close(self) -> None:
        if self._run is not None:
            self._mlflow.end_run()
            self._run = None


def get_logger(cfg: Dict[str, Any], log_dir: str) -> Optional[TensorBoardLogger]:
    if cfg.get("metric", {}).get("log_level", 1) == 0:
        return None
    logger_cfg = cfg.get("logger", {}) or {}
    if logger_cfg.get("name") == "mlflow":
        from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

        if not _IS_MLFLOW_AVAILABLE:
            raise ModuleNotFoundError(
                "logger=mlflow requires the 'mlflow' package (reference guards it the "
                "same way, utils/imports.py); install it or use logger=default"
            )
        return MlflowLogger(
            log_dir,
            tracking_uri=logger_cfg.get("tracking_uri"),
            experiment_name=logger_cfg.get("experiment_name"),
            run_name=logger_cfg.get("run_name"),
            run_id=logger_cfg.get("run_id"),
        )
    return TensorBoardLogger(log_dir)
