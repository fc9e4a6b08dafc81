"""``EnvPool``: a shared-memory, multi-process, fault-tolerant vector env.

The gap it closes: ``gym.vector.SyncVectorEnv`` steps envs serially on the host
thread and ``AsyncVectorEnv`` pays a pickle round-trip per step; at DreamerV3
walker shapes that is single-core MuJoCo+GL time during which the device sits
idle.  ``EnvPool`` runs one worker process per env
*group*, all groups stepping concurrently, with obs/reward/done slabs in shared
memory (``shared.py``) so the per-step host cost is a pipe ack and a memcpy.

Semantics are a drop-in for the existing
``SyncVectorEnv(..., autoreset_mode=SAME_STEP)`` path (``utils/env.py``):
identical batched obs layout, float64 rewards, ``final_obs``/``final_info``
payloads merged through the same ``VectorEnv._add_info`` aggregation, and
identical seeding (``reset(seed=s)`` seeds env ``i`` with ``s + i``) — the
tier-1 parity tests assert bit-equality against ``SyncVectorEnv``.

Robustness layer:

* **step timeout** — a worker that does not ack within ``step_timeout_s`` is
  declared hung, killed and restarted;
* **heartbeat watchdog** — each worker stamps a shared timestamp from a daemon
  thread; a stale stamp (dead process) is detected even between commands;
* **automatic restart** — a replacement worker is forked, its envs rebuilt and
  reseeded deterministically (base seed + a generation offset), and the
  affected envs surface the boundary as ``truncated=True`` with
  ``info["rollout_restart"]`` (the ``RestartOnException`` convention, so every
  training loop's ordinary done path marks the episode boundary);
* **restart budget** — more than ``max_restarts`` restarts over the pool's
  lifetime raises ``RolloutAbortError`` after a clean teardown.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

import gymnasium as gym
import numpy as np
from gymnasium.vector import AutoresetMode, VectorEnv
from gymnasium.vector.utils import batch_space

from sheeprl_tpu.obs import flight_recorder
from sheeprl_tpu.obs.tracer import span
from sheeprl_tpu.rollout.shared import RolloutSlabs
from sheeprl_tpu.rollout.worker import worker_entry


class RolloutAbortError(RuntimeError):
    """Raised when the worker-restart budget is exhausted: the env fleet is
    persistently failing and continuing would silently corrupt training data."""


class _WorkerTimeout(Exception):
    pass


class _WorkerCrashed(Exception):
    pass


class _Worker:
    """Parent-side handle: process + pipe + env-index range + restart generation."""

    __slots__ = ("idx", "first", "env_fns", "proc", "conn", "generation", "failed", "restarts", "timeouts", "crashes")

    def __init__(self, idx: int, first: int, env_fns: Sequence[Callable]):
        self.idx = idx
        self.first = first
        self.env_fns = list(env_fns)
        self.proc: Optional[mp.Process] = None
        self.conn = None
        self.generation = 0
        self.failed = False
        # Per-worker fault ledger, quoted in the RolloutAbortError post-mortem.
        self.restarts = 0
        self.timeouts = 0
        self.crashes = 0

    @property
    def num_envs(self) -> int:
        return len(self.env_fns)

    @property
    def env_indices(self) -> range:
        return range(self.first, self.first + len(self.env_fns))


# Deterministic reseed offset per restart generation (prime, so overlapping
# worker seed ranges don't re-collide after a restart).
_RESEED_STRIDE = 7919


class EnvPool(VectorEnv):
    def __init__(
        self,
        env_fns: Sequence[Callable[[], gym.Env]],
        num_workers: Optional[int] = None,
        step_timeout_s: float = 60.0,
        heartbeat_interval_s: float = 2.0,
        max_restarts: int = 3,
        restart_backoff_s: float = 0.5,
        start_method: Optional[str] = None,
        autoreset_mode: AutoresetMode = AutoresetMode.SAME_STEP,
        observation_space: Optional[gym.Space] = None,
        action_space: Optional[gym.Space] = None,
    ):
        super().__init__()
        if autoreset_mode != AutoresetMode.SAME_STEP:
            raise ValueError(f"EnvPool implements SAME_STEP autoreset only, got {autoreset_mode}")
        if not env_fns:
            raise ValueError("EnvPool needs at least one env_fn")
        self.env_fns = list(env_fns)
        self.num_envs = len(self.env_fns)
        self.autoreset_mode = autoreset_mode
        self.step_timeout_s = float(step_timeout_s)
        self.heartbeat_interval_s = float(heartbeat_interval_s)
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)

        start_method = start_method or "fork"
        if start_method != "fork":
            # Thunks are closures (make_env) — only fork can ship them to workers.
            raise ValueError(
                f"EnvPool requires the 'fork' start method (env thunks are closures); got {start_method!r}"
            )
        self._ctx = mp.get_context(start_method)

        if observation_space is None or action_space is None:
            # Probe one env for the spaces/metadata, AsyncVectorEnv-style.
            probe = self.env_fns[0]()
            observation_space = observation_space or probe.observation_space
            action_space = action_space or probe.action_space
            self.metadata = dict(getattr(probe, "metadata", {}) or {})
            self.spec = getattr(probe, "spec", None)
            probe.close()
        self.single_observation_space = observation_space
        self.single_action_space = action_space
        self.observation_space = batch_space(observation_space, self.num_envs)
        self.action_space = batch_space(action_space, self.num_envs)
        self.metadata = {**getattr(self, "metadata", {}), "autoreset_mode": autoreset_mode}

        cpus = os.cpu_count() or 1
        if num_workers is None:
            num_workers = min(self.num_envs, max(cpus, 1))
        num_workers = max(1, min(int(num_workers), self.num_envs))
        self.num_workers = num_workers

        # Contiguous groups, sizes differing by at most one.
        base, extra = divmod(self.num_envs, num_workers)
        self._workers: List[_Worker] = []
        first = 0
        for w in range(num_workers):
            n = base + (1 if w < extra else 0)
            self._workers.append(_Worker(w, first, self.env_fns[first : first + n]))
            first += n

        self._slabs = RolloutSlabs(self.single_observation_space, self.single_action_space, self.num_envs, num_workers)
        self._views = self._slabs.views()
        self._env_seeds: List[Optional[int]] = [None] * self.num_envs
        self._reset_options: Optional[dict] = None
        self._step_pending = False
        self.closed = False

        # Rollout/* counters, surfaced by ``rollout_metrics``.
        self._total_restarts = 0
        self._timeout_restarts = 0
        self._crash_restarts = 0
        self._step_count = 0

        for w in self._workers:
            self._spawn(w)
        for w in self._workers:
            try:
                self._collect(w, self.step_timeout_s, expect="ready")
            except (_WorkerTimeout, _WorkerCrashed) as e:
                self.close(terminate=True)
                raise RuntimeError(f"EnvPool worker {w.idx} failed to start: {e}") from e

    # ------------------------------------------------------------------ process mgmt
    def _spawn(self, w: _Worker) -> None:
        parent_conn, child_conn = self._ctx.Pipe(duplex=True)
        w.conn = parent_conn
        w.failed = False
        w.proc = self._ctx.Process(
            target=worker_entry,
            args=(w.idx, w.first, w.env_fns, self._slabs, child_conn, self.heartbeat_interval_s, w.generation),
            name=f"envpool-worker-{w.idx}-gen{w.generation}",
            daemon=True,
        )
        w.proc.start()
        child_conn.close()

    def _kill(self, w: _Worker) -> None:
        if w.proc is not None and w.proc.is_alive():
            w.proc.terminate()
            w.proc.join(timeout=2.0)
            if w.proc.is_alive():
                w.proc.kill()
                w.proc.join(timeout=2.0)
        if w.conn is not None:
            try:
                w.conn.close()
            except Exception:
                pass
        w.conn = None
        w.proc = None

    def _send(self, w: _Worker, msg: tuple) -> None:
        try:
            w.conn.send(msg)
        except (BrokenPipeError, OSError) as e:
            raise _WorkerCrashed(f"worker {w.idx} pipe broken on send: {e}")

    def _collect(self, w: _Worker, timeout_s: float, expect: str = "ok"):
        """Wait for a worker ack, policing the timeout and process liveness."""
        deadline = time.monotonic() + timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise _WorkerTimeout(f"worker {w.idx} exceeded {timeout_s:.1f}s step timeout")
            if w.conn.poll(min(remaining, 0.2)):
                try:
                    msg = w.conn.recv()
                except (EOFError, OSError) as e:
                    raise _WorkerCrashed(f"worker {w.idx} pipe closed: {e}")
                if msg[0] == "error":
                    raise _WorkerCrashed(f"worker {w.idx} raised:\n{msg[1]}")
                if msg[0] != expect:
                    raise _WorkerCrashed(f"worker {w.idx} protocol violation: got {msg[0]!r}, wanted {expect!r}")
                return msg[1]
            if w.proc is None or not w.proc.is_alive():
                # Drain a final message that may have been sent before death.
                if w.conn.poll(0):
                    continue
                code = None if w.proc is None else w.proc.exitcode
                raise _WorkerCrashed(f"worker {w.idx} died (exitcode={code})")

    def _abort_post_mortem(self) -> str:
        """Per-worker fault ledger for the RolloutAbortError message: WHY the budget
        ran out, without the operator having to dig through metrics or event logs."""
        ages = self.heartbeat_ages()
        rows = []
        for w in self._workers:
            age = ages[w.idx] if w.idx < len(ages) else float("inf")
            age_s = f"{age:.1f}s" if np.isfinite(age) else "never"
            rows.append(
                f"worker {w.idx}: restarts={w.restarts} timeouts={w.timeouts} "
                f"crashes={w.crashes} last_heartbeat={age_s} ago"
            )
        return (
            f"totals: restarts={self._total_restarts} timeouts={self._timeout_restarts} "
            f"crashes={self._crash_restarts} over {self._step_count} steps; " + "; ".join(rows)
        )

    def heartbeat_ages(self) -> np.ndarray:
        """Seconds since each worker's last heartbeat stamp (inf before first beat)."""
        stamps = np.array(self._views.heartbeats, dtype=np.float64)
        now = time.time()
        ages = np.where(stamps > 0, now - stamps, np.inf)
        return ages

    # ------------------------------------------------------------------ restart
    def _worker_seeds(self, w: _Worker) -> List[Optional[int]]:
        offset = w.generation * _RESEED_STRIDE
        return [None if s is None else s + offset for s in (self._env_seeds[i] for i in w.env_indices)]

    def _restart(self, w: _Worker, reason: str) -> None:
        """Kill + replace a failed worker; its envs come back freshly reset with
        generation-offset seeds.  Raises ``RolloutAbortError`` past the budget."""
        with span("Rollout/restart"):
            while True:
                self._total_restarts += 1
                flight_recorder.record_event(
                    "rollout_restart",
                    worker=w.idx,
                    reason=reason,
                    restart=self._total_restarts,
                    budget=self.max_restarts,
                )
                if self._total_restarts > self.max_restarts:
                    post_mortem = self._abort_post_mortem()
                    self.close(terminate=True)
                    flight_recorder.record_event(
                        "rollout_abort", worker=w.idx, reason=reason, restarts=self._total_restarts
                    )
                    raise RolloutAbortError(
                        f"EnvPool exceeded max_restarts={self.max_restarts} "
                        f"(last failure: worker {w.idx}: {reason}); {post_mortem}"
                    )
                w.restarts += 1
                warnings.warn(f"EnvPool restarting worker {w.idx} ({reason}); restart {self._total_restarts}/{self.max_restarts}")
                self._kill(w)
                if self.restart_backoff_s > 0:
                    time.sleep(self.restart_backoff_s)
                w.generation += 1
                self._spawn(w)
                try:
                    self._collect(w, self.step_timeout_s, expect="ready")
                    self._send(w, ("reset", self._worker_seeds(w), self._reset_options))
                    self._collect(w, self.step_timeout_s)
                    w.failed = False
                    return
                except (_WorkerTimeout, _WorkerCrashed) as e:
                    reason = f"replacement failed: {e}"

    # ------------------------------------------------------------------ VectorEnv API
    def reset(self, *, seed=None, options=None):
        if seed is None:
            seeds: List[Optional[int]] = [None] * self.num_envs
        elif isinstance(seed, int):
            seeds = [seed + i for i in range(self.num_envs)]
        else:
            seeds = list(seed)
            if len(seeds) != self.num_envs:
                raise ValueError(f"got {len(seeds)} seeds for {self.num_envs} envs")
        self._env_seeds = seeds
        self._reset_options = dict(options) if options else None
        self._step_pending = False

        with span("Rollout/reset"):
            for w in self._workers:
                try:
                    self._send(w, ("reset", self._worker_seeds(w), self._reset_options))
                except _WorkerCrashed as e:
                    w.failed = True
                    self._restart(w, str(e))  # restart includes the reset
            payloads = self._gather(command="reset")
        infos = self._merge_infos(payloads)
        return self._views.read_obs_batch(), infos

    def step_async(self, actions) -> None:
        if self._step_pending:
            raise RuntimeError("step_async called with a step already pending")
        self._views.write_actions(actions)
        self._step_pending = True
        for w in self._workers:
            try:
                self._send(w, ("step",))
            except _WorkerCrashed:
                w.failed = True  # handled in step_wait

    def step_wait(self):
        if not self._step_pending:
            raise RuntimeError("step_wait called without step_async")
        with span("Rollout/step_wait"):
            payloads = self._gather(command="step")
        self._step_pending = False
        self._step_count += 1
        infos = self._merge_infos(payloads)
        return (
            self._views.read_obs_batch(),
            np.array(self._views.rewards, dtype=np.float64),
            np.array(self._views.terminated, dtype=np.bool_),
            np.array(self._views.truncated, dtype=np.bool_),
            infos,
        )

    def step(self, actions):
        with span("Rollout/step"):
            self.step_async(actions)
            return self.step_wait()

    def _gather(self, command: str) -> Dict[int, List[dict]]:
        """Collect all worker acks; on a hung/crashed worker, restart it and
        fabricate a truncated boundary for its envs."""
        per_env: Dict[int, List[dict]] = {}
        # Shared wall-clock start: workers run concurrently, so each gets the
        # full step budget measured from dispatch, not from its turn in the loop.
        deadline = time.monotonic() + self.step_timeout_s
        for w in self._workers:
            failure: Optional[str] = None
            if w.failed:
                failure = "pipe broken at dispatch"
            else:
                try:
                    payloads = self._collect(w, max(deadline - time.monotonic(), 0.01))
                    for gi, entries in payloads:
                        per_env[gi] = entries
                    continue
                except _WorkerTimeout as e:
                    self._timeout_restarts += 1
                    w.timeouts += 1
                    failure = str(e)
                    flight_recorder.record_event("rollout_timeout", worker=w.idx, error=failure)
                except _WorkerCrashed as e:
                    self._crash_restarts += 1
                    w.crashes += 1
                    failure = str(e)
                    flight_recorder.record_event("rollout_crash", worker=w.idx, error=failure)
            self._restart(w, failure)
            # The replacement reset its envs and wrote fresh obs to the slab;
            # surface the break as a truncation (RestartOnException convention).
            for gi in w.env_indices:
                self._views.rewards[gi] = 0.0
                self._views.terminated[gi] = False
                self._views.truncated[gi] = command == "step"
                per_env[gi] = [{"rollout_restart": True}]
        return per_env

    def _merge_infos(self, per_env: Dict[int, List[dict]]) -> dict:
        infos: dict = {}
        for gi in range(self.num_envs):
            for entry in per_env.get(gi, ()):
                infos = self._add_info(infos, entry, gi)
        return infos

    # ------------------------------------------------------------------ teardown
    def close_extras(self, terminate: bool = False, **kwargs) -> None:
        for w in self._workers:
            if w.proc is None:
                continue
            if terminate or w.failed or not w.proc.is_alive():
                self._kill(w)
                continue
            try:
                # A pending step's ack may still be in flight; drain it first.
                if self._step_pending and w.conn.poll(self.step_timeout_s):
                    w.conn.recv()
                self._send(w, ("close",))
                self._collect(w, timeout_s=5.0)
            except (_WorkerTimeout, _WorkerCrashed):
                pass
            finally:
                self._kill(w)
        self._step_pending = False

    def close(self, **kwargs) -> None:
        if getattr(self, "closed", True):
            return
        self.closed = True
        self.close_extras(**kwargs)

    def __del__(self):
        try:
            self.close(terminate=True)
        except Exception:
            pass

    # ------------------------------------------------------------------ telemetry
    @property
    def total_restarts(self) -> int:
        return self._total_restarts

    def rollout_metrics(self) -> Dict[str, float]:
        ages = self.heartbeat_ages()
        finite = ages[np.isfinite(ages)]
        return {
            "Rollout/worker_restarts": float(self._total_restarts),
            "Rollout/worker_timeouts": float(self._timeout_restarts),
            "Rollout/worker_crashes": float(self._crash_restarts),
            "Rollout/env_steps": float(self._step_count),
            "Rollout/num_workers": float(self.num_workers),
            "Rollout/heartbeat_age_max": float(finite.max()) if finite.size else 0.0,
        }


def rollout_metrics(envs: Any) -> Dict[str, float]:
    """``Rollout/*`` counters from a vector env, ``{}`` when it is not an EnvPool —
    lets every algo loop merge pool telemetry with one unconditional line."""
    fn = getattr(envs, "rollout_metrics", None)
    return fn() if callable(fn) else {}
