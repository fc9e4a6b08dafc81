"""The fleet manager: front + N replicas under one supervising loop.

``python -m sheeprl_tpu.supervise --serve serve.fleet.enabled=True ...`` lands
here (dispatched by :func:`sheeprl_tpu.fault.supervisor.supervise_serve`).  The
manager owns processes, not requests:

* it spawns the front (``python -m sheeprl_tpu.serve.fleet``) and
  ``serve.fleet.min_replicas`` replicas (each ``python -m sheeprl_tpu.serve``
  on an ephemeral port), writing a record file into
  ``<serve.fleet.dir>/replicas/`` once a replica's ready file appears — that is
  how the front admits it;
* every child death is classified the supervisor way: rc 75 (drained
  preemption) respawns immediately with a bumped generation; a crash backs off
  on the slot's *consecutive*-crash count (reset by any clean preemption) and
  is bounded by ``fault.max_retries`` per slot; a SIGKILL mid-flight is just a
  crash — the front reroutes the dead replica's in-flight requests while the
  manager respawns it, and the warm persistent compile cache makes the respawn
  cheap;
* the autoscaler (:class:`~sheeprl_tpu.serve.fleet.autoscale.AutoscaleDecider`)
  reads the front's ``front_status.json`` and grows the fleet on sustained
  queue depth / drains one replica (SIGTERM → rc 75 → slot retired) on
  sustained idle, between ``min_replicas`` and ``max_replicas``;
* ``serve.fleet.canary.spec`` adds a dedicated canary slot serving the
  candidate version (``serve.policies=[spec]``); it is never autoscaled away
  and the front routes the canary fraction to it.

Device placement is explicit (``distributed/chips.py``): the front runs no JAX;
every replica is a chip-holding child.  On a host with one chip the single
replica gets it; on a multi-chip host replica slot *i* is pinned to chip *i*;
on a host without TPUs (or under ``JAX_PLATFORMS=cpu``) replicas share the CPU
backend.  ``max_replicas`` (+ the canary) beyond the host's chip count is
refused at launch, and each spawn line says where the child runs.

Like every supervising loop, the manager writes a lifetime summary JSON
(``fault.summary_path`` / ``SHEEPRL_TPU_SUPERVISE_SUMMARY``) on ALL exit
paths: spawns, respawns, scale events, per-slot retry/preemption counts.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from sheeprl_tpu.distributed import chips
from sheeprl_tpu.fault import preemption as fault_preemption
from sheeprl_tpu.fault.counters import RESTARTS_ENV_VAR
from sheeprl_tpu.fault.preemption import RESUMABLE_EXIT_CODE
from sheeprl_tpu.fault.supervisor import (
    _strip_override,
    backoff_seconds,
    fault_cfg,
    write_supervisor_summary,
)
from sheeprl_tpu.serve.fleet.autoscale import AutoscaleDecider
from sheeprl_tpu.serve.fleet.front import RECORDS_SUBDIR

#: Env var carrying the replica's fleet slot index (telemetry row identity).
SERVE_SLOT_ENV_VAR = "SHEEPRL_TPU_SERVE_SLOT"


@dataclass
class _Slot:
    name: str  # "front", "replica<N>", "canary0"
    index: int  # telemetry slot id (SHEEPRL_TPU_SERVE_SLOT)
    role: str  # "front" | "replica"
    canary: bool = False
    proc: Optional[subprocess.Popen] = None
    generation: int = 0  # bumped per respawn → fresh telemetry lineage
    retries: int = 0  # total crashes, bounded by fault.max_retries
    consecutive: int = 0  # backoff input; reset by a clean preemption
    preemptions: int = 0
    desired: bool = True  # False once scale-down / abandonment retired it
    abandoned: bool = False
    ready_recorded: bool = False
    next_spawn_at: float = 0.0  # monotonic; crash backoff scheduling
    ready_file: Optional[Path] = None
    record_path: Optional[Path] = None


class FleetManager:
    def __init__(self, overrides: List[str], cfg: Any):
        self.overrides = list(overrides)
        self.cfg = cfg
        fleet_cfg = cfg.serve.fleet
        self.fleet_cfg = fleet_cfg
        self.fleet_dir = (
            Path(str(fleet_cfg.dir))
            if fleet_cfg.dir
            else Path(tempfile.mkdtemp(prefix="sheeprl_fleet_"))
        )
        self.fleet_dir.mkdir(parents=True, exist_ok=True)
        self.records_dir = self.fleet_dir / RECORDS_SUBDIR
        self.records_dir.mkdir(parents=True, exist_ok=True)
        self.min_replicas = int(fleet_cfg.min_replicas)
        self.max_replicas = int(fleet_cfg.max_replicas)
        self.decider = AutoscaleDecider(
            min_replicas=self.min_replicas,
            max_replicas=self.max_replicas,
            scale_up_queue_depth=float(fleet_cfg.scale_up_queue_depth),
            scale_up_after_s=float(fleet_cfg.scale_up_after_s),
            scale_down_after_s=float(fleet_cfg.scale_down_after_s),
            cooldown_s=float(fleet_cfg.scale_cooldown_s),
        )
        canary_cfg = fleet_cfg.get("canary") or {}
        self.canary_spec: Optional[str] = (
            str(canary_cfg.get("spec")) if canary_cfg.get("spec") else None
        )
        f_cfg = fault_cfg(cfg)
        self.f_cfg = f_cfg
        self.max_retries = int(f_cfg.get("max_retries", 3))
        self.max_preemptions = f_cfg.get("max_preemptions")
        self.base_backoff = float(f_cfg.get("backoff_s", 2.0))
        self.max_backoff = float(f_cfg.get("backoff_max_s", 60.0))
        self.drain_timeout_s = float(cfg.serve.drain_timeout_s)

        # Every replica slot the fleet can ever fill holds a chip of its own.
        self.chips = chips.check_chip_budget(
            self.max_replicas + (1 if self.canary_spec else 0),
            "serve.fleet.max_replicas" + (" + the canary" if self.canary_spec else ""),
        )

        self.slots: Dict[str, _Slot] = {}
        self.fleet = None  # FleetAggregator (obs.fleet.dir)
        self.trace_id: Optional[str] = None
        self.summary: Dict[str, Any] = {
            "mode": "fleet",
            "fleet_dir": str(self.fleet_dir),
            "events": [],
            "scale_ups": 0,
            "scale_downs": 0,
            "slots": {},
            "outcome": None,
            "rc": None,
        }

    # ------------------------------------------------------------------- argv
    def _front_argv(self) -> List[str]:
        ov, _ = _strip_override(self.overrides, "serve.fleet.dir")
        ov = ov + [f"serve.fleet.dir={self.fleet_dir}"]
        if not self.fleet_cfg.ready_file:
            ov, _ = _strip_override(ov, "serve.fleet.ready_file")
            ov += [f"serve.fleet.ready_file={self.fleet_dir / 'front_ready.json'}"]
        if not self.fleet_cfg.summary_path:
            ov, _ = _strip_override(ov, "serve.fleet.summary_path")
            ov += [f"serve.fleet.summary_path={self.fleet_dir / 'front_summary.json'}"]
        return [sys.executable, "-m", "sheeprl_tpu.serve.fleet"] + ov

    def _replica_argv(self, slot: _Slot) -> List[str]:
        ov = list(self.overrides)
        for key in ("serve.port", "serve.ready_file", "serve.summary_path"):
            ov, _ = _strip_override(ov, key)
        ov += [
            "serve.port=0",
            f"serve.ready_file={slot.ready_file}",
            f"serve.summary_path={self.fleet_dir / (slot.name + '_summary.json')}",
        ]
        if slot.canary:
            ov, _ = _strip_override(ov, "serve.policies")
            ov += [f"serve.policies=[{self.canary_spec}]"]
        return [sys.executable, "-m", "sheeprl_tpu.serve"] + ov

    # ------------------------------------------------------------------ spawning
    def _make_slot(self, name: str, index: int, role: str, canary: bool = False) -> _Slot:
        slot = _Slot(
            name=name,
            index=index,
            role=role,
            canary=canary,
            ready_file=self.fleet_dir / f"{name}_ready.json",
            record_path=(self.records_dir / f"{name}.json") if role == "replica" else None,
        )
        self.slots[name] = slot
        return slot

    def _slot_env(self, slot: _Slot) -> Dict[str, str]:
        """Device placement by slot: the front runs no policy (CPU backend);
        a replica holds a chip — on a multi-chip host the chip whose id is the
        slot index, which is unique among live replicas (the canary sits at
        ``max_replicas``)."""
        if slot.role == "front":
            return chips.cpu_env(os.environ)
        return chips.accelerator_env(os.environ, chip=slot.index if self.chips > 1 else None)

    def _spawn(self, slot: _Slot) -> None:
        if slot.ready_file is not None:
            slot.ready_file.unlink(missing_ok=True)
        if slot.record_path is not None:
            slot.record_path.unlink(missing_ok=True)
        slot.ready_recorded = False
        env = self._slot_env(slot)
        env[RESTARTS_ENV_VAR] = str(slot.generation)
        if slot.role == "replica":
            env[SERVE_SLOT_ENV_VAR] = str(slot.index)
        from sheeprl_tpu.obs.fleet import FLEET_ENV_VAR, TRACE_ID_ENV_VAR

        env.pop(FLEET_ENV_VAR, None)
        if self.fleet is not None:
            env[FLEET_ENV_VAR] = self.fleet.address
        if self.trace_id:
            env[TRACE_ID_ENV_VAR] = self.trace_id
        argv = self._front_argv() if slot.role == "front" else self._replica_argv(slot)
        slot.proc = subprocess.Popen(argv, env=env)
        self._event("spawn", slot, generation=slot.generation, pid=slot.proc.pid)
        self._log(
            f"spawned {slot.name} (gen {slot.generation}, pid {slot.proc.pid}) on {chips.describe(env)}"
        )

    def _event(self, kind: str, slot: Optional[_Slot] = None, **extra: Any) -> None:
        row = {"kind": kind, "time": time.time(), **extra}
        if slot is not None:
            row["slot"] = slot.name
        self.summary["events"].append(row)

    # ------------------------------------------------------------------- lifecycle
    def _check_ready(self) -> None:
        """Replica ready file → record file: the front's admission signal."""
        for slot in self.slots.values():
            if (
                slot.ready_recorded
                or slot.proc is None
                or slot.ready_file is None
                or not slot.ready_file.is_file()
            ):
                continue
            try:
                ready = json.loads(slot.ready_file.read_text())
            except (OSError, ValueError):
                continue
            slot.ready_recorded = True
            self._event("ready", slot, generation=slot.generation)
            if slot.record_path is not None:
                record = {
                    "name": slot.name,
                    "host": ready.get("host", "127.0.0.1"),
                    "port": int(ready.get("port", 0)),
                    "canary": slot.canary,
                    "generation": slot.generation,
                    "pid": slot.proc.pid,
                }
                tmp = slot.record_path.with_suffix(".tmp")
                tmp.write_text(json.dumps(record, indent=2))
                os.replace(tmp, slot.record_path)
                self._log(f"{slot.name} ready at {record['host']}:{record['port']}")

    def _reap(self) -> Optional[int]:
        """Classify child deaths.  Returns an exit code when the fleet is done."""
        for slot in list(self.slots.values()):
            if slot.proc is None or slot.proc.poll() is None:
                continue
            rc = slot.proc.returncode
            slot.proc = None
            slot.ready_recorded = False
            if slot.record_path is not None:
                slot.record_path.unlink(missing_ok=True)
            if not slot.desired:
                # The drain we asked for (scale-down): the slot retires.
                self._event("retired", slot, rc=rc)
                self._log(f"{slot.name} retired (rc={rc})")
                del self.slots[slot.name]
                continue
            if rc == RESUMABLE_EXIT_CODE:
                slot.preemptions += 1
                slot.consecutive = 0  # a correct drain proves the binary healthy
                self._event("preemption", slot, rc=rc)
                if (
                    self.max_preemptions is not None
                    and slot.preemptions > int(self.max_preemptions)
                ):
                    self._log(f"{slot.name} exceeded fault.max_preemptions; giving up")
                    return self._finish("preemption_budget", rc)
                slot.generation += 1
                slot.next_spawn_at = 0.0  # respawn immediately: down = lost capacity
                self._log(f"{slot.name} drained on preemption; respawning immediately")
                continue
            if rc == 0 and slot.role == "front":
                self._log("front shut down cleanly; stopping the fleet")
                return self._finish("clean", 0)
            # Crash (or an unexpected clean replica exit — same respawn path,
            # but a true crash consumes the retry budget and backs off).
            if rc != 0:
                slot.retries += 1
                slot.consecutive += 1
                self._event("crash", slot, rc=rc)
                if self.fleet is not None:
                    try:
                        self.fleet.collect_blackboxes(f"{slot.name}_rc{rc}")
                    except Exception:
                        pass
                if slot.retries > self.max_retries:
                    slot.abandoned = True
                    slot.desired = False
                    self._event("abandoned", slot, rc=rc)
                    self._log(f"{slot.name} exceeded fault.max_retries={self.max_retries}")
                    if slot.role == "front" or not self._live_or_pending_replicas():
                        return self._finish("retry_budget", rc if rc else 1)
                    continue
                delay = backoff_seconds(slot.consecutive, self.base_backoff, self.max_backoff)
                self._log(
                    f"{slot.name} died (rc={rc}); retry {slot.retries}/{self.max_retries} "
                    f"(consecutive crash {slot.consecutive}) in {delay:.1f}s"
                )
            else:
                self._event("clean_exit", slot, rc=rc)
                delay = 0.0
            slot.generation += 1
            slot.next_spawn_at = time.monotonic() + delay
        return None

    def _live_or_pending_replicas(self) -> bool:
        return any(
            s.role == "replica" and s.desired and not s.canary for s in self.slots.values()
        )

    def _respawn_due(self) -> None:
        now = time.monotonic()
        for slot in self.slots.values():
            if slot.desired and slot.proc is None and not slot.abandoned and now >= slot.next_spawn_at:
                self._spawn(slot)

    # ------------------------------------------------------------------ autoscale
    def _free_replica_index(self) -> int:
        used = {s.index for s in self.slots.values() if s.role == "replica" and not s.canary}
        i = 0
        while i in used:
            i += 1
        return i

    def _autoscale(self) -> None:
        status = self._read_front_status()
        if status is None:
            return
        live = sum(
            1
            for s in self.slots.values()
            if s.role == "replica" and not s.canary and s.desired and s.ready_recorded
        )
        decision = self.decider.decide(time.monotonic(), live, float(status.get("pending", 0)))
        if decision == "up" and live < self.max_replicas:
            index = self._free_replica_index()
            slot = self._make_slot(f"replica{index}", index, "replica")
            # Warm scale-up: the persistent compile cache means the new replica
            # deserializes its ladder instead of compiling it.
            self._spawn(slot)
            self.summary["scale_ups"] += 1
            self._event("scale_up", slot, live=live)
            self._log(f"scale up -> {slot.name} (live {live} -> {live + 1})")
        elif decision == "down" and live > self.min_replicas:
            candidates = [
                s
                for s in self.slots.values()
                if s.role == "replica" and not s.canary and s.desired and s.proc is not None
                and s.ready_recorded
            ]
            if not candidates:
                return
            victim = max(candidates, key=lambda s: s.index)
            victim.desired = False
            try:
                victim.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
            self.summary["scale_downs"] += 1
            self._event("scale_down", victim, live=live)
            self._log(f"scale down -> draining {victim.name} (live {live} -> {live - 1})")

    def _read_front_status(self) -> Optional[Dict[str, Any]]:
        try:
            return json.loads((self.fleet_dir / "front_status.json").read_text())
        except (OSError, ValueError):
            return None

    # ------------------------------------------------------------------ main loop
    def run(self) -> int:
        from sheeprl_tpu.obs.fleet import TRACE_ID_ENV_VAR, FleetAggregator, new_trace_id

        self.trace_id = os.environ.get(TRACE_ID_ENV_VAR) or new_trace_id()
        fault_preemption.install_signal_handlers()  # SIGTERM -> orderly fleet drain
        obs_fleet = dict((self.cfg.get("obs") or {}).get("fleet") or {})
        if bool(obs_fleet.get("enabled", True)) and obs_fleet.get("dir"):
            try:
                self.fleet = FleetAggregator(
                    str(obs_fleet["dir"]),
                    liveness_timeout_s=float(obs_fleet.get("liveness_timeout_s", 10.0)),
                    trace_id=self.trace_id,
                    max_timeline_mb=float(obs_fleet.get("max_timeline_mb", 64.0)),
                )
                self._log(f"fleet telemetry at {self.fleet.address} -> {obs_fleet['dir']}")
            except OSError as e:
                self._log(f"fleet telemetry disabled: {e}")
        try:
            self._spawn(self._make_slot("front", 0, "front"))
            for i in range(self.min_replicas):
                self._spawn(self._make_slot(f"replica{i}", i, "replica"))
            if self.canary_spec:
                # The canary slot id sits past max_replicas so it never collides
                # with an autoscaled incumbent's telemetry row.
                self._spawn(self._make_slot("canary0", self.max_replicas, "replica", canary=True))
            while not fault_preemption.preemption_requested():
                time.sleep(0.2)
                self._check_ready()
                done = self._reap()
                if done is not None:
                    return done
                self._respawn_due()
                self._autoscale()
            self._log("preempted; draining the fleet")
            return self._finish("preempted", self._shutdown_children())
        except BaseException:
            if self.summary["outcome"] is None:
                self.summary["outcome"] = "supervisor_crashed"
            raise
        finally:
            self._kill_stragglers()
            for slot in self.slots.values():
                self.summary["slots"][slot.name] = {
                    "role": slot.role,
                    "canary": slot.canary,
                    "generation": slot.generation,
                    "retries": slot.retries,
                    "preemptions": slot.preemptions,
                    "abandoned": slot.abandoned,
                }
            write_supervisor_summary(self.f_cfg, self.summary)
            if self.fleet is not None:
                self.fleet.close()

    def _finish(self, outcome: str, rc: int) -> int:
        self.summary["outcome"] = outcome
        self.summary["rc"] = rc
        return rc

    def _shutdown_children(self) -> int:
        """Orderly drain: the front first (clients see ``draining`` and every
        in-flight request flushes through the replicas), replicas after."""
        order = sorted(self.slots.values(), key=lambda s: 0 if s.role == "front" else 1)
        for slot in order:
            if slot.proc is not None and slot.proc.poll() is None:
                try:
                    slot.proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + self.drain_timeout_s + 5.0
        for slot in order:
            if slot.proc is None:
                continue
            try:
                slot.proc.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                pass
        return 0

    def _kill_stragglers(self) -> None:
        for slot in self.slots.values():
            if slot.proc is not None and slot.proc.poll() is None:
                try:
                    slot.proc.kill()
                    slot.proc.wait(timeout=5)
                except (OSError, subprocess.TimeoutExpired):
                    pass

    @staticmethod
    def _log(msg: str) -> None:
        print(f"[fleet] {msg}", flush=True)


def supervise_fleet(overrides: List[str], cfg: Any = None) -> int:
    """Entry point for ``supervise --serve`` with ``serve.fleet.enabled=True``."""
    if cfg is None:
        from sheeprl_tpu.config.core import compose

        cfg = compose(config_name="serve_cli", overrides=overrides)
    return FleetManager(overrides, cfg).run()
