"""Sebulba launcher: ``python -m sheeprl_tpu.sebulba <overrides>``.

The PR-10 autoresume supervisor grown into a *process manager*: instead of
relaunching one training process on death, it places and babysits a whole
topology — one learner plus ``distributed.num_actors`` actor processes, each an
ordinary ``python -m sheeprl_tpu`` run with role overrides stamped on (so every
child gets the full CLI pipeline: config compose, chaos install, flight
recorder, blackbox dumps).

Lifecycle policy:

* the **learner** is the run: when it exits, everything exits with its code;
  the launcher never respawns it (that remains ``sheeprl_tpu.supervise``'s job,
  which can wrap this launcher exactly like any other run).
* an **actor** that dies (chaos SIGKILL, OOM, env crash) is respawned with a
  bumped ``SHEEPRL_TPU_ACTOR_GENERATION`` after bounded backoff
  (``distributed.respawn_backoff_s``, ``distributed.max_actor_respawns``,
  reusing the supervisor's ``backoff_seconds`` curve).  A respawned actor
  reconnects, receives the freshest params as a welcome publish, and refills
  its replay shard from scratch.  An actor that exits 0 is done.
* a slot whose respawn budget is exhausted is **abandoned**: the launcher
  connects to the learner and sends an ``abandon`` control message so the
  learner stops waiting for that slot instead of starving.

Device placement is explicit, never inherited (``distributed/chips.py``): the
learner is the one chip-holding child and keeps the launcher's own platform
setting; every actor is placed on the CPU backend (``JAX_PLATFORMS=cpu``) —
actors step environments and run a small policy forward, and a chip belongs to
one process at a time.  Each spawn line says where the child runs.  The
launcher itself never imports JAX.

Children write their logs into distinct run dirs — the learner keeps the pinned
``run_name``; actor *i* gets ``<run_name>_actor<i>`` — so the versioned log-dir
machinery never races across processes.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from sheeprl_tpu.distributed import chips
from sheeprl_tpu.distributed.placement import (
    GENERATION_ENV_VAR,
    ROLE_ACTOR,
    ROLE_LEARNER,
    SUMMARY_ENV_VAR,
    PlacementSpec,
    placement_from_cfg,
)
from sheeprl_tpu.distributed.transport import Listener, connect
from sheeprl_tpu.fault.supervisor import _strip_override, backoff_seconds, run_dir_for
from sheeprl_tpu.obs.fleet import (
    FLEET_ENV_VAR,
    TRACE_ID_ENV_VAR,
    FleetAggregator,
    new_trace_id,
)


def _log(msg: str) -> None:
    print(f"[sebulba] {msg}", flush=True)


def _base_overrides(overrides: List[str]) -> List[str]:
    """Strip the launcher-owned keys so children only see what we stamp on."""
    for key in ("distributed.role", "distributed.port", "distributed.actor_id", "run_name",
                "fault.autoresume"):
        overrides, _ = _strip_override(overrides, key)
    return overrides


def _spawn(
    overrides: List[str],
    child_ovs: List[str],
    run_name: str,
    env: Dict[str, str],
    log_prefix: str,
) -> subprocess.Popen:
    cmd = [sys.executable, "-m", "sheeprl_tpu"] + overrides + child_ovs + [
        f"run_name={run_name}",
        "fault.autoresume=False",
    ]
    _log(f"spawning {log_prefix} on {chips.describe(env)}: {' '.join(cmd[3:])}")
    return subprocess.Popen(cmd, env=env)


def role_env(role: str) -> Dict[str, str]:
    """Device placement by role: the learner holds the accelerator (under the
    launcher's own platform setting), actors run on the CPU backend."""
    if role == ROLE_LEARNER:
        return chips.accelerator_env(os.environ)
    return chips.cpu_env(os.environ)


def _abandon(spec: PlacementSpec, port: int, actor_id: int) -> None:
    try:
        ch = connect(spec.host, port, timeout_s=5.0)
        ch.send("abandon", None, actor_id=actor_id)
        ch.close()
    except (ConnectionError, OSError) as e:
        _log(f"could not notify learner of abandoned actor {actor_id}: {e}")


def launch(args: Optional[List[str]] = None) -> int:
    """Compose the placement, spawn learner + actors, babysit until done."""
    from sheeprl_tpu.config.core import compose

    overrides = list(args if args is not None else sys.argv[1:])
    overrides = _base_overrides(overrides)
    if not any(ov.startswith("distributed.mode=") for ov in overrides):
        overrides.append("distributed.mode=sebulba")
    cfg = compose(overrides=overrides)
    spec = placement_from_cfg(cfg)
    if not cfg.get("run_name"):
        import datetime

        stamp = datetime.datetime.now().strftime("%Y-%m-%d_%H-%M-%S")
        cfg.run_name = f"{stamp}_{cfg.get('exp_name', 'run')}_{cfg.get('seed', 0)}_sebulba"
    run_name = str(cfg.run_name)

    # Reserve the rendezvous port here (port=0 → pick a free one) and release it
    # before the learner binds: children get the concrete number as an override.
    port = spec.port
    if port == 0:
        probe = Listener(spec.host, 0)
        port = probe.port
        probe.close()

    # Fleet telemetry plane: the launcher hosts the aggregator (the only process
    # that outlives every role) and hands children its address + the run-level
    # trace id through the environment.  run_dir_for needs no JAX — the fleet
    # dir lands next to the learner's versioned log dirs.
    fleet: Optional[FleetAggregator] = None
    trace_id = os.environ.get(TRACE_ID_ENV_VAR) or new_trace_id()
    fleet_cfg = dict((cfg.get("obs") or {}).get("fleet") or {})
    if bool(fleet_cfg.get("enabled", True)):
        fleet_dir = str(fleet_cfg.get("dir") or run_dir_for(cfg) / "fleet")
        try:
            fleet = FleetAggregator(
                fleet_dir,
                host=spec.host,
                liveness_timeout_s=float(fleet_cfg.get("liveness_timeout_s", 10.0)),
                trace_id=trace_id,
                max_timeline_mb=float(fleet_cfg.get("max_timeline_mb", 64.0)),
            )
            _log(f"fleet telemetry at {fleet.address} -> {fleet_dir} (trace_id={trace_id})")
        except OSError as e:
            _log(f"fleet telemetry disabled: {e}")

    # Exactly one chip-holding child (the learner), so the topology can never
    # over-subscribe the host's chips, whatever distributed.num_actors is.
    def child_env(role: str, generation: int = 0) -> Dict[str, str]:
        env = role_env(role)
        # The summary sink is learner-only; role/ids travel as overrides.
        env.pop(SUMMARY_ENV_VAR, None)
        if role == ROLE_LEARNER and os.environ.get(SUMMARY_ENV_VAR):
            env[SUMMARY_ENV_VAR] = os.environ[SUMMARY_ENV_VAR]
        env[GENERATION_ENV_VAR] = str(generation)
        env[TRACE_ID_ENV_VAR] = trace_id
        env.pop(FLEET_ENV_VAR, None)
        if fleet is not None:
            env[FLEET_ENV_VAR] = fleet.address
        return env

    learner = _spawn(
        overrides,
        spec.child_overrides(ROLE_LEARNER, port),
        run_name,
        child_env(ROLE_LEARNER),
        "learner",
    )
    actors: Dict[int, Optional[subprocess.Popen]] = {}
    generations: Dict[int, int] = {i: 0 for i in range(spec.num_actors)}
    respawns: Dict[int, int] = {i: 0 for i in range(spec.num_actors)}
    respawn_at: Dict[int, float] = {}
    for i in range(spec.num_actors):
        actors[i] = _spawn(
            overrides,
            spec.child_overrides(ROLE_ACTOR, port, actor_id=i),
            f"{run_name}_actor{i}",
            child_env(ROLE_ACTOR),
            f"actor{i}",
        )

    children = lambda: [p for p in [learner, *actors.values()] if p is not None]
    terminating = {"flag": False}

    def forward_term(signum, frame):  # pragma: no cover - signal timing
        terminating["flag"] = True
        for p in children():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)

    old_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            old_handlers[sig] = signal.signal(sig, forward_term)
        except ValueError:  # not on the main thread (tests)
            pass

    def collect_fleet_blackboxes(reason: str) -> None:
        """Fleet blackbox: a child died — ask every survivor to dump its flight-
        recorder ring into one correlated crash bundle (plus any on-disk
        ``blackbox/`` dumps, the dead child's own crash dump among them)."""
        if fleet is None or terminating["flag"]:
            return
        try:
            bundle = fleet.collect_blackboxes(reason)
            if bundle:
                _log(f"fleet blackbox bundle: {bundle}")
        except Exception as e:  # forensics must never take down the topology
            _log(f"fleet blackbox collection failed: {e}")

    try:
        while True:
            rc = learner.poll()
            if rc is not None:
                _log(f"learner exited rc={rc}")
                if rc != 0:
                    collect_fleet_blackboxes(f"learner_rc{rc}")
                return rc
            now = time.monotonic()
            for i, proc in list(actors.items()):
                if proc is not None and proc.poll() is not None:
                    arc = proc.returncode
                    actors[i] = None
                    if arc == 0:
                        _log(f"actor{i} done")
                        continue
                    collect_fleet_blackboxes(f"actor{i}_rc{arc}")
                    if terminating["flag"] or not spec.respawn:
                        _log(f"actor{i} died rc={arc}; not respawning")
                        continue
                    respawns[i] += 1
                    if fleet is not None:
                        fleet.note_respawn(i, respawns[i])
                    if respawns[i] > spec.max_actor_respawns:
                        _log(
                            f"actor{i} died rc={arc}; respawn budget "
                            f"({spec.max_actor_respawns}) exhausted — abandoning slot"
                        )
                        _abandon(spec, port, i)
                        continue
                    delay = backoff_seconds(respawns[i], spec.respawn_backoff_s, 30.0)
                    _log(
                        f"actor{i} died rc={arc}; respawn {respawns[i]}/"
                        f"{spec.max_actor_respawns} in {delay:.1f}s"
                    )
                    respawn_at[i] = now + delay
                elif proc is None and i in respawn_at and now >= respawn_at[i]:
                    del respawn_at[i]
                    generations[i] += 1
                    actors[i] = _spawn(
                        overrides,
                        spec.child_overrides(ROLE_ACTOR, port, actor_id=i),
                        f"{run_name}_actor{i}_g{generations[i]}",
                        child_env(ROLE_ACTOR, generation=generations[i]),
                        f"actor{i}(gen{generations[i]})",
                    )
            time.sleep(0.05)
    finally:
        for p in children():
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 10.0
        for p in children():
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()
        if fleet is not None:
            # After the children exited: their exporters' close-time flushes and
            # trace shipments are in, so the merged Perfetto file and the final
            # snapshot cover every process.
            fleet.close()
        for sig, handler in old_handlers.items():
            signal.signal(sig, handler)


def main(args: Optional[List[str]] = None) -> None:
    sys.exit(launch(args))
