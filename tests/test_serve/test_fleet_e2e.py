"""Slow end-to-end chaos test: a REAL fleet (supervisor -> front + 2 replica
processes), session-affine clients in flight, one replica SIGKILLed — every
accepted request must still be answered.  The CI fleet smoke drives the same
scenario with the shell harness; this is the in-repo repro."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.serve.client import FleetClient

pytestmark = pytest.mark.slow

MODEL = "fleet_e2e_ppo"

TINY_PPO = [
    "exp=ppo",
    "env=jax_cartpole",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.encoder.mlp_features_dim=8",
    "env.num_envs=1",
    "env.capture_video=False",
]


@pytest.fixture(scope="module")
def registry(tmp_path_factory):
    import jax

    from sheeprl_tpu.checkpoint.manager import CheckpointManager
    from sheeprl_tpu.config.core import compose, save_config
    from sheeprl_tpu.parallel.mesh import MeshContext, build_mesh
    from sheeprl_tpu.utils.env import make_env
    from sheeprl_tpu.utils.model_manager import LocalModelManager
    from sheeprl_tpu.utils.policy import build_policy

    tmp = tmp_path_factory.mktemp("fleet_e2e")
    cfg = compose(config_name="config", overrides=TINY_PPO)
    env = make_env(cfg, 0, 0, None, "fleet_e2e")()
    ctx = MeshContext(mesh=build_mesh(devices=jax.devices()[:1]), precision="fp32", seed=0)
    policy, params = build_policy(ctx, cfg, env.observation_space, env.action_space)
    env.close()

    ckpt = CheckpointManager(tmp / "run" / "checkpoints").save(0, {"params": params})
    save_config(cfg, tmp / "run" / "config.yaml")
    mm = LocalModelManager(registry_dir=tmp / "registry")
    mm.register_model(str(ckpt), MODEL)
    return tmp / "registry", policy.obs_template


def _wait_for(predicate, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.2)


def test_fleet_survives_a_sigkilled_replica_with_zero_lost_replies(registry, tmp_path):
    registry_dir, obs_template = registry
    fleet_dir = tmp_path / "fleet"
    summary_path = tmp_path / "supervisor_summary.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for var in ("SHEEPRL_TPU_FLEET", "SHEEPRL_TPU_FLEET_SUMMARY", "SHEEPRL_TPU_SUPERVISE_SUMMARY"):
        env.pop(var, None)
    sup = subprocess.Popen(
        [
            sys.executable, "-m", "sheeprl_tpu.supervise", "--serve",
            f"serve.policies=[{MODEL}:1]",
            f"model_manager.registry_dir={registry_dir}",
            "serve.max_batch_size=4",
            "serve.max_batch_delay_ms=2.0",
            "serve.log_every_s=0",
            "serve.fleet.enabled=True",
            f"serve.fleet.dir={fleet_dir}",
            "serve.fleet.min_replicas=2",
            "serve.fleet.max_replicas=2",
            "serve.fleet.probe_interval_s=0.2",
            "serve.fleet.status_interval_s=0.2",
            f"fault.summary_path={summary_path}",
            f"compile_cache.dir={tmp_path / 'xla_cache'}",
        ],
        env=env,
    )
    try:
        front_ready = fleet_dir / "front_ready.json"
        records_dir = fleet_dir / "replicas"
        _wait_for(front_ready.is_file, 300, "front ready file")
        port = json.loads(front_ready.read_text())["port"]
        endpoint = ("127.0.0.1", port)

        def two_replicas_admitted():
            try:
                with FleetClient([endpoint], timeout_s=5.0) as probe:
                    pong = probe.ping(timeout=5.0)
            except (ConnectionError, TimeoutError, OSError):
                return False
            replicas = (pong.get("fleet") or {}).get("replicas") or {}
            return sum(1 for r in replicas.values() if r.get("alive")) >= 2 and pong["policies"]

        _wait_for(two_replicas_admitted, 300, "two admitted replicas")

        obs = {k: np.zeros(shape, dtype=np.dtype(dtype)) for k, (shape, dtype) in obs_template.items()}
        clients, per_client = 3, 30
        replies = [0] * clients
        errors = []

        def worker(idx):
            try:
                with FleetClient([endpoint], timeout_s=60.0, session=f"chaos{idx}") as c:
                    for _ in range(per_client):
                        _, meta = c.act(obs, MODEL, timeout=60)
                        assert meta["replica"]
                        replies[idx] += 1
            except Exception as e:  # noqa: BLE001 - every act MUST succeed
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,), daemon=True) for i in range(clients)]
        for t in threads:
            t.start()
        _wait_for(lambda: sum(replies) >= 20 or errors, 120, "clients to get going")

        # prefer a victim with a request in flight (deterministic reroute)
        victim_pid = None
        deadline = time.monotonic() + 10.0
        while victim_pid is None and time.monotonic() < deadline and sum(replies) < clients * per_client:
            try:
                with FleetClient([endpoint], timeout_s=5.0) as probe:
                    fleet_view = probe.ping(timeout=5.0)["fleet"]["replicas"]
            except (ConnectionError, TimeoutError, OSError):
                continue
            busy = [n for n, r in fleet_view.items() if r.get("inflight", 0) > 0 and not r.get("canary")]
            for record_file in sorted(records_dir.glob("*.json")):
                rec = json.loads(record_file.read_text())
                if rec["name"] in busy:
                    victim_pid = rec["pid"]
                    break
        if victim_pid is None:  # fall back to any live replica
            recs = [json.loads(p.read_text()) for p in sorted(records_dir.glob("*.json"))]
            victim_pid = next(r["pid"] for r in recs if not r["canary"])
        os.kill(victim_pid, signal.SIGKILL)

        for t in threads:
            t.join(timeout=300)
        assert not errors, errors[0]
        assert sum(replies) == clients * per_client  # zero lost replies

        sup.send_signal(signal.SIGTERM)
        assert sup.wait(timeout=120) == 0  # orderly fleet drain
    finally:
        if sup.poll() is None:
            sup.kill()
            sup.wait(timeout=30)

    front_summary = json.loads((fleet_dir / "front_summary.json").read_text())
    assert front_summary["accepted"] == front_summary["replied"]
    assert front_summary["errors"] == 0 and front_summary["dropped"] == 0
    sup_summary = json.loads(summary_path.read_text())
    assert sup_summary["mode"] == "fleet" and sup_summary["outcome"] == "preempted"
    # the SIGKILL was classified as a crash (the respawn may still be inside
    # its backoff window when the fleet is torn down — that's fine, the zero-
    # loss assertion above already proved the reroute)
    kinds = [e["kind"] for e in sup_summary["events"]]
    assert "crash" in kinds and kinds.count("spawn") >= 3
