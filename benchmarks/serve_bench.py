"""Serve-tier benchmark: continuous batching vs naive dispatch + cold/warm start.

Emits BENCH-style JSON rows on stdout (``benchmarks/bench_compare.py`` pins the
directions: ``serve_*`` is higher-better by prefix, with ``serve_p99_ms`` and
``serve_startup_seconds`` pinned lower-better by exact name):

* ``serve_throughput_rps`` — replies/s of the continuously-batched server at
  ``--clients`` closed-loop clients, with the NAIVE one-request-per-dispatch
  baseline (``serve.max_batch_size=1``: the ladder collapses to ``[1]``, so
  every request is its own dispatch) and the speedup ratio riding as extras.
  Same transport, same AOT precompile, same clients — the ONLY difference is
  the batching policy, so the ratio isolates what continuous batching buys.
* ``serve_p99_ms`` — the batched server's end-to-end p99 (enqueue→reply send)
  from its exit summary, naive p99 as an extra.
* ``serve_startup_seconds`` — spawn→ready wall of a WARM replica start (value)
  vs the COLD start that populated the persistent compile cache (extra): the
  AOT ladder deserializes from disk instead of recompiling.  The cold start
  gets a fixed sub-directory of the resolved cache, emptied first
  (``sheeprl_tpu/utils/compile_cache.py``).

The served artifact is built without training: a freshly-initialised tiny PPO
agent on ``jax_cartpole`` is checkpointed and registered — serving cost does not
depend on how good the weights are.

Process discipline: this driver never initialises JAX.  The artifact is built by
a child placed on the CPU backend by statement (it measures nothing), and each
replica — one alive at a time — is a child that holds the accelerator; every row
carries the ``platform`` / ``device_kind`` / ``device_count`` its replica wrote
into its ready file.

Usage::

    python benchmarks/serve_bench.py
    python benchmarks/serve_bench.py --clients 32 --requests 100 --max-batch 32
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

MODEL_NAME = "serve_bench_ppo"

TINY_PPO = [
    "exp=ppo",
    "env=jax_cartpole",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.dense_units=16",
    "algo.mlp_layers=1",
    "algo.encoder.mlp_features_dim=16",
    "env.num_envs=1",
    "env.capture_video=False",
]


def build_artifact(tmp: Path) -> Tuple[Path, Dict[str, tuple]]:
    """Checkpoint + register an untrained tiny PPO policy in a child process on
    the CPU backend (so the driver never holds the chip its replicas need);
    returns ``(registry_dir, obs_template)``."""
    from sheeprl_tpu.distributed import chips

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--build-artifact", str(tmp)],
        cwd=REPO, env=chips.cpu_env(os.environ), check=True, capture_output=True, text=True,
    )
    template = json.loads(proc.stdout.strip().splitlines()[-1])
    return tmp / "registry", {k: (tuple(shape), dtype) for k, (shape, dtype) in template.items()}


def _build_artifact_here(tmp: Path) -> None:
    """Child half of :func:`build_artifact`; prints the obs template as JSON."""
    import jax

    from sheeprl_tpu.config.core import compose, save_config
    from sheeprl_tpu.checkpoint.manager import CheckpointManager
    from sheeprl_tpu.parallel.mesh import MeshContext, build_mesh
    from sheeprl_tpu.utils.env import make_env
    from sheeprl_tpu.utils.model_manager import LocalModelManager
    from sheeprl_tpu.utils.policy import build_policy

    cfg = compose(config_name="config", overrides=TINY_PPO)
    env = make_env(cfg, 0, 0, None, "serve_bench")()
    ctx = MeshContext(mesh=build_mesh(devices=jax.devices()[:1]), precision="fp32", seed=0)
    policy, params = build_policy(ctx, cfg, env.observation_space, env.action_space)
    env.close()

    ckpt_path = CheckpointManager(tmp / "run" / "checkpoints").save(0, {"params": params})
    save_config(cfg, tmp / "run" / "config.yaml")
    LocalModelManager(registry_dir=str(tmp / "registry")).register_model(str(ckpt_path), MODEL_NAME)
    print(json.dumps({k: (list(shape), str(dtype)) for k, (shape, dtype) in policy.obs_template.items()}))


def replica_env(chip: Optional[int] = None) -> Dict[str, str]:
    """A replica holds the accelerator (``chip`` pins it to one chip of a
    multi-chip host); its platform is never defaulted to the CPU here."""
    from sheeprl_tpu.distributed import chips

    env = chips.accelerator_env(os.environ, chip=chip)
    env.pop("SHEEPRL_TPU_SERVE_SUMMARY", None)
    return env


class Replica:
    """One server subprocess: spawn, wait-ready, SIGTERM-drain, summary."""

    def __init__(
        self,
        registry: Path,
        workdir: Path,
        max_batch: int,
        cache_dir: Optional[str] = None,
        chip: Optional[int] = None,
    ):
        self.ready_file = workdir / "ready.json"
        self.summary_file = workdir / "summary.json"
        workdir.mkdir(parents=True, exist_ok=True)
        args = [
            sys.executable, "-m", "sheeprl_tpu.serve",
            f"serve.policies=[{MODEL_NAME}:latest]",
            f"model_manager.registry_dir={registry}",
            "serve.host=127.0.0.1",
            "serve.port=0",
            f"serve.max_batch_size={max_batch}",
            f"serve.ready_file={self.ready_file}",
            f"serve.summary_path={self.summary_file}",
            "serve.log_every_s=0",
            "compile_cache.enabled=True",
        ]
        if cache_dir:  # else: wherever utils/compile_cache.py resolves it
            args.append(f"compile_cache.dir={cache_dir}")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            args, cwd=REPO, env=replica_env(chip),
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        )
        self.startup_seconds: Optional[float] = None
        self.ready: Optional[Dict] = None

    def wait_ready(self, timeout_s: float = 300.0) -> Dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.ready_file.is_file():
                try:
                    self.ready = json.loads(self.ready_file.read_text())
                except json.JSONDecodeError:  # mid-replace; retry
                    time.sleep(0.05)
                    continue
                self.startup_seconds = time.perf_counter() - self.t_spawn
                return self.ready
            if self.proc.poll() is not None:
                raise RuntimeError(f"server died during startup (rc={self.proc.returncode})")
            time.sleep(0.05)
        raise TimeoutError(f"server not ready within {timeout_s}s")

    def stop(self) -> Dict:
        """SIGTERM → drain → exit 75; returns the exit summary."""
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=120)
        if rc != 75:
            raise RuntimeError(f"expected drain exit code 75, got {rc}")
        return json.loads(self.summary_file.read_text())


def drive_clients(
    port: int, obs_template: Dict[str, tuple], clients: int, requests: int
) -> Tuple[float, int]:
    """``clients`` closed-loop threads x ``requests`` round-trips each; returns
    ``(wall_seconds, total_replies)``."""
    import numpy as np

    from sheeprl_tpu.serve.client import PolicyClient

    obs = {
        k: np.zeros(shape, dtype=np.dtype(dtype)) for k, (shape, dtype) in obs_template.items()
    }
    replies = [0] * clients
    errors: List[Exception] = []
    barrier = threading.Barrier(clients + 1)

    def worker(idx: int) -> None:
        try:
            client = PolicyClient("127.0.0.1", port)
            barrier.wait()
            for _ in range(requests):
                client.act(obs, MODEL_NAME, timeout=60)
                replies[idx] += 1
            client.close()
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)
            try:
                barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(i,), daemon=True) for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()  # all clients connected: the clock measures serving, not connects
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} client(s) failed: {errors[0]}")
    return wall, sum(replies)


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--clients", type=int, default=32)
    parser.add_argument("--requests", type=int, default=100, help="round-trips per client")
    parser.add_argument("--max-batch", type=int, default=32)
    parser.add_argument("--build-artifact", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.build_artifact:  # child mode of build_artifact
        _build_artifact_here(Path(args.build_artifact))
        return

    from sheeprl_tpu.utils.compile_cache import empty_cold_start_dir

    tmp = Path(tempfile.mkdtemp(prefix="serve_bench_"))
    registry, obs_template = build_artifact(tmp)
    cache_dir = empty_cold_start_dir("serve_startup")

    # -- cold start: empty persistent cache, every ladder bucket compiles.
    replica = Replica(registry, tmp / "cold", args.max_batch, cache_dir)
    replica.wait_ready()
    cold_startup = replica.startup_seconds
    replica.stop()

    # -- warm start: same cache dir, the ladder deserializes from disk.
    replica = Replica(registry, tmp / "warm", args.max_batch, cache_dir)
    ready = replica.wait_ready()
    warm_startup = replica.startup_seconds
    device = {k: ready[k] for k in ("platform", "device_kind", "device_count")}

    # -- continuous batching throughput on the warm replica.
    wall, total = drive_clients(ready["port"], obs_template, args.clients, args.requests)
    batched_rps = total / wall if wall > 0 else 0.0
    batched_summary = replica.stop()
    batched = batched_summary["policies"][f"{MODEL_NAME}:1"]["metrics"]

    # -- naive baseline: one request per dispatch (ladder [1]), same everything.
    replica = Replica(registry, tmp / "naive", 1, cache_dir)
    ready = replica.wait_ready()
    n_wall, n_total = drive_clients(ready["port"], obs_template, args.clients, args.requests)
    naive_rps = n_total / n_wall if n_wall > 0 else 0.0
    naive_summary = replica.stop()
    naive = naive_summary["policies"][f"{MODEL_NAME}:1"]["metrics"]

    print(json.dumps({
        "metric": "serve_throughput_rps",
        "value": round(batched_rps, 2),
        "unit": (
            f"replies/s (continuous batching, max_batch={args.max_batch}, "
            f"{args.clients} closed-loop clients x {args.requests} requests)"
        ),
        "naive_rps": round(naive_rps, 2),
        "speedup_vs_naive": round(batched_rps / naive_rps, 2) if naive_rps > 0 else None,
        "batch_fill": round(batched.get("Serve/batch_fill", 0.0), 3),
        "replies": total,
        "recompiles": batched_summary["recompiles"],
        **device,
    }))
    print(json.dumps({
        "metric": "serve_p99_ms",
        "value": round(batched.get("Serve/latency_ms/p99", float("nan")), 3),
        "unit": f"ms enqueue->reply p99 (continuous batching, {args.clients} clients)",
        "p50_ms": round(batched.get("Serve/latency_ms/p50", float("nan")), 3),
        "naive_p99_ms": round(naive.get("Serve/latency_ms/p99", float("nan")), 3),
        **device,
    }))
    print(json.dumps({
        "metric": "serve_startup_seconds",
        "value": round(warm_startup, 2),
        "unit": "s spawn->ready, warm persistent compile cache",
        "cold_startup_seconds": round(cold_startup, 2),
        "warm_speedup": round(cold_startup / warm_startup, 2) if warm_startup else None,
        "compile_cache_dir": cache_dir,
        **device,
    }))


if __name__ == "__main__":
    main()
