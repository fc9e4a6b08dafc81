"""Serving-fleet benchmark: front + N replicas, replica-count scaling, fleet p99.

Emits BENCH-style JSON rows on stdout (``benchmarks/bench_compare.py`` pins the
directions: ``fleet_*`` is higher-better by prefix, ``fleet_p99_ms`` pinned
lower-better by exact name):

* ``fleet_replies_per_sec`` — replies/s through the fleet front at the highest
  replica count, with the per-replica-count sweep (``rps_1_replica``,
  ``rps_2_replicas``, ...) and the scaling ratio max-vs-1 riding as extras.
  Every request crosses the front: the sweep isolates what adding replicas buys
  *after* paying the routing hop, which is the number capacity planning needs.
* ``fleet_p99_ms`` — end-to-end p99 (front accept → reply send) from the
  front's exit summary at the highest replica count, front p50 and the share of
  rerouted requests as extras.

All replicas share one persistent compile cache (wherever
``sheeprl_tpu/utils/compile_cache.py`` resolves it), so replica 2..N start warm —
the same mechanism the autoscaler leans on for fast scale-up.  This driver never
initialises JAX: each replica is a child holding a chip of its own (a sweep past
the host's chip count is refused), the front is placed on the CPU backend, and
every row carries the device its replicas reported.  The served
artifact is the untrained tiny PPO from ``serve_bench`` (serving cost does not
depend on how good the weights are).

Usage::

    python benchmarks/fleet_bench.py
    python benchmarks/fleet_bench.py --clients 16 --requests 50 --max-replicas 2
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

from serve_bench import MODEL_NAME, Replica, build_artifact  # noqa: E402


def _front_env() -> Dict[str, str]:
    """The front runs no policy: placed on the CPU backend by statement."""
    from sheeprl_tpu.distributed import chips

    env = chips.cpu_env(os.environ)
    for var in ("SHEEPRL_TPU_SERVE_SUMMARY", "SHEEPRL_TPU_FLEET_SUMMARY", "SHEEPRL_TPU_FLEET"):
        env.pop(var, None)
    return env


class Front:
    """One fleet-front subprocess over a static replica list."""

    def __init__(self, workdir: Path, endpoints: List[str]):
        self.ready_file = workdir / "front_ready.json"
        self.summary_file = workdir / "front_summary.json"
        workdir.mkdir(parents=True, exist_ok=True)
        args = [
            sys.executable, "-m", "sheeprl_tpu.serve.fleet",
            "serve.fleet.enabled=True",
            f"serve.fleet.replicas=[{','.join(endpoints)}]",
            f"serve.fleet.dir={workdir}",
            "serve.fleet.host=127.0.0.1",
            "serve.fleet.port=0",
            f"serve.fleet.ready_file={self.ready_file}",
            f"serve.fleet.summary_path={self.summary_file}",
        ]
        self.proc = subprocess.Popen(
            args, cwd=REPO, env=_front_env(),
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        )

    def wait_ready(self, timeout_s: float = 60.0) -> Dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.ready_file.is_file():
                try:
                    return json.loads(self.ready_file.read_text())
                except json.JSONDecodeError:  # mid-replace; retry
                    time.sleep(0.05)
                    continue
            if self.proc.poll() is not None:
                raise RuntimeError(f"front died during startup (rc={self.proc.returncode})")
            time.sleep(0.05)
        raise TimeoutError(f"front not ready within {timeout_s}s")

    def stop(self) -> Dict:
        """SIGTERM → drain → exit 75; returns the front's exit summary."""
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=120)
        if rc != 75:
            raise RuntimeError(f"expected front drain exit code 75, got {rc}")
        return json.loads(self.summary_file.read_text())


def drive_fleet_clients(
    port: int, obs_template: Dict[str, tuple], clients: int, requests: int
) -> Tuple[float, int]:
    """``clients`` closed-loop FleetClients x ``requests`` round-trips each."""
    import numpy as np

    from sheeprl_tpu.serve.client import FleetClient

    obs = {
        k: np.zeros(shape, dtype=np.dtype(dtype)) for k, (shape, dtype) in obs_template.items()
    }
    replies = [0] * clients
    errors: List[Exception] = []
    barrier = threading.Barrier(clients + 1)

    def worker(idx: int) -> None:
        try:
            with FleetClient([("127.0.0.1", port)]) as client:
                client.ping()  # connect before the clock starts
                barrier.wait()
                for _ in range(requests):
                    client.act(obs, MODEL_NAME, timeout=60)
                    replies[idx] += 1
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)
            try:
                barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(i,), daemon=True) for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} client(s) failed: {errors[0]}")
    return wall, sum(replies)


def run_fleet(
    tmp: Path,
    registry: Path,
    obs_template: Dict[str, tuple],
    n_replicas: int,
    clients: int,
    requests: int,
    max_batch: int,
) -> Tuple[float, Dict, Dict]:
    """Spawn ``n_replicas`` + one front, drive the clients through the front,
    tear everything down; returns ``(replies_per_sec, front_summary, device)``.
    Every replica holds a chip of its own (replica ``i`` pinned to chip ``i`` on
    a multi-chip host); more replicas than chips is refused before any spawn."""
    from sheeprl_tpu.distributed import chips

    n_chips = chips.check_chip_budget(n_replicas, f"a {n_replicas}-replica fleet")
    workdir = tmp / f"fleet_{n_replicas}r"
    replicas = [
        Replica(registry, workdir / f"replica{i}", max_batch, chip=i if n_chips > 1 else None)
        for i in range(n_replicas)
    ]
    front = None
    try:
        readies = [r.wait_ready() for r in replicas]
        endpoints = [f"127.0.0.1:{ready['port']}" for ready in readies]
        device = {k: readies[0][k] for k in ("platform", "device_kind", "device_count")}
        front = Front(workdir / "front", endpoints)
        ready = front.wait_ready()
        wall, total = drive_fleet_clients(ready["port"], obs_template, clients, requests)
        summary = front.stop()
        front = None
        if summary["replied"] != total or summary["errors"]:
            raise RuntimeError(f"front lost replies: drove {total}, summary {summary}")
        return (total / wall if wall > 0 else 0.0), summary, device
    finally:
        if front is not None:
            front.proc.kill()
        for r in replicas:
            if r.proc.poll() is None:
                try:
                    r.stop()
                except Exception:
                    r.proc.kill()


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--clients", type=int, default=16)
    parser.add_argument("--requests", type=int, default=50, help="round-trips per client")
    parser.add_argument("--max-replicas", type=int, default=2, help="sweep 1..N replicas")
    parser.add_argument("--max-batch", type=int, default=16)
    args = parser.parse_args(argv)

    tmp = Path(tempfile.mkdtemp(prefix="fleet_bench_"))
    registry, obs_template = build_artifact(tmp)

    sweep: Dict[int, float] = {}
    summary: Dict = {}
    device: Dict = {}
    for n in range(1, args.max_replicas + 1):
        sweep[n], summary, device = run_fleet(
            tmp, registry, obs_template, n, args.clients, args.requests, args.max_batch
        )

    top_n = max(sweep)
    extras = {
        f"rps_{n}_replica{'s' if n > 1 else ''}": round(rps, 2) for n, rps in sweep.items()
    }
    print(json.dumps({
        "metric": "fleet_replies_per_sec",
        "value": round(sweep[top_n], 2),
        "unit": (
            f"replies/s through the fleet front, {top_n} replicas, "
            f"{args.clients} closed-loop clients x {args.requests} requests"
        ),
        **extras,
        "scaling_vs_1_replica": round(sweep[top_n] / sweep[1], 2) if sweep.get(1) else None,
        **device,  # per replica, as each replica's ready file reports it
    }))
    p99 = summary.get("p99_ms")
    p50 = summary.get("p50_ms")
    print(json.dumps({
        "metric": "fleet_p99_ms",
        "value": round(p99, 3) if isinstance(p99, (int, float)) else None,
        "unit": f"ms front accept->reply p99, {top_n} replicas, {args.clients} clients",
        "p50_ms": round(p50, 3) if isinstance(p50, (int, float)) else None,
        "rerouted": summary.get("rerouted", 0),
        "replied": summary.get("replied", 0),
        **device,
    }))


if __name__ == "__main__":
    main()
