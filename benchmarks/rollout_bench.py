"""Vector-env backend benchmark: env-steps/s for SyncVectorEnv vs AsyncVectorEnv
vs EnvPool at DreamerV3 walker shapes (4 envs, 64x64x3 uint8 pixels + a small
proprio vector, 6-dim continuous actions).

The env is a dummy pixel env with a configurable simulated step cost
(``--step-ms``, default 2 ms, the order of a single-env MuJoCo+GL DreamerV3
walker step at action_repeat 2).  On a multi-core host
the pool's concurrent workers should sustain >=2x the serial SyncVectorEnv
rate at that cost; ``--step-ms 0`` measures pure dispatch/IPC overhead instead.

Emits one JSON row per backend on stdout, shaped like the ``BENCH_*.json``
trajectory entries (``{"metric", "value", "unit", ...}``), plus a speedup row:

    python benchmarks/rollout_bench.py
    python benchmarks/rollout_bench.py --num-envs 8 --steps 500 --step-ms 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import gymnasium as gym
import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sheeprl_tpu.envs.dummy import ContinuousDummyEnv  # noqa: E402


class _SimStepCost(gym.Wrapper):
    """Busy-wait a fixed per-step cost: emulates single-core MuJoCo+GL work
    (sleep() would under-represent SyncVectorEnv, which pays the cost serially
    on a real simulator whether or not the GIL is released)."""

    def __init__(self, env: gym.Env, step_ms: float):
        super().__init__(env)
        self._cost_s = step_ms / 1e3

    def step(self, action):
        if self._cost_s > 0:
            end = time.perf_counter() + self._cost_s
            while time.perf_counter() < end:
                pass
        return self.env.step(action)


def make_thunks(num_envs: int, step_ms: float, screen_size: int, ep_len: int) -> List[Callable[[], gym.Env]]:
    def thunk() -> gym.Env:
        env = ContinuousDummyEnv(image_size=(3, screen_size, screen_size), n_steps=ep_len, action_dim=6)
        return _SimStepCost(env, step_ms)

    return [thunk for _ in range(num_envs)]


def _build(backend: str, thunks, num_workers: Optional[int]):
    from gymnasium.vector import AsyncVectorEnv, AutoresetMode, SyncVectorEnv

    if backend == "sync":
        return SyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
    if backend == "async":
        return AsyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
    if backend == "pool":
        from sheeprl_tpu.rollout import EnvPool

        return EnvPool(thunks, num_workers=num_workers, step_timeout_s=120.0)
    raise ValueError(f"unknown backend {backend!r}")


def bench_backend(backend: str, args) -> float:
    thunks = make_thunks(args.num_envs, args.step_ms, args.screen_size, args.ep_len)
    envs = _build(backend, thunks, args.num_workers)
    try:
        envs.reset(seed=42)
        actions = np.zeros((args.num_envs, 6), dtype=np.float32)
        for _ in range(args.warmup_steps):
            envs.step(actions)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            envs.step(actions)
        elapsed = time.perf_counter() - t0
    finally:
        envs.close()
    return args.steps * args.num_envs / elapsed if elapsed > 0 else float("inf")


def main(argv: Optional[List[str]] = None) -> Dict[str, float]:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--num-envs", type=int, default=4)
    parser.add_argument("--num-workers", type=int, default=None)
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--warmup-steps", type=int, default=10)
    parser.add_argument("--step-ms", type=float, default=2.0)
    parser.add_argument("--screen-size", type=int, default=64)
    parser.add_argument("--ep-len", type=int, default=1000)
    parser.add_argument("--backends", type=str, default="sync,async,pool")
    parser.add_argument("--json-out", type=str, default=None)
    args = parser.parse_args(argv)

    shape_note = (
        f"{args.num_envs} envs, {args.screen_size}x{args.screen_size}x3 uint8 + 10-dim proprio, "
        f"{args.step_ms:g}ms sim step, {os.cpu_count()} host CPUs"
    )
    rates: Dict[str, float] = {}
    rows = []
    for backend in [b.strip() for b in args.backends.split(",") if b.strip()]:
        rates[backend] = bench_backend(backend, args)
        rows.append(
            {
                "metric": f"rollout_env_steps_per_sec_{backend}",
                "value": round(rates[backend], 2),
                "unit": f"env-steps/s ({shape_note})",
            }
        )
    if "sync" in rates and "pool" in rates and rates["sync"] > 0:
        rows.append(
            {
                "metric": "rollout_envpool_speedup_vs_sync",
                "value": round(rates["pool"] / rates["sync"], 3),
                "unit": f"x ({shape_note})",
            }
        )
    for row in rows:
        print(json.dumps(row))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=1)
    return rates


if __name__ == "__main__":
    main()
