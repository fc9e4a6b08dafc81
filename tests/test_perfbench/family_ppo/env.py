"""The stand-in family's traffic generator: a seeded vector-observation environment.

Observations and rewards are drawn from the seed; episodes have a fixed length and
end by termination.  It pays the clock its three dues (``perfbench/envs/clock.py``)
and, while ``clock.LOG_ROWS`` is true, keeps every transition it emitted: the
observation the policy saw, the action it then took, the reward and the end flag.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import gymnasium as gym
import numpy as np

from perfbench.envs import clock


class VectorObsEnv(gym.Env):
    def __init__(self, seed: int = 0, rank: int = 0, obs_dim: int = 10, n_actions: int = 5, episode_length: int = 6, **_ignored):
        self.rank = int(rank)
        self.episode_length = int(episode_length)
        self.observation_space = gym.spaces.Dict({"state": gym.spaces.Box(-np.inf, np.inf, shape=(int(obs_dim),), dtype=np.float32)})
        self.action_space = gym.spaces.Discrete(int(n_actions))
        self._rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, self.rank, 0x5EED])
        self._t = 0
        self._pending: Optional[np.ndarray] = None
        self.rows: List[Dict[str, object]] = []
        self.steps = 0
        self.seconds = 0.0
        clock.ENVS.append(self)

    def _obs(self) -> np.ndarray:
        return self._rng.standard_normal(self.observation_space["state"].shape).astype(np.float32)

    def reset(self, seed: Optional[int] = None, options=None):
        super().reset(seed=None)  # the stream was fixed at construction
        self._t = 0
        self._pending = self._obs()
        return {"state": self._pending}, {}

    def step(self, action):
        if self.rank == 0 and clock.HOOK is not None:
            clock.HOOK(self)
        t0 = time.perf_counter()
        self.steps += 1
        self._t += 1
        reward = float(np.float32(self._rng.standard_normal()))
        done = self._t >= self.episode_length
        if clock.LOG_ROWS:
            self.rows.append({"obs": self._pending, "action": int(action), "reward": reward, "done": float(done)})
        self._pending = self._obs()
        t1 = time.perf_counter()
        self.seconds += t1 - t0
        if clock.KEEP_INTERVALS:
            clock.INTERVALS.append(("env_step", t0, t1))
        return {"state": self._pending}, reward, done, False, {}


def stored_rows() -> Dict[str, np.ndarray]:
    """The kept transitions of every env, stacked ``[rows, envs, ...]``."""
    n = min(len(e.rows) for e in clock.ENVS)
    col = lambda k, dtype: np.stack([np.asarray([r[k] for r in e.rows[:n]], dtype) for e in clock.ENVS], axis=1)  # noqa: E731
    return {"obs": col("obs", np.float32), "action": col("action", np.int32), "reward": col("reward", np.float32), "done": col("done", np.float32)}
