"""The benchmark's traffic generator: a seeded pixel environment.

A copy of the *idea* of the program's ``DiscreteDummyEnv`` (fixed-length episodes,
64x64x3 uint8 frames), not of its code: frames and rewards here are drawn from the
seed, so the decoder and the reward head have something to fit, and episodes end
alternately by termination and truncation, so ``is_first`` resets, terminal rows
and both flags occur in the window.

The environment is also the benchmark's clock and its witness (``envs/clock.py`` says
what a generator owes the clock; this one pays it in ``__init__`` and ``step``):
while ``clock.LOG_ROWS`` is true every env keeps the rows a DreamerV3 replay has to
hold for it (observation, arrival reward and flags, the action then taken; a
terminal row with a zero action at an episode's end), in its own memory.  The
plain reference gathers its batches from these rows at the indices the program
drew, so "what is sampled is what was stored" is part of what ``correct`` checks.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import gymnasium as gym
import numpy as np

from perfbench.envs import clock


class PixelEnv(gym.Env):
    metadata = {"render_modes": ["rgb_array"], "render_fps": 30}

    def __init__(
        self,
        seed: int = 0,
        rank: int = 0,
        n_actions: int = 17,
        episode_length: int = 200,
        screen_size: int = 64,
        blocks: int = 8,
        reward_scale: float = 0.5,
        **_ignored,
    ):
        self.rank = int(rank)
        self.n_actions = int(n_actions)
        self.episode_length = int(episode_length)
        self.size = int(screen_size)
        self.blocks = int(blocks)
        self.reward_scale = float(reward_scale)
        self.observation_space = gym.spaces.Dict(
            {"rgb": gym.spaces.Box(0, 255, shape=(3, self.size, self.size), dtype=np.uint8)}
        )
        self.action_space = gym.spaces.Discrete(self.n_actions)
        # the stream of frames and rewards is a function of the seed alone
        self._rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x5EED])
        self._t = 0
        self._episode = 0
        self._contrast = 1.0
        self._pending: Optional[Dict[str, object]] = None
        self.rows: List[Dict[str, object]] = []
        self.steps = 0
        self.seconds = 0.0  # this env's own cost, summed over its steps
        clock.ENVS.append(self)

    # -- generation ---------------------------------------------------------
    def _frame(self) -> np.ndarray:
        """A blocky frame around mid-grey whose contrast is the episode's own (drawn at
        reset): rows of a batch that come from different episodes then differ in every
        loss term, so leaving rows out of a batch shows."""
        small = self._rng.integers(0, 256, size=(3, self.blocks, self.blocks)).astype(np.float32)
        small = np.clip(127.5 + self._contrast * (small - 127.5), 0, 255).astype(np.uint8)
        rep = self.size // self.blocks
        return np.repeat(np.repeat(small, rep, axis=1), rep, axis=2)

    def _keep(self, row: Dict[str, object]) -> None:
        if clock.LOG_ROWS:
            self.rows.append(row)

    # -- gym API ------------------------------------------------------------
    def reset(self, seed: Optional[int] = None, options=None):
        # the vector env passes the run's seed here; the stream was fixed at construction
        super().reset(seed=None)
        self._t = 0
        self._contrast = float(self._rng.uniform(0.1, 1.0))
        frame = self._frame()
        self._pending = {"rgb": frame, "reward": 0.0, "terminated": 0.0, "truncated": 0.0, "is_first": 1.0}
        return {"rgb": frame}, {}

    def step(self, action):
        if self.rank == 0 and clock.HOOK is not None:
            clock.HOOK(self)
        t0 = time.perf_counter()
        self.steps += 1
        action = int(action)
        row = dict(self._pending)
        row["action"] = action
        self._keep(row)

        self._t += 1
        frame = self._frame()
        reward = float(np.float32(self._rng.standard_normal() * self.reward_scale))
        done = self._t >= self.episode_length
        terminated = bool(done and self._episode % 2 == 0)
        truncated = bool(done and not terminated)
        if done:
            self._episode += 1
            self._keep(
                {
                    "rgb": frame,
                    "reward": reward,
                    "terminated": float(terminated),
                    "truncated": float(truncated),
                    "is_first": 0.0,
                    "action": None,
                }
            )
        self._pending = {"rgb": frame, "reward": reward, "terminated": 0.0, "truncated": 0.0, "is_first": 0.0}
        t1 = time.perf_counter()
        self.seconds += t1 - t0
        if clock.KEEP_INTERVALS:
            clock.INTERVALS.append(("env_step", t0, t1))
        return {"rgb": frame}, reward, terminated, truncated, {}

    def render(self):
        return np.transpose(self._pending["rgb"], (1, 2, 0))

    def close(self):
        pass


def stored_rows(n_actions: int) -> List[Dict[str, np.ndarray]]:
    """Per env, the kept rows stacked into arrays ``[rows, ...]`` in the layout a
    replay row has: ``rgb`` uint8 [3,H,W], ``reward`` (the observation key) [1],
    ``actions`` one-hot [A], ``rewards``/``terminated``/``truncated``/``is_first`` [1]."""
    out = []
    for env in clock.ENVS:
        n = len(env.rows)
        acts = np.zeros((n, n_actions), np.float32)
        for i, r in enumerate(env.rows):
            if r["action"] is not None:
                acts[i, r["action"]] = 1.0
        col = lambda k: np.asarray([r[k] for r in env.rows], np.float32).reshape(n, 1)  # noqa: E731
        out.append(
            {
                "rgb": np.stack([r["rgb"] for r in env.rows]) if n else np.zeros((0, 3, env.size, env.size), np.uint8),
                "reward": col("reward"),
                "actions": acts,
                "rewards": col("reward"),
                "terminated": col("terminated"),
                "truncated": col("truncated"),
                "is_first": col("is_first"),
            }
        )
    return out
