"""A small seeded token environment: the policy writes a sequence one token at a time
and a programmatic scorer pays each token.

Observations and actions are ids of one vocabulary of ``vocab`` tokens.  The
observation ``{"token": [id]}`` is the environment's own next token (a seeded stream,
shifted by the token the policy just wrote, so the policy's choices show in its
context); the reward of a step is the seeded score of the written token given the
observed one, in ``[-1, 1]``: ``score[(observed + written) % vocab]``.  Episode lengths
are drawn log-uniformly from ``[min_length, max_length]``; episodes end alternately by
termination and by truncation.  For ``exp=ppo_recurrent_decoder``; the benchmark has a
generator of its own (``perfbench/envs/token_env.py``).
"""

from __future__ import annotations

from typing import Optional

import gymnasium as gym
import numpy as np


class TokenScoreEnv(gym.Env):
    def __init__(self, vocab: int = 64, min_length: int = 24, max_length: int = 96, seed: Optional[int] = 0, rank: Optional[int] = 0):
        self.vocab = int(vocab)
        self.min_length, self.max_length = int(min_length), int(max_length)
        self.observation_space = gym.spaces.Dict({"token": gym.spaces.Box(0, self.vocab - 1, shape=(1,), dtype=np.int32)})
        self.action_space = gym.spaces.Discrete(self.vocab)
        # the score table belongs to the task (the seed), the stream to the env (seed and rank)
        self.score = np.random.default_rng([int(seed or 0), 0x5C0]).uniform(-1.0, 1.0, self.vocab).astype(np.float32)
        self._rng = np.random.default_rng([int(seed or 0), int(rank or 0), 0x70C])
        self._episodes = 0
        self._t = 0
        self._length = 0
        self._token = 0

    def _draw(self, written: int = 0) -> np.ndarray:
        self._token = (int(self._rng.integers(self.vocab)) + written) % self.vocab
        return np.array([self._token], np.int32)

    def reset(self, seed: Optional[int] = None, options=None):
        super().reset(seed=None)  # the stream was fixed at construction
        self._t = 0
        self._length = int(np.exp(self._rng.uniform(np.log(self.min_length), np.log(self.max_length + 1))))
        self._episodes += 1
        return {"token": self._draw()}, {}

    def step(self, action):
        written = int(action)
        reward = float(self.score[(self._token + written) % self.vocab])
        self._t += 1
        done = self._t >= self._length
        terminated = done and self._episodes % 2 == 1
        return {"token": self._draw(written)}, reward, terminated, done and not terminated, {}
