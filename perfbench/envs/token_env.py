"""The benchmark's traffic generator for a policy that reads and writes token ids.

Sequences of ``min_length`` .. ``max_length`` tokens, log-uniform from the seed, ending
alternately by termination and by truncation.  ``correct`` compares the run's first
rollouts, which end before the shortest of those sequences does; so the first
``early_ends`` envs (by rank) are given a first episode of 2 .. ``early_end_within``
tokens, uniform from the seed, and ends of both kinds fall inside the compared rows.
``early_end_within`` is those rows' number: every such episode is over while the
harness still warms up, and every token of the timed window belongs to an episode of
the stated lengths.
Each step the policy is shown one token (a seeded stream shifted by the token it just
wrote, so its own choices are in its context) and writes one; the reward is dense and
seeded: the score of the written token given the observed one, in ``[0, 1]`` (a mean
well off zero, so that the advantages' mean, which the policy loss reads, is no
difference of nearly equal sums).  Odd envs end their odd episodes by termination and
their even ones by truncation, even envs the other way round.  Ids
are drawn from the slice of the vocabulary that the configuration holds.

It pays the clock its three dues (``envs/clock.py``) and, while ``clock.LOG_ROWS`` is
true, keeps a row a step: token in, token out, reward, both end flags and the token
that followed (the episode's last observation where it was cut).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import gymnasium as gym
import numpy as np

from perfbench.envs import clock


class TokenEnv(gym.Env):
    def __init__(self, seed: int = 0, rank: int = 0, vocab: int = 64, min_length: int = 8, max_length: int = 32, early_ends: int = 0, early_end_within: int = 0, **_ignored):
        self.rank = int(rank)
        self.vocab = int(vocab)
        self.min_length, self.max_length = int(min_length), int(max_length)
        self.early_end_within = int(early_end_within) if self.rank < int(early_ends) else 0
        self.observation_space = gym.spaces.Dict({"token": gym.spaces.Box(0, self.vocab - 1, shape=(1,), dtype=np.int32)})
        self.action_space = gym.spaces.Discrete(self.vocab)
        # the stream of tokens, lengths and scores is a function of the seed alone
        self._rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x70CE])
        self._score = np.random.default_rng([0x5C04E, self.vocab]).uniform(0.0, 1.0, self.vocab).astype(np.float32)
        self._episode = 0
        self._t = 0
        self._length = 0
        self._token = 0
        self.rows: List[Dict[str, object]] = []
        self.steps = 0
        self.seconds = 0.0
        clock.ENVS.append(self)

    def _draw(self, written: int = 0) -> np.ndarray:
        self._token = (int(self._rng.integers(self.vocab)) + written) % self.vocab
        return np.array([self._token], np.int32)

    def reset(self, seed: Optional[int] = None, options=None):
        super().reset(seed=None)  # the stream was fixed at construction
        self._t = 0
        self._length = int(np.exp(self._rng.uniform(np.log(self.min_length), np.log(self.max_length + 1))))
        if self._episode == 0 and self.early_end_within:
            self._length = int(self._rng.integers(2, self.early_end_within + 1))
        self._episode += 1
        return {"token": self._draw()}, {}

    def step(self, action):
        if self.rank == 0 and clock.HOOK is not None:
            clock.HOOK(self)
        t0 = time.perf_counter()
        self.steps += 1
        self._t += 1
        seen, written = self._token, int(action)
        reward = float(self._score[(seen + written) % self.vocab])
        done = self._t >= self._length
        terminated = done and (self._episode + self.rank) % 2 == 1
        obs = self._draw(written)
        if clock.LOG_ROWS:
            self.rows.append({"obs": seen, "action": written, "reward": reward, "terminated": float(terminated), "truncated": float(done and not terminated), "final_obs": int(obs[0])})
        t1 = time.perf_counter()
        self.seconds += t1 - t0
        if clock.KEEP_INTERVALS:
            clock.INTERVALS.append(("env_step", t0, t1))
        return {"token": obs}, reward, terminated, done and not terminated, {}


def stored_rows() -> Dict[str, np.ndarray]:
    """The kept rows of every env, stacked ``[rows, envs]``."""
    n = min(len(e.rows) for e in clock.ENVS)
    col = lambda k, dtype: np.stack([np.asarray([r[k] for r in e.rows[:n]], dtype) for e in clock.ENVS], axis=1)  # noqa: E731
    kinds = {"obs": np.int32, "action": np.int32, "reward": np.float32, "terminated": np.float32, "truncated": np.float32, "final_obs": np.int32}
    return {k: col(k, dtype) for k, dtype in kinds.items()}
