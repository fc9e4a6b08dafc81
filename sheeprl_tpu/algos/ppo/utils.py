"""PPO helpers (reference: ``/root/reference/sheeprl/algos/ppo/utils.py``)."""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.distributions import Categorical, Normal
from sheeprl_tpu.obs.tracer import trace_span

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/entropy_loss",
}
MODELS_TO_REGISTER = {"agent"}


@trace_span("Time/h2d_transfer")
def prepare_obs(obs: Dict[str, np.ndarray], cnn_keys: Sequence[str], mlp_keys: Sequence[str]) -> Dict[str, jax.Array]:
    """numpy env observations → device arrays (uint8 images stay uint8; the encoder
    normalises on device, reference ``utils.py:…prepare_obs``)."""
    out: Dict[str, jax.Array] = {}
    for k in cnn_keys:
        out[k] = jnp.asarray(obs[k])
    for k in mlp_keys:
        out[k] = jnp.asarray(obs[k], dtype=jnp.float32)
    return out


def actions_as_dist(actor_out: Sequence[jax.Array], is_continuous: bool):
    if is_continuous:
        mean, log_std = jnp.split(actor_out[0], 2, axis=-1)
        return Normal(mean, jnp.exp(log_std))
    return [Categorical(logits) for logits in actor_out]


def sample_actions(key: jax.Array, actor_out: Sequence[jax.Array], is_continuous: bool, greedy: bool = False):
    """Returns (env_actions, stored_actions, logprob)."""
    if is_continuous:
        dist = actions_as_dist(actor_out, True)
        act = dist.mode if greedy else dist.sample(key)
        logprob = dist.log_prob(act).sum(-1)
        return act, act, logprob
    dists = actions_as_dist(actor_out, False)
    keys = jax.random.split(key, len(dists))
    acts = [d.mode if greedy else d.sample(k) for d, k in zip(dists, keys)]
    logprob = sum(d.log_prob(a) for d, a in zip(dists, acts))
    stacked = jnp.stack(acts, axis=-1)
    return stacked, stacked, logprob


def log_prob_and_entropy(actor_out: Sequence[jax.Array], actions: jax.Array, is_continuous: bool):
    if is_continuous:
        dist = actions_as_dist(actor_out, True)
        return dist.log_prob(actions).sum(-1), dist.entropy().sum(-1)
    dists = actions_as_dist(actor_out, False)
    logprob = sum(d.log_prob(actions[..., i]) for i, d in enumerate(dists))
    entropy = sum(d.entropy() for d in dists)
    return logprob, entropy


def chunked_log_prob_and_entropy(hidden: jax.Array, head: jax.Array, actions: jax.Array, chunk: int, dtype: Any):
    """Log-probability of ``actions`` ``[N]`` and entropy under ``Categorical(hidden @ head)``
    for ``hidden`` ``[N, D]`` and a wide ``head`` ``[D, V]``, with the logits formed
    ``chunk`` tokens at a time and formed again in the backward pass: a float32 copy of
    ``[N, V]`` is never held (0.62 GB at 4,096 tokens over 37,984 classes).  The same
    arithmetic as ``log_prob_and_entropy`` over the whole logits."""
    n, d = hidden.shape
    if n % chunk:
        raise ValueError(f"{n} tokens do not divide into chunks of {chunk}")

    @jax.checkpoint
    def one(h, a):
        logits = jnp.dot(h.astype(dtype), head.astype(dtype), preferred_element_type=jnp.float32)
        dist = Categorical(logits)
        return dist.log_prob(a), dist.entropy()

    logprob, entropy = jax.lax.map(lambda t: one(*t), (hidden.reshape(n // chunk, chunk, d), actions.reshape(n // chunk, chunk)))
    return logprob.reshape(n), entropy.reshape(n)


def test(agent, params, ctx, cfg, log_dir: str, greedy: bool = True) -> float:
    """Greedy single-env evaluation episode (reference ``utils.py:test``)."""
    from sheeprl_tpu.utils.env import make_env

    env = make_env(cfg, cfg.seed, 0, log_dir, "test")()
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    @jax.jit
    def policy(p, obs, key):
        actor_out, _ = agent.apply(p, obs)
        env_act, _, _ = sample_actions(key, actor_out, agent.is_continuous, greedy=greedy)
        return env_act

    obs, _ = env.reset(seed=cfg.seed)
    done = False
    cum_reward = 0.0
    while not done:
        obs_t = prepare_obs({k: np.asarray(v)[None] for k, v in obs.items()}, cnn_keys, mlp_keys)
        act = np.asarray(jax.device_get(policy(params, obs_t, ctx.rng())))[0]
        if not agent.is_continuous and len(agent.action_dims) == 1:
            act = act.item()
        obs, reward, terminated, truncated, _ = env.step(act)
        done = bool(terminated or truncated)
        cum_reward += float(reward)
    env.close()
    return cum_reward
