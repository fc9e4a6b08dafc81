"""Plain float32 ``jax.numpy`` reference of the clipped PPO update: the policy and
value MLPs, GAE, the clipped surrogate, the value and entropy terms, the clip by
global norm and the Adam step.  It imports nothing of the program.

One *step* is one update of the program's timed call: ``update_epochs`` full-batch
gradient steps over a rollout of ``rollout_steps x num_envs`` transitions (one
minibatch an epoch, so the order a permutation gives the rows does not enter).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2 = 0.9, 0.999


def param_shapes(S: Dict[str, Any]) -> Dict[str, Any]:
    """The published layout of ``exp=ppo``'s agent for one vector key and one discrete head."""
    D, U, F, L, A = S["obs_dim"], S["dense_units"], S["features_dim"], S["mlp_layers"], S["actions"]

    def mlp(n_in, out=None):
        sizes = [n_in] + [U] * L + ([out] if out is not None else [])
        return {f"Dense_{i}": {"bias": (b,), "kernel": (a, b)} for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))}

    return {
        "params": {
            "actor_backbone": mlp(F),
            "actor_head_0": {"bias": (A,), "kernel": (U, A)},
            "critic": mlp(F, 1),
            "feature_extractor": {"MLP_0": mlp(D, F)},
        }
    }


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


def flat_shapes(S: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    leaves = jax.tree_util.tree_flatten_with_path(param_shapes(S), is_leaf=_is_shape)[0]
    return {"/".join(str(k.key) for k in path): shape for path, shape in leaves}


def make_weights(S: Dict[str, Any], seed: jax.Array) -> Dict[str, Any]:
    """Weights from the seed alone: kernels ~ N(0, 1/fan_in), biases 0.1 N."""
    shapes = param_shapes(S)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for key, shape in zip(keys, leaves):
        scale = 0.1 if len(shape) == 1 else shape[0] ** -0.5
        out.append(scale * jax.random.normal(key, shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


def _dense(p, x):
    return jnp.dot(x, p["kernel"], precision=HI) + p["bias"]


def _mlp(p, x, hidden: int):
    for i in range(hidden):
        x = jnp.tanh(_dense(p[f"Dense_{i}"], x))
    return _dense(p[f"Dense_{hidden}"], x) if f"Dense_{hidden}" in p else x


def forward(S, params, obs):
    """``obs [..., D]`` -> normalised log-probabilities ``[..., A]`` and values ``[...]``."""
    p, L = params["params"], S["mlp_layers"]
    feat = _mlp(p["feature_extractor"]["MLP_0"], obs, L)
    logits = _dense(p["actor_head_0"], _mlp(p["actor_backbone"], feat, L))
    return jax.nn.log_softmax(logits, axis=-1), _mlp(p["critic"], feat, L)[..., 0]


def gae(S, rewards, values, dones, next_value):
    """``[T, N]`` arrays; ``dones[t]`` ends the episode at step t, so its bootstrap is masked."""
    adv, out = jnp.zeros_like(next_value), []
    for t in reversed(range(rewards.shape[0])):
        nv = next_value if t == rewards.shape[0] - 1 else values[t + 1]
        delta = rewards[t] + S["gamma"] * nv * (1.0 - dones[t]) - values[t]
        adv = delta + S["gamma"] * S["gae_lambda"] * (1.0 - dones[t]) * adv
        out.append(adv)
    advantages = jnp.stack(out[::-1])
    return advantages + values, advantages


def losses(S, params, batch):
    logp_all, values = forward(S, params, batch["obs"])
    logp = jnp.take_along_axis(logp_all, batch["action"][..., None], axis=-1)[..., 0]
    ratio = jnp.exp(logp - batch["logprob"])
    clip = S["clip_coef"]
    policy = -jnp.minimum(batch["advantage"] * ratio, batch["advantage"] * jnp.clip(ratio, 1.0 - clip, 1.0 + clip)).mean()
    value = ((values - batch["return"]) ** 2).mean()
    entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
    total = policy + S["vf_coef"] * value - S["ent_coef"] * entropy
    return total, {"policy": policy, "value": value, "entropy": entropy}


def init_state(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "mu": zeros, "nu": zeros, "count": jnp.zeros((), jnp.int32)}


def adam_step(S, state, grads):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < S["max_grad_norm"], 1.0, S["max_grad_norm"] / norm)
    grads = jax.tree.map(lambda g: g * scale, grads)
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1.0 - ADAM_B1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1.0 - ADAM_B2) * g * g, state["nu"], grads)
    c1, c2 = 1.0 - ADAM_B1 ** count.astype(jnp.float32), 1.0 - ADAM_B2 ** count.astype(jnp.float32)
    params = jax.tree.map(lambda p, m, v: p - S["lr"] * (m / c1) / (jnp.sqrt(v / c2) + S["adam_eps"]), state["params"], mu, nu)
    return {"params": params, "mu": mu, "nu": nu, "count": count}


def update(S, state, rollout):
    """One update over ``rollout`` = ``obs [T, N, D]``, ``action``, ``reward``, ``done``
    ``[T, N]`` and ``next_obs [N, D]``, all as the environment emitted them; what the
    policy said while acting (log-probabilities, values) is worked out here."""
    logp_all, values = forward(S, state["params"], rollout["obs"])
    logprob = jnp.take_along_axis(logp_all, rollout["action"][..., None], axis=-1)[..., 0]
    returns, advantages = gae(S, rollout["reward"], values, rollout["done"], forward(S, state["params"], rollout["next_obs"])[1])
    flat = lambda x: x.reshape(-1, *x.shape[2:])  # noqa: E731
    batch = {"obs": flat(rollout["obs"]), "action": flat(rollout["action"]), "logprob": flat(logprob), "return": flat(returns), "advantage": flat(advantages)}
    batch = jax.lax.stop_gradient(batch)
    seen = []
    for _ in range(S["update_epochs"]):
        (_, aux), grads = jax.value_and_grad(lambda p: losses(S, p, batch), has_aux=True)(state["params"])
        state = adam_step(S, state, grads)
        seen.append(aux)
    return state, {k: jnp.mean(jnp.stack([a[k] for a in seen])) for k in seen[0]}
