"""Pure-functional JAX environments — the device half of the Anakin architecture
(Podracer, arxiv 2104.06272; ROADMAP item 1).

The host envs (``sheeprl_tpu/utils/env.py``) step numpy worlds one python call at
a time, and every step pays the player's dispatch + device→host sync on top.  A
:class:`JaxEnv` instead expresses the WHOLE environment as
a pure function over a small state pytree::

    params = env.default_params()
    state, obs = env.reset(params, key)
    state, obs, reward, done, info = env.step(params, state, action, key)

so N instances vmap into one tensor program and the entire act→step→learn loop
compiles into a single ``lax.scan`` dispatch (``sheeprl_tpu/engine/anakin.py``)
— zero host work per env step.

Contract:

* ``state`` is a NamedTuple of arrays (vmappable, checkpointable through
  ``CheckpointManager`` as a plain device pytree); it carries its own step
  counter, so the gymnasium ``TimeLimit`` wrapper has an in-graph equivalent;
* ``step`` NEVER branches in python on traced values (jaxlint JL002): episode
  ends surface as the ``done`` flag and :meth:`JaxEnv.step_autoreset` folds the
  reset in with the ``lax.cond``/``lax.select`` idiom below;
* ``info`` is a small dict of arrays with at least ``terminated``/``truncated``
  (SAC's TD target masks on terminated only, like the host loops) and
  ``final_obs`` — the TRUE pre-reset observation of the finishing step, the
  in-graph analogue of the vector envs' SAME_STEP ``info["final_obs"]``;
* spaces are reported as gymnasium spaces so the existing agent builders work
  unchanged, and the reset distribution matches the gymnasium counterpart
  (documented per env) so host-vs-device runs are statistically comparable.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp


class JaxEnv:
    """Base class for pure-functional envs; subclasses implement ``default_params``,
    ``reset``, ``step`` and the two space properties."""

    name: str = "jax_env"

    def default_params(self) -> NamedTuple:
        raise NotImplementedError

    def reset(self, params: NamedTuple, key: jax.Array) -> Tuple[NamedTuple, jax.Array]:
        raise NotImplementedError

    def step(
        self, params: NamedTuple, state: NamedTuple, action: jax.Array, key: jax.Array
    ) -> Tuple[NamedTuple, jax.Array, jax.Array, jax.Array, Dict[str, Any]]:
        raise NotImplementedError

    def observation_space(self, params: NamedTuple) -> gym.spaces.Box:
        raise NotImplementedError

    def action_space(self, params: NamedTuple) -> gym.spaces.Space:
        raise NotImplementedError

    def step_autoreset(
        self, params: NamedTuple, state: NamedTuple, action: jax.Array, key: jax.Array
    ) -> Tuple[NamedTuple, jax.Array, jax.Array, jax.Array, Dict[str, Any]]:
        """Step with SAME_STEP auto-reset: on ``done`` the returned state/obs are a
        fresh reset (reward and ``info["final_obs"]`` still describe the finishing
        step).  Both branches are computed and ``lax.select``'d — the reset is a
        few FLOPs, and a data-dependent ``lax.cond`` would block vmap batching
        (under vmap it lowers to both branches anyway)."""
        key_step, key_reset = jax.random.split(key)
        stepped, obs_st, reward, done, info = self.step(params, state, action, key_step)
        reset_state, reset_obs = self.reset(params, key_reset)
        state = jax.tree.map(lambda r, s: jax.lax.select(done, r, s), reset_state, stepped)
        obs = jax.lax.select(done, reset_obs, obs_st)
        info = {**info, "final_obs": obs_st}
        return state, obs, reward, done, info

    def sample_action(self, params: NamedTuple, key: jax.Array) -> jax.Array:
        """Uniform random action draw (the prefill analogue of
        ``action_space.sample()``), jittable so prefill scans stay on device."""
        space = self.action_space(params)
        if isinstance(space, gym.spaces.Discrete):
            return jax.random.randint(key, (), 0, int(space.n), dtype=jnp.int32)
        low = jnp.asarray(space.low, jnp.float32)
        high = jnp.asarray(space.high, jnp.float32)
        return jax.random.uniform(key, space.shape, jnp.float32, low, high)


def time_limit(params: NamedTuple, time: jax.Array, terminated: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """In-graph ``TimeLimit``: given the post-step ``time`` counter, return
    ``(truncated, done)``.  ``params.max_episode_steps <= 0`` disables it."""
    max_steps = jnp.asarray(params.max_episode_steps, jnp.int32)
    truncated = jnp.logical_and(max_steps > 0, time >= max_steps)
    truncated = jnp.logical_and(truncated, jnp.logical_not(terminated))
    return truncated, jnp.logical_or(terminated, truncated)
