"""Anakin training mode: acting + env stepping + update fused into ONE dispatch.

The host loops have two end-to-end walls that are architectural: the player
round trip (the host fetches one action from the policy jit per env step, a
dispatch plus a device→host sync) and single-core host env stepping.  The
Podracer "Anakin" architecture (arxiv 2104.06272) removes both: the environment
itself is a pure JAX function (``sheeprl_tpu/envs/jax``), N instances vmap into
one tensor program, and env step, acting, transition writes and the gradient
update compile into a single donated jitted ``lax.scan`` — zero player RTT,
zero host env stepping, zero H2D per step.  The host's entire per-dispatch job
is one jit call plus counter bookkeeping.

This module is the shared acting/update engine ROADMAP item 1 names: the PPO
and SAC entry points delegate here when ``algo.anakin=True`` (requires a
``env.jax.enabled`` env), reusing their existing jitted update builders —

* PPO: the fused iteration collects a ``rollout_steps`` on-device rollout and
  then calls the UNCHANGED :class:`~sheeprl_tpu.algos.ppo.ppo.PPOTrainFns`
  ``train_fn`` on it, so the Anakin update is bit-identical to the host path
  given the same collected batch (pinned by ``tests/test_algos/test_anakin.py``);
* SAC (and DroQ via the same ``make_sac_step_fn``): each in-scan iteration steps
  the envs once, writes the transitions into the PR-5
  :class:`~sheeprl_tpu.data.device_buffer.DeviceTransitionRing` layout carried
  through the scan (``make_scan_writer``), and runs ``replay_ratio`` gradient
  steps off the ring with in-jit uniform sampling (``make_sample_gather``).

Metrics (``Rewards/rew_avg``, episode lengths, ``Loss/*``, ``Health/*``) are
accumulated inside the scan carry, returned per dispatch as device futures and
drained at the existing log cadence — zero extra host syncs per step.  The
scan carry (env states, ring + counters, PRNG key, params, optimizer state)
round-trips through :class:`~sheeprl_tpu.checkpoint.manager.CheckpointManager`
for mid-run resume, and the flight recorder stages a device-side copy of the
carry post-dispatch (the dispatch DONATES it) exactly like the PR-5 fused ring
blocks.  See ``howto/anakin.md``.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Dict, Optional

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled, strict_guard
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.envs.jax import make_jax_env
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.obs import TrainingMonitor, flight_recorder
from sheeprl_tpu.obs import perf as obs_perf
from sheeprl_tpu.obs.health import health_enabled
from sheeprl_tpu.precision import train_policy
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import gae, polynomial_decay

EPISODE_SUM_KEYS = ("Episodes/return_sum", "Episodes/len_sum", "Episodes/count")


def anakin_enabled(cfg) -> bool:
    """The mode gate the entry points test before falling back to their host loop."""
    return bool(cfg.algo.get("anakin", False))


def anakin_env(cfg):
    """Build the pure-functional env + params from the config; hard errors beat a
    silent host fallback — the user asked for the fused mode explicitly."""
    if not bool(cfg.env.jax.get("enabled", False)):
        raise ValueError(
            "algo.anakin=True needs an on-device JAX environment: pick one with "
            "env=jax_cartpole / jax_pendulum / jax_mountain_car (or set "
            "env.jax.enabled=True with env.jax.env_id for a gymnax env)."
        )
    if jax.process_count() > 1:
        raise ValueError(
            "algo.anakin=True is single-process (the fused scan owns the whole "
            "env+learner state); use the host loops for multi-host runs."
        )
    env = make_jax_env(cfg.env.jax.env_id or cfg.env.id)
    return env, env.default_params()


def anakin_mlp_key(cfg) -> str:
    """Anakin envs expose ONE flat vector observation; map it to the single
    configured MLP key (the agents' obs-dict contract)."""
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    if cnn_keys or len(mlp_keys) != 1:
        raise ValueError(
            "algo.anakin=True supports exactly one MLP observation key and no CNN "
            f"keys (the jax envs are flat-vector); got cnn={cnn_keys} mlp={mlp_keys}."
        )
    return mlp_keys[0]


# --------------------------------------------------------------------- episodes
def init_episode_stats(num_envs: int) -> Dict[str, jax.Array]:
    """Per-env running episode accumulators + the dispatch-window sums, all carried
    through the scan (drained at the log cadence, never per step)."""
    return {
        "ep_return": jnp.zeros((num_envs,), jnp.float32),
        "ep_len": jnp.zeros((num_envs,), jnp.int32),
        "return_sum": jnp.zeros((), jnp.float32),
        "len_sum": jnp.zeros((), jnp.float32),
        "count": jnp.zeros((), jnp.float32),
    }


def reset_episode_sums(stats: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {
        **stats,
        "return_sum": jnp.zeros((), jnp.float32),
        "len_sum": jnp.zeros((), jnp.float32),
        "count": jnp.zeros((), jnp.float32),
    }


def update_episode_stats(stats: Dict[str, jax.Array], reward: jax.Array, done: jax.Array):
    """One vectorized env step's bookkeeping: accumulate running returns/lengths,
    fold finished episodes into the window sums, reset the finished envs."""
    ep_return = stats["ep_return"] + reward
    ep_len = stats["ep_len"] + 1
    d = done.astype(jnp.float32)
    return {
        "ep_return": ep_return * (1.0 - d),
        "ep_len": ep_len * (1 - done.astype(jnp.int32)),
        "return_sum": stats["return_sum"] + jnp.sum(ep_return * d),
        "len_sum": stats["len_sum"] + jnp.sum(ep_len.astype(jnp.float32) * d),
        "count": stats["count"] + jnp.sum(d),
    }


def episode_metrics(stats: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {
        "Episodes/return_sum": stats["return_sum"],
        "Episodes/len_sum": stats["len_sum"],
        "Episodes/count": stats["count"],
    }


class AnakinFutures:
    """Deferred per-dispatch metric futures (the Anakin cousin of
    ``utils.blocks.WindowedFutures``): ``track`` keeps the dispatch's metrics tree
    ON DEVICE, ``drain`` is the window's only blocking fetch — episode sums are
    folded into ``Rewards/rew_avg``/``Game/ep_len_avg`` and every other key feeds
    the aggregator.  Window wall-clock gives honest env-steps/s + grad-steps/s.

    Metric leaves may be scalars (plain Anakin) or carry a LEADING MEMBER AXIS
    (population dispatches, ``engine/population.py``).  Member-axis reductions,
    per metric (see ``howto/population.md``):

    * the PLAIN key keeps logging — as the cross-member mean — so existing
      dashboards stay meaningful;
    * ``Population/<key>/member_{m}`` logs each member's window value,
      ``Population/<key>/median`` the cross-member median, and
      ``Population/<key>/best`` the cross-member max (``Rewards/*`` / ``Game/*``
      / ``Episodes/*``) or min (``Loss/*``);
    * ``Rewards/rew_avg`` / ``Game/ep_len_avg`` derive per member from that
      member's episode sums (members with no finished episodes in the window
      are skipped), then reduce the same way.

    Everything still rides the window's single blocking ``device_get`` — zero
    extra host syncs per step regardless of the member count."""

    def __init__(self):
        self._pending = []
        self._window_env_steps = 0
        self._window_grad_steps = 0
        self._window_t0 = 0.0

    def track(self, metrics: Any, env_steps: int, grad_steps: int) -> None:
        if not self._pending and self._window_env_steps == 0:
            self._window_t0 = time.perf_counter()
        self._pending.append(metrics)
        self._window_env_steps += env_steps
        self._window_grad_steps += grad_steps

    def drain(self, aggregator: Optional[MetricAggregator]) -> Dict[str, float]:
        """Fetch every pending dispatch's metrics (one blocking device_get), feed
        the aggregator and return the window's derived rates/episode means plus
        any ``Population/*`` member reductions."""
        from sheeprl_tpu.engine.population import population_rows

        fetched = jax.device_get(self._pending) if self._pending else []
        self._pending.clear()
        ret_sum = len_sum = count = 0.0  # scalars or [K] member vectors
        window: Dict[str, list] = {}
        for tree in fetched:
            ret_sum = ret_sum + np.asarray(tree.pop("Episodes/return_sum", 0.0), np.float64)
            len_sum = len_sum + np.asarray(tree.pop("Episodes/len_sum", 0.0), np.float64)
            count = count + np.asarray(tree.pop("Episodes/count", 0.0), np.float64)
            for k, v in tree.items():
                arr = np.asarray(v)
                if arr.ndim == 0:  # plain Anakin: scalar leaves, historical path
                    if aggregator is not None:
                        aggregator.update(k, float(arr))
                else:  # population: leading member axis
                    if aggregator is not None:
                        aggregator.update(k, float(arr.mean()))
                    window.setdefault(k, []).append(arr)
        elapsed = max(time.perf_counter() - self._window_t0, 1e-9)
        out: Dict[str, float] = {}
        for k, arrs in window.items():
            out.update(population_rows(k, np.mean(np.stack(arrs), axis=0)))
        if np.ndim(count) == 0:
            if count > 0 and aggregator is not None:
                aggregator.update("Rewards/rew_avg", ret_sum / count)
                aggregator.update("Game/ep_len_avg", len_sum / count)
        else:
            with np.errstate(divide="ignore", invalid="ignore"):
                rew = np.where(count > 0, ret_sum / np.maximum(count, 1e-9), np.nan)
                length = np.where(count > 0, len_sum / np.maximum(count, 1e-9), np.nan)
            if np.isfinite(rew).any():
                if aggregator is not None:
                    aggregator.update("Rewards/rew_avg", float(np.nanmean(rew)))
                    aggregator.update("Game/ep_len_avg", float(np.nanmean(length)))
                out.update(population_rows("Rewards/rew_avg", rew))
                out.update(population_rows("Game/ep_len_avg", length))
        if self._window_env_steps > 0:
            out["Time/sps_env_interaction"] = self._window_env_steps / elapsed
        if self._window_grad_steps > 0:
            out["Time/sps_train"] = self._window_grad_steps / elapsed
        self._window_env_steps = 0
        self._window_grad_steps = 0
        return out


def reset_envs(env, env_params, num_envs: int, key: jax.Array):
    keys = jax.random.split(key, num_envs)
    return jax.vmap(env.reset, in_axes=(None, 0))(env_params, keys)


def stage_carry(recorder, carry, **scalars) -> None:
    """Post-dispatch flight-recorder staging: the dispatch DONATED the carry, so
    pre-step references are gone — stage a device-side copy (async, no host sync)
    of the state entering the NEXT dispatch, as the PR-5 fused ring blocks do."""
    if recorder is not None:
        recorder.stage_step(carry=jax.tree.map(jnp.copy, carry), scalars=scalars)


# -------------------------------------------------------------------------- PPO
def make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, obs_key: str, return_batch: bool = False):
    """One fused PPO training iteration: an on-device ``rollout_steps`` collection
    scan (vmapped env + acting policy), GAE, then the UNCHANGED
    ``PPOTrainFns.train_fn`` — calling the already-jitted update inlines the same
    program, which is what makes the Anakin update bit-identical to the host path
    on the same batch.  ``return_batch=True`` (tests/bench) also returns the
    collected batch + the exact key fed to ``train_fn``."""
    from sheeprl_tpu.algos.ppo.utils import sample_actions

    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    batch_n = rollout_steps * num_envs
    gamma, gae_lambda = cfg.algo.gamma, cfg.algo.gae_lambda
    clip_rewards = bool(cfg.env.clip_rewards)
    is_continuous = agent.is_continuous
    discrete_scalar = not is_continuous and len(agent.action_dims) == 1
    act_space = env.action_space(env_params)
    clip_act = is_continuous and bool(
        np.isfinite(act_space.low).all() and np.isfinite(act_space.high).all()
    )
    act_low = jnp.asarray(getattr(act_space, "low", 0.0), jnp.float32)
    act_high = jnp.asarray(getattr(act_space, "high", 0.0), jnp.float32)
    vstep = jax.vmap(env.step_autoreset, in_axes=(None, 0, 0, 0))
    # Precision boundary (howto/precision.md): a CAST COPY of the obs feeds the
    # acting forward; the stored trajectory keeps the env's f32 observations.
    cast_obs = train_policy(cfg).cast_to_compute

    def iteration(carry, clip_coef, ent_coef):
        params = carry["params"]
        stats0 = reset_episode_sums(carry["episode_stats"])

        def act_step(c, _):
            env_state, obs, key, stats = c
            key, k_act, k_step = jax.random.split(key, 3)
            actor_out, value = agent.apply(params, {obs_key: cast_obs(obs)})
            env_act, stored_act, logprob = sample_actions(k_act, actor_out, is_continuous)
            if clip_act:
                env_actions = jnp.clip(env_act, act_low, act_high)
            elif discrete_scalar:
                env_actions = env_act[..., 0].astype(jnp.int32)
            else:
                env_actions = env_act
            step_keys = jax.random.split(k_step, num_envs)
            env_state, next_obs, reward, done, _info = vstep(env_params, env_state, env_actions, step_keys)
            if clip_rewards:
                reward = jnp.clip(reward, -1, 1)
            stats = update_episode_stats(stats, reward, done)
            ys = {
                obs_key: obs,
                "actions": stored_act.reshape(num_envs, -1).astype(jnp.float32),
                "logprobs": logprob.reshape(num_envs),
                "values": value[..., 0],
                "rewards": reward.astype(jnp.float32),
                "dones": done.astype(jnp.float32),
            }
            return (env_state, next_obs, key, stats), ys

        (env_state, obs, key, stats), traj = jax.lax.scan(
            act_step, (carry["env_state"], carry["obs"], carry["key"], stats0), None, length=rollout_steps
        )
        _, next_value = agent.apply(params, {obs_key: cast_obs(obs)})
        returns, advantages = gae(
            traj["rewards"][..., None],
            traj["values"][..., None],
            traj["dones"][..., None],
            next_value[..., 0:1],
            rollout_steps,
            gamma,
            gae_lambda,
        )
        data = {
            obs_key: traj[obs_key],
            "actions": traj["actions"],
            "logprobs": traj["logprobs"],
            "values": traj["values"],
            "returns": returns[..., 0],
            "advantages": advantages[..., 0],
        }
        data = jax.tree.map(lambda x: x.reshape(batch_n, *x.shape[2:]), data)

        key, k_train = jax.random.split(key)
        params, opt_state, metrics = fns.train_fn(
            params, carry["opt_state"], data, k_train, clip_coef, ent_coef
        )
        metrics = {**metrics, **episode_metrics(stats)}
        new_carry = {
            "params": params,
            "opt_state": opt_state,
            "env_state": env_state,
            "obs": obs,
            "key": key,
            "episode_stats": stats,
        }
        if return_batch:
            return new_carry, metrics, data, k_train
        return new_carry, metrics

    return iteration


def ppo_anakin(ctx, cfg) -> None:
    """The Anakin PPO entry path (``algo.anakin=True``), called by
    ``sheeprl_tpu.algos.ppo.ppo.main``.  With ``algo.population.size=K`` (or a
    sweep) every piece of per-run state gains a leading member axis and K
    independent members train in the same single donated dispatch
    (``engine/population.py``; howto/population.md)."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns
    from sheeprl_tpu.algos.ppo.utils import AGGREGATOR_KEYS, test
    from sheeprl_tpu.engine.population import (
        PopulationSpec,
        member_keys,
        population_transform,
        set_injected_lr,
        slice_member,
        stack_members,
    )

    env, env_params = anakin_env(cfg)
    obs_key = anakin_mlp_key(cfg)
    pop = PopulationSpec.from_cfg(cfg, "ppo")
    members = pop.size if pop.enabled else 1
    log_dir = get_log_dir(cfg)
    if ctx.is_global_zero:
        save_config(cfg, Path(log_dir) / "config.yaml")
    logger = get_logger(cfg, log_dir)
    monitor = TrainingMonitor(cfg, log_dir)

    obs_space = gym.spaces.Dict({obs_key: env.observation_space(env_params)})
    act_space = env.action_space(env_params)
    agent, params = build_agent(ctx, act_space, obs_space, cfg)

    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    policy_steps_per_iter = num_envs * rollout_steps
    total_steps = int(cfg.algo.total_steps)
    num_updates = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1

    sweeps_lr = pop.enabled and pop.sweeps_lr("optimizer.lr")
    fns = PPOTrainFns(ctx, agent, cfg, [obs_key], num_updates, inject_lr=sweeps_lr)
    iteration = make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, obs_key)
    # The whole iteration is ONE donated jit: env scan + GAE + the update block —
    # for a population, lifted over the member axis first (howto/population.md).
    if pop.enabled:
        dispatch = obs_perf.instrument(
            cfg,
            "anakin/ppo_pop_dispatch",
            strict_guard(
                cfg,
                "anakin/ppo_pop_dispatch",
                jax.jit(population_transform(iteration, pop.vectorize, n_args=2), donate_argnums=(0,)),
            ),
        )
    else:
        dispatch = obs_perf.instrument(
            cfg,
            "anakin/ppo_dispatch",
            strict_guard(cfg, "anakin/ppo_dispatch", jax.jit(iteration, donate_argnums=(0,))),
        )

    if pop.enabled:
        # Per-member init: member 0 draws exactly what the plain path draws
        # (population.size=1 is then bit-identical to plain Anakin); members
        # m > 0 get fresh init draws / folded key streams.
        member_params = [params] + [build_agent(ctx, act_space, obs_space, cfg)[1] for _ in range(1, members)]
        lr_values = pop.values("optimizer.lr", cfg.algo.optimizer.lr)
        member_carries = []
        reset_keys = member_keys(ctx.local_rng(), members)
        carry_keys = member_keys(ctx.rng(), members)
        for m in range(members):
            opt_m = fns.opt.init(member_params[m])
            if sweeps_lr:
                opt_m = set_injected_lr(opt_m, lr_values[m])
            env_state_m, obs0_m = reset_envs(env, env_params, num_envs, reset_keys[m])
            member_carries.append(
                {
                    "params": member_params[m],
                    "opt_state": ctx.replicate(opt_m),
                    "env_state": env_state_m,
                    "obs": obs0_m,
                    "key": carry_keys[m],
                    "episode_stats": init_episode_stats(num_envs),
                }
            )
        carry = stack_members(member_carries)
    else:
        env_state, obs0 = reset_envs(env, env_params, num_envs, ctx.local_rng())
        carry = {
            "params": params,
            "opt_state": ctx.replicate(fns.opt.init(params)),
            "env_state": env_state,
            "obs": obs0,
            "key": ctx.rng(),
            "episode_stats": init_episode_stats(num_envs),
        }

    aggregator = MetricAggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(AGGREGATOR_KEYS | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    futures = AnakinFutures()
    recorder = flight_recorder.get_active()
    if recorder is not None:
        recorder.arm_replay("sheeprl_tpu.engine.anakin:replay_update", num_updates=num_updates)

    start_update, policy_step, last_log, last_checkpoint = 1, 0, 0, 0
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from, templates={"carry": jax.device_get(carry)}
        )
        carry = ctx.replicate(state["carry"])
        start_update = state["update"] + 1
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)

    grad_steps_per_update = fns.grad_steps_per_update
    clip0 = pop.values("clip_coef", cfg.algo.clip_coef) if pop.enabled else [float(cfg.algo.clip_coef)]
    ent0 = pop.values("ent_coef", cfg.algo.ent_coef) if pop.enabled else [float(cfg.algo.ent_coef)]

    guard = TrainingGuard(cfg, log_dir)

    def save_ckpt():
        nonlocal last_checkpoint
        with monitor.phase("checkpoint"):
            path = ckpt_manager.save(
                policy_step,
                {
                    "carry": carry,
                    "update": update,
                    "policy_step": policy_step,
                    "last_log": last_log,
                    "last_checkpoint": policy_step,
                },
            )
        last_checkpoint = policy_step
        return path

    for update in range(start_update, num_updates + 1):
        monitor.advance()
        clip_coef, ent_coef = list(clip0), list(ent0)
        if cfg.algo.anneal_clip_coef:  # per member, each from its own swept initial value
            clip_coef = [
                polynomial_decay(update, initial=c, final=0.0, max_decay_steps=num_updates) for c in clip_coef
            ]
        if cfg.algo.anneal_ent_coef:
            ent_coef = [
                polynomial_decay(update, initial=e, final=0.0, max_decay_steps=num_updates) for e in ent_coef
            ]
        if pop.enabled:
            coef_args = (jnp.asarray(clip_coef, jnp.float32), jnp.asarray(ent_coef, jnp.float32))
            staged_coefs = {"clip_coef": [float(c) for c in clip_coef], "ent_coef": [float(e) for e in ent_coef]}
        else:
            coef_args = (float(clip_coef[0]), float(ent_coef[0]))
            staged_coefs = {"clip_coef": float(clip_coef[0]), "ent_coef": float(ent_coef[0])}
        with timer("Time/train_time"), monitor.phase("dispatch"):
            carry, metrics = dispatch(carry, *coef_args)
        futures.track(metrics, policy_steps_per_iter * members, grad_steps_per_update * members)
        policy_step += policy_steps_per_iter
        stage_carry(recorder, carry, update=update, **staged_coefs)

        if logger is not None and (
            policy_step - last_log >= cfg.metric.log_every or update == num_updates or cfg.dry_run
        ):
            out = futures.drain(aggregator)  # the window's only blocking device sync
            out.update(aggregator.compute())
            if not sweeps_lr:
                out["Params/lr"] = (
                    float(fns.lr_schedule(update * grad_steps_per_update))
                    if fns.lr_schedule is not None
                    else float(cfg.algo.optimizer.lr)
                )
            if pop.enabled:  # the sweep table is static — log it with every flush
                for name, values in pop.sweep.items():
                    for m, v in enumerate(values):
                        out[f"Population/Params/{name}/member_{m}"] = float(v)
            monitor.log_metrics(logger, out, policy_step)
            aggregator.reset()
            last_log = policy_step

        if (
            cfg.checkpoint.every > 0
            and (policy_step - last_checkpoint) >= cfg.checkpoint.every
            or update == num_updates
            and cfg.checkpoint.save_last
        ):
            save_ckpt()
        guard.boundary(policy_step, save_ckpt)

    monitor.close()
    if cfg.algo.run_test and ctx.is_global_zero:
        # population: the greedy test episode runs member 0's policy (the member
        # continuing the run's base seed stream — see howto/population.md)
        test_params = slice_member(carry["params"], 0) if pop.enabled else carry["params"]
        reward = test(agent, test_params, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if logger is not None:
        logger.close()


# -------------------------------------------------------------------------- SAC
def make_sac_anakin_dispatch(env, env_params, actor, critic, cfg, act_space, ring, batch_size: int, inject_lr=()):
    """Builder of fused SAC Anakin dispatch programs: ``builder(steps,
    grad_per_step, train)`` returns the python function for a ``steps``-iteration
    scan where each iteration steps the vmapped envs once, writes the transition
    row into the ring arrays CARRIED through the scan
    (:meth:`DeviceTransitionRing.make_scan_writer`), and — when ``train`` — runs
    ``grad_per_step`` :func:`~sheeprl_tpu.algos.sac.sac.make_sac_step_fn` updates
    off in-jit uniform ring sampling.  ``train=False`` is the prefill program
    (uniform random actions, no updates).  DroQ rides the same shape through its
    own step fn."""
    from sheeprl_tpu.algos.sac.sac import make_sac_step_fn

    # inject_lr: population lr sweeps carry per-member rates in the opt state.
    actor_opt, critic_opt, alpha_opt, step_update = make_sac_step_fn(
        actor, critic, cfg, act_space, inject_lr=inject_lr
    )
    sample_gather = ring.make_sample_gather(batch_size)
    write_row = ring.make_scan_writer()
    num_envs = ring.n_envs
    cap = ring.capacity
    strict = strict_enabled(cfg)
    health = health_enabled(cfg)
    clip_rewards = bool(cfg.env.clip_rewards)
    act_low = jnp.asarray(act_space.low, jnp.float32)
    act_high = jnp.asarray(act_space.high, jnp.float32)
    rescale = bool(np.isfinite(act_space.low).all() and np.isfinite(act_space.high).all())
    vstep = jax.vmap(env.step_autoreset, in_axes=(None, 0, 0, 0))
    vsample = jax.vmap(env.sample_action, in_axes=(None, 0))
    # Precision boundary: acting casts a COPY of the obs; ring rows keep the
    # buffer's storage dtype (buffer.store_dtype handles the ring plane).
    cast_obs = train_policy(cfg).cast_to_compute

    def builder(steps: int, grad_per_step: int, train: bool):
        def dispatch(carry):
            def iter_step(c, _):
                params, o_state, env_state, obs, arrays, rows_added, gstep, key, stats = c
                key, k_act, k_step = jax.random.split(key, 3)
                if train:  # trace-time constant: prefill compiles its own program
                    mean, log_std = actor.apply(params["actor"], cast_obs(obs))
                    tanh_act = actor.dist(mean, log_std).sample(k_act)
                else:
                    raw = vsample(env_params, jax.random.split(k_act, num_envs))
                    tanh_act = 2 * (raw - act_low) / (act_high - act_low) - 1 if rescale else raw
                env_act = act_low + (tanh_act + 1) * 0.5 * (act_high - act_low) if rescale else tanh_act
                step_keys = jax.random.split(k_step, num_envs)
                env_state, next_obs, reward, done, info = vstep(env_params, env_state, env_act, step_keys)
                if clip_rewards:
                    reward = jnp.clip(reward, -1, 1)
                stats = update_episode_stats(stats, reward, done)
                rows = {
                    "obs": obs,
                    # the TRUE final obs of finishing episodes (autoreset already
                    # swapped ``next_obs``), mirroring the host loops' final_obs fixup
                    "next_obs": info["final_obs"],
                    "actions": tanh_act,
                    "rewards": reward[:, None].astype(jnp.float32),
                    # truncated episodes still bootstrap (done=0 in the TD target)
                    "dones": info["terminated"][:, None].astype(jnp.float32),
                }
                arrays = write_row(arrays, rows, rows_added)
                rows_added = rows_added + 1
                metrics = {}
                if train and grad_per_step > 0:
                    filled = jnp.minimum(rows_added, cap)

                    def gstep_fn(cc, x):
                        p, o = cc
                        count, k = x
                        k_sample, k_update = jax.random.split(k)
                        batch, age_metrics = sample_gather(arrays, filled, rows_added, k_sample)
                        p, o, m = step_update(p, o, count, batch, k_update)
                        if health:  # replay staleness rides the same metrics tree
                            m = {**m, **age_metrics}
                        return (p, o), m

                    key, k_grad = jax.random.split(key)
                    counts = gstep + jnp.arange(grad_per_step, dtype=jnp.int32)
                    gkeys = jax.random.split(k_grad, grad_per_step)
                    (params, o_state), metrics = jax.lax.scan(
                        gstep_fn, (params, o_state), (counts, gkeys)
                    )
                    metrics = jax.tree.map(jnp.mean, metrics)
                    gstep = gstep + grad_per_step
                return (params, o_state, env_state, next_obs, arrays, rows_added, gstep, key, stats), metrics

            stats0 = reset_episode_sums(carry["episode_stats"])
            init = (
                carry["params"],
                carry["opt_state"],
                carry["env_state"],
                carry["obs"],
                carry["ring"],
                carry["rows_added"],
                carry["gstep"],
                carry["key"],
                stats0,
            )
            (params, o_state, env_state, obs, arrays, rows_added, gstep, key, stats), metrics = jax.lax.scan(
                iter_step, init, None, length=steps
            )
            metrics = jax.tree.map(jnp.mean, metrics)
            metrics = {**metrics, **episode_metrics(stats)}
            metrics = maybe_inject_nonfinite(cfg, metrics)
            if strict:  # trace-time constant: the callback only exists in strict runs
                nan_scan(metrics, "anakin/sac_dispatch")
            new_carry = {
                "params": params,
                "opt_state": o_state,
                "env_state": env_state,
                "obs": obs,
                "ring": arrays,
                "rows_added": rows_added,
                "gstep": gstep,
                "key": key,
                "episode_stats": stats,
            }
            return new_carry, metrics

        return dispatch

    return actor_opt, critic_opt, alpha_opt, builder


class SacAnakinDispatcher:
    """Compile-once cache of the SAC dispatch programs keyed on (steps,
    grad_per_step, train) — the steady state uses exactly one program; the
    prefill and a tail remainder add at most two more.  ``transform`` lifts each
    program over the population member axis before jitting
    (``engine/population.py``: ``lax.map`` by default, ``vmap`` when
    ``algo.population.vectorize=True``)."""

    def __init__(self, builder, cfg, transform=None):
        self._builder = builder
        self._cfg = cfg
        self._transform = transform
        self._programs: dict = {}

    def __call__(self, carry, steps: int, grad_per_step: int, train: bool):
        sig = (steps, grad_per_step, train)
        prog = self._programs.get(sig)
        if prog is None:
            fn = self._builder(steps, grad_per_step, train)
            name = f"anakin/sac_dispatch_{steps}x{grad_per_step}{'t' if train else 'p'}"
            if self._transform is not None:
                fn = self._transform(fn)
                name = f"anakin/sac_pop_dispatch_{steps}x{grad_per_step}{'t' if train else 'p'}"
            prog = obs_perf.instrument(
                self._cfg, name, strict_guard(self._cfg, name, jax.jit(fn, donate_argnums=(0,)))
            )
            self._programs[sig] = prog
        return prog(carry)


def sac_anakin(ctx, cfg) -> None:
    """The Anakin SAC entry path (``algo.anakin=True``), called by
    ``sheeprl_tpu.algos.sac.sac.main``.  ``algo.population.size=K`` trains K
    independent members — each with its own params, optimizer state, env states,
    replay ring and PRNG streams — in one donated dispatch
    (``engine/population.py``; howto/population.md)."""
    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.utils import AGGREGATOR_KEYS, test
    from sheeprl_tpu.data.device_buffer import DeviceTransitionRing, resolve_store_dtype
    from sheeprl_tpu.engine.population import (
        PopulationSpec,
        member_keys,
        population_transform,
        set_injected_lr,
        slice_member,
        stack_members,
    )

    env, env_params = anakin_env(cfg)
    mlp_key = anakin_mlp_key(cfg)
    pop = PopulationSpec.from_cfg(cfg, "sac")
    members = pop.size if pop.enabled else 1
    replay_ratio = float(cfg.algo.replay_ratio)
    grad_per_step = int(round(replay_ratio))
    if grad_per_step < 1 or abs(replay_ratio - grad_per_step) > 1e-9:
        raise ValueError(
            f"algo.anakin=True needs an integer algo.replay_ratio >= 1 (the fused "
            f"scan runs a static number of gradient steps per env step); got "
            f"{replay_ratio}."
        )

    log_dir = get_log_dir(cfg)
    if ctx.is_global_zero:
        save_config(cfg, Path(log_dir) / "config.yaml")
    logger = get_logger(cfg, log_dir)
    monitor = TrainingMonitor(cfg, log_dir)

    obs_space_box = env.observation_space(env_params)
    act_space = env.action_space(env_params)
    if not isinstance(act_space, gym.spaces.Box):
        raise ValueError("SAC anakin needs a continuous (Box) jax env, e.g. env=jax_pendulum")
    obs_space = gym.spaces.Dict({mlp_key: obs_space_box})
    actor, critic, params = build_agent(ctx, act_space, obs_space, cfg)
    # Donation safety: critic_target aliases critic's buffers at init — a donated
    # carry must not contain the same buffer twice (see the host ring path).
    params = jax.tree.map(jnp.copy, params)

    num_envs = int(cfg.env.num_envs)
    obs_dim = int(np.prod(obs_space_box.shape))
    act_dim = int(np.prod(act_space.shape))
    batch_size = int(cfg.algo.per_rank_batch_size)
    capacity = max(int(cfg.buffer.size) // max(num_envs, 1), 1)
    ring = DeviceTransitionRing(
        capacity,
        num_envs,
        {
            "obs": ((obs_dim,), jnp.float32),
            "next_obs": ((obs_dim,), jnp.float32),
            "actions": ((act_dim,), jnp.float32),
            "rewards": ((1,), jnp.float32),
            "dones": ((1,), jnp.float32),
        },
        store_dtype=resolve_store_dtype(cfg.buffer.get("store_dtype")),
    )
    inject = tuple(n for n in ("actor", "critic", "alpha") if f"{n}.optimizer.lr" in pop.sweep)
    actor_opt, critic_opt, alpha_opt, builder = make_sac_anakin_dispatch(
        env, env_params, actor, critic, cfg, act_space, ring, batch_size, inject_lr=inject
    )

    def init_opt_state(p, member=0):
        o = {
            "actor": actor_opt.init(p["actor"]),
            "critic": critic_opt.init(p["critic"]),
            "alpha": alpha_opt.init(p["log_alpha"]),
        }
        for n in inject:  # stamp the member's swept rate into its own state
            o[n] = set_injected_lr(o[n], pop.sweep[f"{n}.optimizer.lr"][member])
        return ctx.replicate(o)

    if pop.enabled:
        dispatcher = SacAnakinDispatcher(
            builder, cfg, transform=lambda fn: population_transform(fn, pop.vectorize)
        )
        # Per-member init: member 0 draws exactly what the plain path draws
        # (population.size=1 is bit-identical to plain Anakin); m > 0 members
        # get fresh param inits and folded key streams.
        member_params = [params] + [
            jax.tree.map(jnp.copy, build_agent(ctx, act_space, obs_space, cfg)[2]) for _ in range(1, members)
        ]
        reset_keys = member_keys(ctx.local_rng(), members)
        carry_keys = member_keys(ctx.rng(), members)
        member_carries = []
        for m in range(members):
            env_state_m, obs0_m = reset_envs(env, env_params, num_envs, reset_keys[m])
            member_carries.append(
                {
                    "params": member_params[m],
                    "opt_state": init_opt_state(member_params[m], m),
                    "env_state": env_state_m,
                    "obs": obs0_m,
                    "rows_added": jnp.zeros((), jnp.int32),
                    "gstep": jnp.zeros((), jnp.int32),
                    "key": carry_keys[m],
                    "episode_stats": init_episode_stats(num_envs),
                }
            )
        carry = stack_members(member_carries)
        # member-axis ring arrays built at the stacked shape directly (stacking
        # K per-member copies would transiently allocate K extra rings)
        carry["ring"] = ring.population_arrays(members)
    else:
        dispatcher = SacAnakinDispatcher(builder, cfg)
        env_state, obs0 = reset_envs(env, env_params, num_envs, ctx.local_rng())
        carry = {
            "params": params,
            "opt_state": init_opt_state(params),
            "env_state": env_state,
            "obs": obs0,
            "ring": ring.arrays,
            "rows_added": jnp.zeros((), jnp.int32),
            "gstep": jnp.zeros((), jnp.int32),
            "key": ctx.rng(),
            "episode_stats": init_episode_stats(num_envs),
        }

    aggregator = MetricAggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(AGGREGATOR_KEYS | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    futures = AnakinFutures()
    recorder = flight_recorder.get_active()
    if recorder is not None:
        recorder.arm_replay("sheeprl_tpu.engine.anakin:replay_update")

    total_steps = int(cfg.algo.total_steps)
    num_iters = max(total_steps // max(num_envs, 1), 1) if not cfg.dry_run else 1
    prefill_steps = int(cfg.algo.learning_starts) // max(num_envs, 1) if not cfg.dry_run else 0
    prefill_steps = min(prefill_steps, num_iters - 1) if num_iters > 1 else 0
    steps_per_dispatch = max(int(cfg.algo.anakin_steps_per_dispatch), 1) if not cfg.dry_run else 1

    iter_num, policy_step, last_log, last_checkpoint = 0, 0, 0, 0
    resumed = False
    if cfg.checkpoint.get("resume_from"):
        ckpt_carry = carry if cfg.buffer.checkpoint else {k: v for k, v in carry.items() if k != "ring"}
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from, templates={"carry": jax.device_get(ckpt_carry)}
        )
        restored = ctx.replicate(state["carry"])
        if "ring" not in restored:
            # buffer.checkpoint=False dropped the ring: restart replay from empty
            # (rows_added derives the in-jit sampling range, so it resets too).
            restored = {**restored, "ring": carry["ring"], "rows_added": carry["rows_added"]}
        carry = restored
        iter_num = state["iter_num"]
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)
        resumed = True

    def _maybe_log(final: bool) -> None:
        nonlocal last_log
        if logger is not None and (
            policy_step - last_log >= cfg.metric.log_every or final or cfg.dry_run
        ):
            out = futures.drain(aggregator)  # the window's only blocking device sync
            out.update(aggregator.compute())
            if policy_step > 0:
                out["Params/replay_ratio"] = grad_per_step  # static by construction
            if pop.enabled:  # the sweep table is static — log it with every flush
                for name, values in pop.sweep.items():
                    for m, v in enumerate(values):
                        out[f"Population/Params/{name}/member_{m}"] = float(v)
            monitor.log_metrics(logger, out, policy_step)
            aggregator.reset()
            last_log = policy_step

    def save_ckpt():
        nonlocal last_checkpoint
        ckpt_carry = carry if cfg.buffer.checkpoint else {k: v for k, v in carry.items() if k != "ring"}
        with monitor.phase("checkpoint"):
            path = ckpt_manager.save(
                policy_step,
                {
                    "carry": ckpt_carry,
                    "iter_num": iter_num,
                    "policy_step": policy_step,
                    "last_log": last_log,
                    "last_checkpoint": policy_step,
                },
            )
        last_checkpoint = policy_step
        return path

    def _maybe_checkpoint(final: bool) -> None:
        if (
            cfg.checkpoint.every > 0
            and (policy_step - last_checkpoint) >= cfg.checkpoint.every
            or final
            and cfg.checkpoint.save_last
        ):
            save_ckpt()

    guard = TrainingGuard(cfg, log_dir)

    # Prefill: one dispatch of uniform random acting (a resumed run already has a
    # trained policy and a restored ring — skip it, like the host loops).
    if prefill_steps > 0 and iter_num < prefill_steps and not resumed:
        monitor.advance()
        with timer("Time/env_interaction_time"), monitor.phase("dispatch"):
            carry, metrics = dispatcher(carry, prefill_steps - iter_num, 0, False)
        futures.track(metrics, (prefill_steps - iter_num) * num_envs * members, 0)
        policy_step += (prefill_steps - iter_num) * num_envs
        iter_num = prefill_steps
        stage_carry(recorder, carry, iter_num=iter_num)
        guard.boundary(policy_step, save_ckpt)

    while iter_num < num_iters:
        monitor.advance()
        steps = min(steps_per_dispatch, num_iters - iter_num)
        with timer("Time/train_time"), monitor.phase("dispatch"):
            carry, metrics = dispatcher(carry, steps, grad_per_step, True)
        futures.track(metrics, steps * num_envs * members, steps * grad_per_step * members)
        policy_step += steps * num_envs
        iter_num += steps
        stage_carry(recorder, carry, iter_num=iter_num)
        final = iter_num >= num_iters
        _maybe_log(final)
        _maybe_checkpoint(final)
        guard.boundary(policy_step, save_ckpt)

    monitor.close()
    if cfg.algo.run_test and ctx.is_global_zero:
        # population: the greedy test episode runs member 0's policy
        test_params = slice_member(carry["params"], 0) if pop.enabled else carry["params"]
        reward = test(actor, test_params, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if logger is not None:
        logger.close()


# ------------------------------------------------------------------ replay
def replay_update(cfg, dump_dir, member: Optional[int] = None):
    """Flight-recorder replay builder: an Anakin blackbox stages the carry
    entering the NEXT dispatch (post-dispatch device-side copy — the dispatch
    donates its input), so replay rebuilds the fused program from the dumped
    config and re-executes that one dispatch on CPU.

    Population dumps (``algo.population``) stage the FULL stacked carry.
    ``member=None`` replays the whole population dispatch; ``member=m`` slices
    member ``m``'s carry off the member axis and replays it through the PLAIN
    single-member program with that member's swept hyperparameters — under the
    default ``vectorize=False`` mode this is the exact program the member ran
    (``python -m sheeprl_tpu.obs.replay_blackbox <dir> --member m``)."""
    from sheeprl_tpu.engine.population import PopulationSpec, population_transform, slice_member
    from sheeprl_tpu.obs import replay_blackbox
    from sheeprl_tpu.parallel.mesh import make_mesh_context

    ctx = make_mesh_context(cfg)
    env, env_params = anakin_env(cfg)
    obs_key = anakin_mlp_key(cfg)
    obs_space = gym.spaces.Dict({obs_key: env.observation_space(env_params)})
    act_space = env.action_space(env_params)
    num_envs = int(cfg.env.num_envs)
    algo_name = str(cfg.algo.name)
    pop = PopulationSpec.from_cfg(cfg, "ppo" if algo_name.startswith("ppo") else "sac")
    if member is not None and not pop.enabled:
        raise ValueError("--member replay needs a population dump (algo.population in the dumped config)")
    if member is not None and not 0 <= int(member) < pop.size:
        raise ValueError(f"--member {member} out of range for population size {pop.size}")

    def pop_template(template):
        """Population dumps stage the stacked carry: stack K structure copies."""
        if not pop.enabled:
            return template
        return jax.tree.map(lambda x: jnp.stack([x] * pop.size), template)

    env_state0, obs0 = reset_envs(env, env_params, num_envs, jax.random.PRNGKey(0))

    if algo_name.startswith("ppo"):
        from sheeprl_tpu.algos.ppo.agent import build_agent
        from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns

        agent, params0 = build_agent(ctx, act_space, obs_space, cfg)
        raw = replay_blackbox.load_state(dump_dir)
        num_updates = int(raw["statics"].get("num_updates", 1))
        fns = PPOTrainFns(
            ctx, agent, cfg, [obs_key], num_updates, inject_lr=pop.enabled and pop.sweeps_lr("optimizer.lr")
        )
        template = {
            "params": params0,
            "opt_state": fns.opt.init(params0),
            "env_state": env_state0,
            "obs": obs0,
            "key": jax.random.PRNGKey(0),
            "episode_stats": init_episode_stats(num_envs),
        }
        state = replay_blackbox.load_state(dump_dir, {"carry": jax.device_get(pop_template(template))})
        iteration = make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, obs_key)
        scalars = state.get("scalars", {})
        clip = scalars.get("clip_coef", cfg.algo.clip_coef)
        ent = scalars.get("ent_coef", cfg.algo.ent_coef)
        staged = ctx.replicate(state["carry"])
        if pop.enabled and member is None:
            clip = np.broadcast_to(np.asarray(clip, np.float32), (pop.size,))
            ent = np.broadcast_to(np.asarray(ent, np.float32), (pop.size,))
            carry, metrics = jax.jit(population_transform(iteration, pop.vectorize, n_args=2))(
                staged, jnp.asarray(clip), jnp.asarray(ent)
            )
        else:
            if member is not None:
                staged = slice_member(staged, int(member))
                clip = np.reshape(np.broadcast_to(np.asarray(clip, np.float64), (pop.size,)), -1)[int(member)]
                ent = np.reshape(np.broadcast_to(np.asarray(ent, np.float64), (pop.size,)), -1)[int(member)]
            carry, metrics = jax.jit(iteration)(staged, float(clip), float(ent))
    else:
        from sheeprl_tpu.algos.sac.agent import build_agent
        from sheeprl_tpu.data.device_buffer import DeviceTransitionRing, resolve_store_dtype

        actor, critic, params0 = build_agent(ctx, act_space, obs_space, cfg)
        obs_dim = int(np.prod(obs_space[obs_key].shape))
        act_dim = int(np.prod(act_space.shape))
        capacity = max(int(cfg.buffer.size) // max(num_envs, 1), 1)
        ring = DeviceTransitionRing(
            capacity,
            num_envs,
            {
                "obs": ((obs_dim,), jnp.float32),
                "next_obs": ((obs_dim,), jnp.float32),
                "actions": ((act_dim,), jnp.float32),
                "rewards": ((1,), jnp.float32),
                "dones": ((1,), jnp.float32),
            },
            store_dtype=resolve_store_dtype(cfg.buffer.get("store_dtype")),
        )
        inject = tuple(n for n in ("actor", "critic", "alpha") if f"{n}.optimizer.lr" in pop.sweep)
        actor_opt, critic_opt, alpha_opt, builder = make_sac_anakin_dispatch(
            env, env_params, actor, critic, cfg, act_space, ring, int(cfg.algo.per_rank_batch_size),
            inject_lr=inject,
        )
        template = {
            "params": params0,
            "opt_state": {
                "actor": actor_opt.init(params0["actor"]),
                "critic": critic_opt.init(params0["critic"]),
                "alpha": alpha_opt.init(params0["log_alpha"]),
            },
            "env_state": env_state0,
            "obs": obs0,
            "ring": ring.arrays,
            "rows_added": jnp.zeros((), jnp.int32),
            "gstep": jnp.zeros((), jnp.int32),
            "key": jax.random.PRNGKey(0),
            "episode_stats": init_episode_stats(num_envs),
        }
        state = replay_blackbox.load_state(dump_dir, {"carry": jax.device_get(pop_template(template))})
        grad_per_step = int(round(float(cfg.algo.replay_ratio)))
        program = builder(1, grad_per_step, True)
        staged = ctx.replicate(state["carry"])
        if pop.enabled and member is None:
            carry, metrics = jax.jit(population_transform(program, pop.vectorize))(staged)
        else:
            if member is not None:
                staged = slice_member(staged, int(member))
            carry, metrics = jax.jit(program)(staged)

    host_metrics = jax.device_get(metrics)
    import optax

    out = {
        "metrics": host_metrics,
        "new_param_norm": float(jax.device_get(optax.global_norm(carry["params"]))),
    }
    if member is not None:
        out["member"] = int(member)
    return out


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): BOTH Anakin
    dispatch programs — the fused PPO iteration (env scan + GAE + the unchanged
    ``PPOTrainFns.train_fn``) and the fused SAC dispatch (env step + ring write +
    in-jit-sampled gradient steps) — each as the DONATED jit the engine
    dispatches, at tiny vmapped-env shapes."""
    from sheeprl_tpu.algos.ppo.agent import build_agent as build_ppo_agent
    from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns
    from sheeprl_tpu.algos.sac.agent import build_agent as build_sac_agent
    from sheeprl_tpu.analysis.ir.synth import compose_tiny, tiny_ctx
    from sheeprl_tpu.analysis.ir.types import AuditEntry
    from sheeprl_tpu.data.device_buffer import DeviceTransitionRing, resolve_store_dtype

    entries = []

    # ------------------------------------------------------------- PPO dispatch
    cfg = compose_tiny(
        [
            "exp=ppo",
            "env=jax_cartpole",
            "algo.anakin=True",
            "algo.mlp_keys.encoder=[state]",
            "algo.rollout_steps=4",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.encoder.mlp_features_dim=8",
            "env.num_envs=2",
        ]
    )
    ctx = tiny_ctx(cfg)
    env, env_params = anakin_env(cfg)
    obs_key = anakin_mlp_key(cfg)
    obs_space = gym.spaces.Dict({obs_key: env.observation_space(env_params)})
    act_space = env.action_space(env_params)
    agent, params = build_ppo_agent(ctx, act_space, obs_space, cfg)
    num_envs = int(cfg.env.num_envs)
    fns = PPOTrainFns(ctx, agent, cfg, [obs_key], num_updates=4)
    iteration = make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, obs_key)
    dispatch = jax.jit(iteration, donate_argnums=(0,))
    env_state, obs0 = reset_envs(env, env_params, num_envs, jax.random.PRNGKey(1))
    carry = {
        "params": params,
        "opt_state": fns.opt.init(params),
        "env_state": env_state,
        "obs": obs0,
        "key": jax.random.PRNGKey(0),
        "episode_stats": init_episode_stats(num_envs),
    }
    entries.append(
        AuditEntry(
            name="anakin/ppo_dispatch",
            fn=dispatch,
            args=(carry, 0.2, 0.0),
            covers=("anakin_ppo",),
            precision=str(cfg.mesh.precision),
        )
    )

    # Population variant (K=2, default member-scan mode): the same iteration
    # lifted over the member axis — audited as its own donated program because
    # the member axis must thread through every carry consumer without breaking
    # the donation contract (IR001) or blowing the compile-memory budget (IR006).
    from sheeprl_tpu.engine.population import population_transform

    pop_carry = jax.tree.map(lambda x: jnp.stack([x, x]), carry)
    pop_dispatch = jax.jit(population_transform(iteration, vectorize=False, n_args=2), donate_argnums=(0,))
    entries.append(
        AuditEntry(
            name="anakin/ppo_pop_dispatch",
            fn=pop_dispatch,
            args=(pop_carry, jnp.full((2,), 0.2, jnp.float32), jnp.zeros((2,), jnp.float32)),
            covers=("anakin_ppo_pop",),
            precision=str(cfg.mesh.precision),
        )
    )

    # ------------------------------------------------------------- SAC dispatch
    cfg = compose_tiny(
        [
            "exp=sac",
            "env=jax_pendulum",
            "algo.anakin=True",
            "algo.mlp_keys.encoder=[state]",
            "algo.hidden_size=8",
            "algo.per_rank_batch_size=4",
            "algo.replay_ratio=1",
            "env.num_envs=2",
            "buffer.size=64",
        ]
    )
    ctx = tiny_ctx(cfg)
    env, env_params = anakin_env(cfg)
    mlp_key = anakin_mlp_key(cfg)
    obs_space_box = env.observation_space(env_params)
    act_space = env.action_space(env_params)
    obs_space = gym.spaces.Dict({mlp_key: obs_space_box})
    actor, critic, params = build_sac_agent(ctx, act_space, obs_space, cfg)
    params = jax.tree.map(jnp.copy, params)  # donation safety (critic_target aliases)
    num_envs = int(cfg.env.num_envs)
    obs_dim = int(np.prod(obs_space_box.shape))
    act_dim = int(np.prod(act_space.shape))
    capacity = max(int(cfg.buffer.size) // max(num_envs, 1), 1)
    ring = DeviceTransitionRing(
        capacity,
        num_envs,
        {
            "obs": ((obs_dim,), jnp.float32),
            "next_obs": ((obs_dim,), jnp.float32),
            "actions": ((act_dim,), jnp.float32),
            "rewards": ((1,), jnp.float32),
            "dones": ((1,), jnp.float32),
        },
        store_dtype=resolve_store_dtype(cfg.buffer.get("store_dtype")),
    )
    actor_opt, critic_opt, alpha_opt, builder = make_sac_anakin_dispatch(
        env, env_params, actor, critic, cfg, act_space, ring, int(cfg.algo.per_rank_batch_size)
    )
    env_state, obs0 = reset_envs(env, env_params, num_envs, jax.random.PRNGKey(1))
    carry = {
        "params": params,
        "opt_state": {
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
            "alpha": alpha_opt.init(params["log_alpha"]),
        },
        "env_state": env_state,
        "obs": obs0,
        "ring": ring.arrays,
        "rows_added": jnp.zeros((), jnp.int32),
        "gstep": jnp.zeros((), jnp.int32),
        "key": jax.random.PRNGKey(0),
        "episode_stats": init_episode_stats(num_envs),
    }
    dispatch = jax.jit(builder(2, 1, True), donate_argnums=(0,))
    entries.append(
        AuditEntry(
            name="anakin/sac_dispatch",
            fn=dispatch,
            args=(carry,),
            covers=("anakin_sac",),
            precision=str(cfg.mesh.precision),
        )
    )

    # Population variant (K=2): ring arrays + counters + params all gain the
    # member axis; the fused env-step/ring-write/update program is unchanged.
    pop_carry = jax.tree.map(lambda x: jnp.stack([x, x]), carry)
    pop_dispatch = jax.jit(population_transform(builder(2, 1, True), vectorize=False), donate_argnums=(0,))
    entries.append(
        AuditEntry(
            name="anakin/sac_pop_dispatch",
            fn=pop_dispatch,
            args=(pop_carry,),
            covers=("anakin_sac_pop",),
            precision=str(cfg.mesh.precision),
        )
    )

    # ----------------------------------------------------- bf16 algo.precision
    # The same two dispatch programs with mesh.precision pinned to fp32 and the
    # algo.precision=bf16 knob doing ALL the work — IR002 then proves the
    # algo-level override alone puts bf16 on the dots (params stay f32; the
    # existing entries above already exercise mesh-inherited bf16-mixed).
    cfg = compose_tiny(
        [
            "exp=ppo",
            "env=jax_cartpole",
            "algo.anakin=True",
            "algo.mlp_keys.encoder=[state]",
            "algo.rollout_steps=4",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.encoder.mlp_features_dim=8",
            "env.num_envs=2",
            "mesh.precision=fp32",
            "algo.precision=bf16",
        ]
    )
    ctx = tiny_ctx(cfg)
    env, env_params = anakin_env(cfg)
    obs_key = anakin_mlp_key(cfg)
    obs_space = gym.spaces.Dict({obs_key: env.observation_space(env_params)})
    act_space = env.action_space(env_params)
    agent, params = build_ppo_agent(ctx, act_space, obs_space, cfg)
    num_envs = int(cfg.env.num_envs)
    fns = PPOTrainFns(ctx, agent, cfg, [obs_key], num_updates=4)
    iteration = make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, obs_key)
    env_state, obs0 = reset_envs(env, env_params, num_envs, jax.random.PRNGKey(1))
    carry = {
        "params": params,
        "opt_state": fns.opt.init(params),
        "env_state": env_state,
        "obs": obs0,
        "key": jax.random.PRNGKey(0),
        "episode_stats": init_episode_stats(num_envs),
    }
    entries.append(
        AuditEntry(
            name="anakin/ppo_dispatch_bf16",
            fn=jax.jit(iteration, donate_argnums=(0,)),
            args=(carry, 0.2, 0.0),
            covers=("anakin_ppo_bf16",),
            precision="bf16",
        )
    )

    cfg = compose_tiny(
        [
            "exp=sac",
            "env=jax_pendulum",
            "algo.anakin=True",
            "algo.mlp_keys.encoder=[state]",
            "algo.hidden_size=8",
            "algo.per_rank_batch_size=4",
            "algo.replay_ratio=1",
            "env.num_envs=2",
            "buffer.size=64",
            "mesh.precision=fp32",
            "algo.precision=bf16",
        ]
    )
    ctx = tiny_ctx(cfg)
    env, env_params = anakin_env(cfg)
    mlp_key = anakin_mlp_key(cfg)
    obs_space_box = env.observation_space(env_params)
    act_space = env.action_space(env_params)
    obs_space = gym.spaces.Dict({mlp_key: obs_space_box})
    actor, critic, params = build_sac_agent(ctx, act_space, obs_space, cfg)
    params = jax.tree.map(jnp.copy, params)  # donation safety (critic_target aliases)
    num_envs = int(cfg.env.num_envs)
    obs_dim = int(np.prod(obs_space_box.shape))
    act_dim = int(np.prod(act_space.shape))
    capacity = max(int(cfg.buffer.size) // max(num_envs, 1), 1)
    ring = DeviceTransitionRing(
        capacity,
        num_envs,
        {
            "obs": ((obs_dim,), jnp.float32),
            "next_obs": ((obs_dim,), jnp.float32),
            "actions": ((act_dim,), jnp.float32),
            "rewards": ((1,), jnp.float32),
            "dones": ((1,), jnp.float32),
        },
        store_dtype=resolve_store_dtype(cfg.buffer.get("store_dtype")),
    )
    actor_opt, critic_opt, alpha_opt, builder = make_sac_anakin_dispatch(
        env, env_params, actor, critic, cfg, act_space, ring, int(cfg.algo.per_rank_batch_size)
    )
    env_state, obs0 = reset_envs(env, env_params, num_envs, jax.random.PRNGKey(1))
    carry = {
        "params": params,
        "opt_state": {
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
            "alpha": alpha_opt.init(params["log_alpha"]),
        },
        "env_state": env_state,
        "obs": obs0,
        "ring": ring.arrays,
        "rows_added": jnp.zeros((), jnp.int32),
        "gstep": jnp.zeros((), jnp.int32),
        "key": jax.random.PRNGKey(0),
        "episode_stats": init_episode_stats(num_envs),
    }
    entries.append(
        AuditEntry(
            name="anakin/sac_dispatch_bf16",
            fn=jax.jit(builder(2, 1, True), donate_argnums=(0,)),
            args=(carry,),
            covers=("anakin_sac_bf16",),
            precision="bf16",
        )
    )
    return entries
