"""PPO training loop (reference: ``/root/reference/sheeprl/algos/ppo/ppo.py:105-…``).

TPU-first structure:

* rollout: host loop over the vectorized envs; actions sampled by one jitted policy call
  per step (HOST→DEVICE obs copy at the boundary, like the reference's ``prepare_obs``);
* GAE: computed on device as a reverse ``lax.scan`` over the whole rollout;
* update: the ENTIRE optimisation (``update_epochs`` × minibatch sweep with fresh
  per-epoch permutations) is ONE jitted call built from nested ``lax.scan`` —
  vs the reference's python-loop-per-minibatch with a DDP all-reduce per backward
  (``ppo.py:40-50`` + Fabric).  Gradient sync over the ``data`` mesh axis is inserted by
  GSPMD: the batch is sharded, params replicated, loss is a global mean.
* annealing (lr / clip / entropy coefficients) stays on host and enters the jitted step
  as traced scalars (no recompilation), mirroring ``polynomial_decay`` semantics.
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.analysis.strict import (
    assert_finite,
    maybe_inject_nonfinite,
    nan_scan,
    strict_enabled,
    strict_guard,
)
from sheeprl_tpu.algos.ppo.agent import build_agent
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import (
    AGGREGATOR_KEYS,
    log_prob_and_entropy,
    prepare_obs,
    sample_actions,
    test,
)
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.obs import perf as obs_perf
from sheeprl_tpu.obs import TrainingMonitor, flight_recorder
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.precision import train_policy
from sheeprl_tpu.rollout import PipelinedPlayer, rollout_metrics
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, record_episode_stats
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import gae, polynomial_decay


def make_optimizer(
    opt_cfg: Dict[str, Any], max_grad_norm: float, lr_schedule=None, inject_lr: bool = False
) -> optax.GradientTransformation:
    """``inject_lr=True`` builds the same optimizer through
    ``optax.inject_hyperparams`` so the learning rate lives in the OPTIMIZER
    STATE instead of the update closure — the population engine's
    vmapped-by-hyperparameter init (``engine/population.py``) then stamps a
    per-member rate into each member's state while every member runs the
    identical update program.  Incompatible with a schedule (a swept rate is a
    per-member constant)."""
    lr = lr_schedule if lr_schedule is not None else opt_cfg.get("lr", 1e-3)
    if inject_lr and lr_schedule is not None:
        raise ValueError("inject_lr (population lr sweep) and a lr schedule are mutually exclusive")
    name = opt_cfg.get("name", "adam")
    if name == "adam":
        wd = opt_cfg.get("weight_decay", 0.0)

        def base(learning_rate):
            o = optax.adam(learning_rate, eps=opt_cfg.get("eps", 1e-8), b1=opt_cfg.get("betas", [0.9, 0.999])[0])
            if wd:
                # torch.optim.Adam weight_decay is L2-into-gradient, i.e. the decay
                # is added BEFORE the Adam scaling (unlike decoupled AdamW).
                o = optax.chain(optax.add_decayed_weights(wd), o)
            return o

    elif name == "adamw":

        def base(learning_rate):
            return optax.adamw(
                learning_rate, eps=opt_cfg.get("eps", 1e-8), weight_decay=opt_cfg.get("weight_decay", 0.0)
            )

    elif name == "sgd":

        def base(learning_rate):
            return optax.sgd(learning_rate, momentum=opt_cfg.get("momentum", 0.0))

    elif name == "rmsprop_tf":
        # TF-style RMSProp: eps inside the sqrt (reference optim/rmsprop_tf.py:14-156),
        # pinned explicitly because optax is deprecating that default.

        def base(learning_rate):
            return optax.rmsprop(
                learning_rate,
                decay=opt_cfg.get("alpha", 0.99),
                eps=opt_cfg.get("eps", 1e-8),
                centered=opt_cfg.get("centered", False),
                momentum=opt_cfg.get("momentum", 0.0),
                eps_in_sqrt=True,
            )

    else:
        raise ValueError(f"Unknown optimizer: {name}")
    opt = optax.inject_hyperparams(base)(learning_rate=lr) if inject_lr else base(lr)
    if max_grad_norm and max_grad_norm > 0:
        return optax.chain(optax.clip_by_global_norm(max_grad_norm), opt)
    return opt


class PPOTrainFns:
    """Jitted PPO functions shared by the coupled and decoupled entry points."""

    def __init__(self, ctx, agent, cfg, obs_keys, num_updates, inject_lr: bool = False):
        if cfg.algo.per_rank_batch_size <= 0:
            raise ValueError("algo.per_rank_batch_size must be positive")
        num_envs = cfg.env.num_envs
        rollout_steps = cfg.algo.rollout_steps
        batch_n = rollout_steps * num_envs
        if batch_n % cfg.algo.per_rank_batch_size != 0:
            raise ValueError(
                f"algo.rollout_steps*env.num_envs ({batch_n}) must be divisible by "
                f"algo.per_rank_batch_size ({cfg.algo.per_rank_batch_size}): static shapes "
                "inside the jitted update require equal minibatches."
            )
        self.batch_n = batch_n
        self.num_minibatches = batch_n // cfg.algo.per_rank_batch_size
        self.grad_steps_per_update = cfg.algo.update_epochs * self.num_minibatches
        self.lr_schedule = None
        if cfg.algo.anneal_lr:
            if inject_lr:
                raise ValueError(
                    "algo.anneal_lr=True cannot combine with a population learning-rate "
                    "sweep (the swept rate is a per-member constant in the optimizer state)"
                )
            self.lr_schedule = optax.polynomial_schedule(
                init_value=cfg.algo.optimizer.lr,
                end_value=1e-8,
                power=1.0,
                transition_steps=num_updates * self.grad_steps_per_update,
            )
        self.opt = make_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm, self.lr_schedule, inject_lr=inject_lr)

        is_continuous = agent.is_continuous
        batch_sharding = ctx.batch_sharding()
        gamma, gae_lambda = cfg.algo.gamma, cfg.algo.gae_lambda
        loss_reduction = cfg.algo.loss_reduction
        mb_size = cfg.algo.per_rank_batch_size
        num_minibatches = self.num_minibatches
        opt = self.opt
        strict = strict_enabled(cfg)
        health = health_enabled(cfg)  # trace-time constant (obs/health.py)
        # Precision boundary (howto/precision.md): float observation batches are
        # cast to the policy's compute dtype BEFORE the first matmul, so under
        # bf16 the whole forward runs low-precision; heads cast back to f32.
        precision = train_policy(cfg, ctx)

        def cast_obs(obs):
            return precision.cast_to_compute(obs)

        @jax.jit
        def act_fn(p, obs, key):
            actor_out, value = agent.apply(p, cast_obs(obs))
            env_act, stored_act, logprob = sample_actions(key, actor_out, is_continuous)
            return env_act, stored_act, logprob, value[..., 0]

        @jax.jit
        def values_fn(p, obs):
            _, value = agent.apply(p, cast_obs(obs))
            return value[..., 0]

        def loss_fn(p, mb, clip_coef, ent_coef):
            actor_out, new_values = agent.apply(p, cast_obs({k: mb[k] for k in obs_keys}))
            new_logprob, entropy = log_prob_and_entropy(actor_out, mb["actions"], is_continuous)
            adv = mb["advantages"]
            if cfg.algo.normalize_advantages:
                adv = (adv - adv.mean()) / (adv.std() + 1e-8)
            pg = policy_loss(new_logprob, mb["logprobs"], adv, clip_coef, loss_reduction)
            vf = value_loss(
                new_values[..., 0], mb["values"], mb["returns"], clip_coef, cfg.algo.clip_vloss, loss_reduction
            )
            ent = entropy_loss(entropy, loss_reduction)
            total = pg + cfg.algo.vf_coef * vf + ent_coef * ent
            aux = {"Loss/policy_loss": pg, "Loss/value_loss": vf, "Loss/entropy_loss": -ent}
            if health:
                aux["Health/policy_entropy"] = entropy.mean()
                aux["Health/value_mean"] = new_values.mean()
                aux["Health/value_std"] = new_values.std()
            return total, aux

        @jax.jit
        def train_fn(p, o_state, data, key, clip_coef, ent_coef):
            n = data["actions"].shape[0]

            def mb_step(carry, idx):
                p, o_state = carry
                mb = jax.tree.map(lambda x: jax.lax.with_sharding_constraint(x[idx], batch_sharding), data)
                (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, mb, clip_coef, ent_coef)
                updates, o_state = opt.update(grads, o_state, p)
                p = optax.apply_updates(p, updates)
                if health:  # per-module norms/ratios, averaged by the scans below
                    aux = {**aux, **diagnostics(grads=grads, params=p, updates=updates)}
                return (p, o_state), aux

            def epoch_step(carry, ekey):
                perm = jax.random.permutation(ekey, n)
                idxs = perm.reshape(num_minibatches, mb_size)
                carry, auxs = jax.lax.scan(mb_step, carry, idxs)
                return carry, jax.tree.map(jnp.mean, auxs)

            keys = jax.random.split(key, cfg.algo.update_epochs)
            (p, o_state), metrics = jax.lax.scan(epoch_step, (p, o_state), keys)
            metrics = jax.tree.map(jnp.mean, metrics)
            metrics = maybe_inject_nonfinite(cfg, metrics)
            if strict:  # trace-time constant: the callback only exists in strict runs
                nan_scan(metrics, "ppo/train_fn")
            return p, o_state, metrics

        self.act_fn = act_fn
        self.values_fn = values_fn
        self.train_fn = train_fn
        self.gae_fn = jax.jit(
            lambda rew, vals, dones, next_v: gae(rew, vals, dones, next_v, rollout_steps, gamma, gae_lambda)
        )


@register_algorithm(name="ppo")
def main(ctx, cfg) -> None:
    if cfg.algo.anakin:
        # Anakin mode (howto/anakin.md): on-device jax envs, acting and the SAME
        # jitted update fused into one donated scan — the engine owns the loop.
        from sheeprl_tpu.engine.anakin import ppo_anakin

        return ppo_anakin(ctx, cfg)
    rank = ctx.process_index
    if cfg.algo.per_rank_batch_size <= 0:
        raise ValueError("algo.per_rank_batch_size must be positive")

    log_dir = get_log_dir(cfg)
    if ctx.is_global_zero:
        save_config(cfg, Path(log_dir) / "config.yaml")
    logger = get_logger(cfg, log_dir)
    monitor = TrainingMonitor(cfg, log_dir)

    envs = make_vector_env(cfg, cfg.seed, rank, log_dir if cfg.env.capture_video else None)
    obs_space = envs.single_observation_space
    act_space = envs.single_action_space
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    agent, params = build_agent(ctx, act_space, obs_space, cfg)
    is_continuous = agent.is_continuous

    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    world = jax.process_count()
    policy_steps_per_iter = int(num_envs * rollout_steps * world)
    total_steps = int(cfg.algo.total_steps)
    num_updates = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1

    fns = PPOTrainFns(ctx, agent, cfg, obs_keys, num_updates)
    batch_n = fns.batch_n
    grad_steps_per_update = fns.grad_steps_per_update
    lr_schedule = fns.lr_schedule
    opt_state = ctx.replicate(fns.opt.init(params))

    rb = ReplayBuffer(
        rollout_steps,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
    )
    rb.seed(cfg.seed + rank)

    aggregator = MetricAggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(AGGREGATOR_KEYS | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    guard = TrainingGuard(cfg, log_dir)

    act_fn, values_fn, train_fn, gae_fn = fns.act_fn, fns.values_fn, fns.train_fn, fns.gae_fn
    # analysis.strict: signature guard on the jitted update (drift -> hard error)
    train_fn = obs_perf.instrument(cfg, "ppo/train_fn", strict_guard(cfg, "ppo/train_fn", train_fn))
    gamma = cfg.algo.gamma

    # Flight recorder (obs/flight_recorder.py): the replay builder rebuilds this
    # exact update from the dumped config + these statics.
    recorder = flight_recorder.get_active()
    if recorder is not None:
        recorder.arm_replay(
            "sheeprl_tpu.algos.ppo.ppo:replay_update",
            act_space=act_space,
            obs_space=obs_space,
            num_updates=num_updates,
        )

    # ------------------------------------------------------------------ resume
    start_update = 1
    policy_step = 0
    last_log = 0
    last_checkpoint = 0
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from, templates={"params": jax.device_get(params), "opt_state": jax.device_get(opt_state)}
        )
        params = ctx.replicate(state["params"])
        opt_state = ctx.replicate(state["opt_state"])
        start_update = state["update"] + 1
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)

    # ------------------------------------------------------------------ loop
    obs, _ = envs.reset(seed=cfg.seed + rank)
    step_data: Dict[str, np.ndarray] = {}
    start_time = time.perf_counter()

    # Acting pipeline (sheeprl_tpu/rollout).  depth 0 reproduces the historical
    # synchronous path exactly; depth>=1 overlaps the policy jit with the env
    # workers at the cost of a policy lag — note PPO's loss then trains on
    # slightly stale log-probs/values (see howto/async_rollout.md).
    def _pipeline_policy(cur_obs):
        obs_t = prepare_obs(cur_obs, cnn_keys, mlp_keys)
        return act_fn(params, obs_t, ctx.local_rng())

    def _pipeline_post(fetched):
        env_act_np, _, logprob_np, value_np = (np.asarray(x) for x in fetched)
        if is_continuous:
            low, high = act_space.low, act_space.high
            env_actions = np.clip(env_act_np, low, high) if np.isfinite(low).all() else env_act_np
        elif len(agent.action_dims) == 1:
            env_actions = env_act_np[..., 0]
        else:
            env_actions = env_act_np
        return env_actions, (env_act_np, logprob_np, value_np)

    rollout_player = PipelinedPlayer(
        envs, _pipeline_policy, _pipeline_post, depth=int((cfg.get("rollout") or {}).get("pipeline_depth", 0))
    )

    for update in range(start_update, num_updates + 1):
        monitor.advance()
        train_time = 0.0
        env_time_start = time.perf_counter()
        with timer("Time/env_interaction_time"):
            for _ in range(rollout_steps):
                with monitor.phase("player"):
                    env_actions, (env_act_np, logprob_np, value_np) = rollout_player.act(obs)
                with monitor.phase("env_step"):
                    next_obs, reward, terminated, truncated, info = rollout_player.env_step(env_actions)
                if cfg.env.clip_rewards:
                    reward = np.clip(reward, -1, 1)
                done = np.logical_or(terminated, truncated)
                reward = np.asarray(reward, dtype=np.float32).reshape(num_envs)

                # Bootstrap truncated episodes: V(final_obs) folds into the reward
                # before storage (reference ``ppo.py:287-306``).
                if truncated.any() and "final_obs" in info:
                    trunc_idx = np.nonzero(truncated)[0]
                    final_obs = {
                        k: np.stack([np.asarray(info["final_obs"][i][k]) for i in trunc_idx])
                        for k in obs_keys
                    }
                    v_final = np.asarray(
                        jax.device_get(values_fn(params, prepare_obs(final_obs, cnn_keys, mlp_keys)))
                    )
                    reward[trunc_idx] += gamma * v_final

                for k in obs_keys:
                    step_data[k] = np.asarray(obs[k])[None]
                step_data["actions"] = env_act_np.reshape(num_envs, -1).astype(np.float32)[None]
                step_data["logprobs"] = logprob_np.reshape(num_envs, 1)[None]
                step_data["values"] = value_np.reshape(num_envs, 1)[None]
                step_data["rewards"] = reward.reshape(num_envs, 1)[None]
                step_data["dones"] = done.astype(np.float32).reshape(num_envs, 1)[None]
                with monitor.phase("buffer_add"):
                    rb.add(step_data, validate_args=cfg.buffer.validate_args)

                obs = next_obs
                policy_step += num_envs * world

                record_episode_stats(aggregator, info)
        env_time = time.perf_counter() - env_time_start

        # Bootstrap + GAE on device.
        local = rb.to_tensor()
        next_value = values_fn(params, prepare_obs(obs, cnn_keys, mlp_keys))[:, None]
        returns, advantages = gae_fn(local["rewards"], local["values"], local["dones"], next_value)
        data = {
            **{k: local[k] for k in obs_keys},
            "actions": local["actions"],
            "logprobs": local["logprobs"][..., 0],
            "values": local["values"][..., 0],
            "returns": returns[..., 0],
            "advantages": advantages[..., 0],
        }
        data = jax.tree.map(lambda x: x.reshape(batch_n, *x.shape[2:]), data)

        # Annealed coefficients (host-side; traced scalars on device).
        clip_coef = cfg.algo.clip_coef
        ent_coef = cfg.algo.ent_coef
        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(update, initial=clip_coef, final=0.0, max_decay_steps=num_updates)
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(update, initial=ent_coef, final=0.0, max_decay_steps=num_updates)

        # Stage this update's exact inputs on the flight recorder: device-array
        # references only (no sync, no copy) — fetched solely if the run crashes.
        key = ctx.rng()
        if recorder is not None:
            recorder.stage_step(
                batch=data,
                carry={"params": params, "opt_state": opt_state},
                key=key,
                scalars={"clip_coef": float(clip_coef), "ent_coef": float(ent_coef), "update": update},
            )
        with timer("Time/train_time"), monitor.phase("dispatch"):
            t0 = time.perf_counter()
            params, opt_state, train_metrics = train_fn(params, opt_state, data, key, clip_coef, ent_coef)
            train_metrics = jax.device_get(train_metrics)
            train_time = time.perf_counter() - t0
        assert_finite(cfg, train_metrics, "ppo/update")
        for k, v in train_metrics.items():
            aggregator.update(k, float(v))

        # Logging cadence (reference ``ppo.py`` metric flush per log_every).
        if logger is not None and (policy_step - last_log >= cfg.metric.log_every or update == num_updates or cfg.dry_run):
            metrics = aggregator.compute()
            metrics["Time/sps_train"] = grad_steps_per_update / train_time if train_time > 0 else 0.0
            metrics["Time/sps_env_interaction"] = (
                policy_steps_per_iter / world / env_time if env_time > 0 else 0.0
            )
            grad_step_count = update * grad_steps_per_update
            metrics["Params/lr"] = (
                float(lr_schedule(grad_step_count)) if lr_schedule is not None else float(cfg.algo.optimizer.lr)
            )
            metrics.update(rollout_metrics(envs))
            monitor.log_metrics(logger, metrics, policy_step)
            aggregator.reset()
            last_log = policy_step

        def save_ckpt():
            nonlocal last_checkpoint
            with monitor.phase("checkpoint"):
                path = ckpt_manager.save(
                    policy_step,
                    {
                        "params": params,
                        "opt_state": opt_state,
                        "update": update,
                        "policy_step": policy_step,
                        "last_log": last_log,
                        "last_checkpoint": policy_step,
                    },
                )
            last_checkpoint = policy_step
            return path

        if (
            cfg.checkpoint.every > 0
            and (policy_step - last_checkpoint) >= cfg.checkpoint.every
            or update == num_updates
            and cfg.checkpoint.save_last
        ):
            save_ckpt()
        guard.boundary(policy_step, save_ckpt)

    monitor.close()
    envs.close()
    if cfg.algo.run_test and ctx.is_global_zero:
        reward = test(agent, params, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if not cfg.get("model_manager", {}).get("disabled", True) and ctx.is_global_zero:
        from sheeprl_tpu.utils.model_manager import maybe_register_models

        maybe_register_models(cfg, log_dir)
    if logger is not None:
        logger.close()


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): AOT-lower the shared
    ``PPOTrainFns.train_fn`` — the jitted update of BOTH the coupled and decoupled
    entry points — at tiny synthetic shapes, through the exact builder the
    training loops use."""
    from sheeprl_tpu.analysis.ir.synth import (
        compose_tiny,
        discrete_act_space,
        tiny_ctx,
        vector_space,
        zeros,
    )
    from sheeprl_tpu.analysis.ir.types import AuditEntry

    cfg = compose_tiny(
        [
            "exp=ppo",
            "env=discrete_dummy",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
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
    obs_space = vector_space()
    act_space = discrete_act_space()
    agent, params = build_agent(ctx, act_space, obs_space, cfg)
    fns = PPOTrainFns(ctx, agent, cfg, ["state"], num_updates=4)
    opt_state = fns.opt.init(params)
    n = int(cfg.algo.rollout_steps * cfg.env.num_envs)
    data = {
        "state": zeros((n, 5)),
        "actions": zeros((n, 1)),
        "logprobs": zeros((n,)),
        "values": zeros((n,)),
        "returns": zeros((n,)),
        "advantages": zeros((n,)),
    }
    return [
        AuditEntry(
            name="ppo/train_fn",
            fn=fns.train_fn,
            args=(params, opt_state, data, jax.random.PRNGKey(0), 0.2, 0.0),
            covers=("ppo", "ppo_decoupled"),
            precision=str(cfg.mesh.precision),
        )
    ]


def replay_update(cfg, dump_dir):
    """Flight-recorder replay builder (``python -m sheeprl_tpu.obs.replay_blackbox``):
    rebuild the PPO jitted update from a blackbox dump's config + statics, restore
    the dumped params/optimizer state/batch, and re-execute the single failing
    update step.  Shared by the coupled and decoupled entry points (same
    ``PPOTrainFns.train_fn``).  Returns the update's host-fetched outputs."""
    from sheeprl_tpu.obs import replay_blackbox
    from sheeprl_tpu.parallel.mesh import make_mesh_context

    ctx = make_mesh_context(cfg)
    raw = replay_blackbox.load_state(dump_dir)
    statics = raw["statics"]
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    agent, params0 = build_agent(ctx, statics["act_space"], statics["obs_space"], cfg)
    fns = PPOTrainFns(ctx, agent, cfg, obs_keys, statics["num_updates"])
    templates = {"carry": jax.device_get({"params": params0, "opt_state": fns.opt.init(params0)})}
    state = replay_blackbox.load_state(dump_dir, templates)
    carry, scalars = state["carry"], state["scalars"]
    new_params, _, metrics = fns.train_fn(
        ctx.replicate(carry["params"]),
        ctx.replicate(carry["opt_state"]),
        state["batch"],
        jnp.asarray(state["key"]),
        scalars["clip_coef"],
        scalars["ent_coef"],
    )
    return {
        "metrics": jax.device_get(metrics),
        "new_param_norm": float(jax.device_get(optax.global_norm(new_params))),
    }
