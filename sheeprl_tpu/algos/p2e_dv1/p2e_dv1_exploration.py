"""P2E-DV1 exploration (reference: ``/root/reference/sheeprl/algos/p2e_dv1/p2e_dv1_exploration.py``).

Plan2Explore on the DreamerV1 stack, one jitted train step with four phases:

1. DV1 world-model update (Normal-KL ELBO) with reward/continue heads on *detached*
   latents;
2. ensemble learning — next observation embedding under a unit-variance Gaussian
   (reference ``:168-184``);
3. exploration behaviour — DV1 dynamics-backprop actor on the intrinsic disagreement
   reward, Gaussian critic without a target (reference ``:186-263``);
4. task behaviour — the DV1 update on the learned reward model (reference ``:268-325``).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer_v1.agent import WorldModelV1
from sheeprl_tpu.algos.dreamer_v1.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v2.agent import exploration_amount
from sheeprl_tpu.algos.p2e import ensemble_loss_normal, intrinsic_reward
from sheeprl_tpu.algos.p2e_dv1.agent import (
    PlayerState,
    build_agent,
    make_player_step,
    parse_actions_dim,
)
from sheeprl_tpu.algos.p2e_dv1.utils import (
    AGGREGATOR_KEYS,
    compute_lambda_values,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal
from sheeprl_tpu.obs import TrainingMonitor
from sheeprl_tpu.obs.health import diagnostics, health_enabled, replay_age_metrics
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, record_episode_stats
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio


def make_train_step(world_model, actor, critic, ensemble_mlp, cfg, cnn_keys, mlp_keys):
    wm_cfg = cfg.algo.world_model
    stoch_size = wm_cfg.stochastic_size
    rec_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    gamma = cfg.algo.gamma
    lmbda = cfg.algo.lmbda
    use_continues = wm_cfg.use_continues
    intr_mult = cfg.algo.intrinsic_reward_multiplier

    wm_opt = make_optimizer(wm_cfg.optimizer, wm_cfg.clip_gradients)
    actor_opt = make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_opt = make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    ens_opt = make_optimizer(cfg.algo.ensembles.optimizer, cfg.algo.ensembles.clip_gradients)

    def init_opt_states(params):
        return {
            "world_model": wm_opt.init(params["world_model"]),
            "actor_task": actor_opt.init(params["actor_task"]),
            "critic_task": critic_opt.init(params["critic_task"]),
            "actor_exploration": actor_opt.init(params["actor_exploration"]),
            "critic_exploration": critic_opt.init(params["critic_exploration"]),
            "ensembles": ens_opt.init(params["ensembles"]),
        }

    def _imagine(actor_params, wm_params, prior0, rec0, latent0, k_img):
        """DV1 rollout: H latents EXCLUDING the start, plus the action taken at each
        visited state (reference ``:198-204``)."""

        def img_step(carry, k):
            prior, rec, latent = carry
            k_act, k_dyn = jax.random.split(k)
            acts, _ = actor.apply(actor_params, jax.lax.stop_gradient(latent), k_act)
            action = jnp.concatenate(acts, -1)
            prior, rec = world_model.apply(wm_params, prior, rec, action, k_dyn, method=WorldModelV1.imagination)
            new_latent = jnp.concatenate([prior, rec], -1)
            return (prior, rec, new_latent), (new_latent, action)

        keys = jax.random.split(k_img, horizon)
        _, (traj, actions) = jax.lax.scan(img_step, (prior0, rec0, latent0), keys, unroll=5)
        return traj, actions  # both [H, N, ...]

    def _continues(wm_params, traj, like):
        if use_continues:
            return jax.nn.sigmoid(world_model.apply(wm_params, traj, method=WorldModelV1.continues))
        return jnp.ones_like(like) * gamma

    def _critic_loss(critic_params, traj, lambda_values, discount):
        qv = Independent(Normal(critic.apply(critic_params, traj[:-1]), 1.0), 1)
        return -jnp.mean(discount[..., 0] * qv.log_prob(lambda_values))

    def train_step(params, opt_states, data, key):
        T, B = data["rewards"].shape[:2]
        k_wm, k_img_e, k_img_t = jax.random.split(key, 3)
        sg = jax.lax.stop_gradient

        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        batch_actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)

        # ---------------------------------------------------- 1. world model
        def wm_loss_fn(wm_params):
            embed = world_model.apply(wm_params, batch_obs, method=WorldModelV1.encode)

            def step(carry, x):
                post, rec = carry
                action, emb, k = x
                rec, post, _, post_ms, prior_ms = world_model.apply(
                    wm_params, post, rec, action, emb, k, method=WorldModelV1.dynamic
                )
                return (post, rec), (rec, post, post_ms, prior_ms)

            keys = jax.random.split(k_wm, T)
            init = (jnp.zeros((B, stoch_size)), jnp.zeros((B, rec_size)))
            _, (recs, posts, post_ms, prior_ms) = jax.lax.scan(step, init, (batch_actions, embed, keys), unroll=8)
            latents = jnp.concatenate([posts, recs], -1)
            recon = world_model.apply(wm_params, latents, method=WorldModelV1.decode)

            obs_lp = 0.0
            for k in cnn_keys:
                target = data[k].astype(jnp.float32) / 255.0 - 0.5
                target = target.reshape(T, B, -1, *target.shape[-2:])
                obs_lp = obs_lp + Independent(Normal(recon[k], jnp.ones_like(recon[k])), 3).log_prob(target)
            for k in mlp_keys:
                obs_lp = obs_lp + Independent(Normal(recon[k], jnp.ones_like(recon[k])), 1).log_prob(data[k])

            reward_lp = Independent(
                Normal(world_model.apply(wm_params, sg(latents), method=WorldModelV1.reward), 1.0), 1
            ).log_prob(data["rewards"])
            continue_lp = None
            if use_continues:
                continue_lp = Independent(
                    BernoulliSafeMode(world_model.apply(wm_params, sg(latents), method=WorldModelV1.continues)), 1
                ).log_prob((1.0 - data["terminated"]) * gamma)

            rec_loss, metrics = reconstruction_loss(
                obs_lp,
                reward_lp,
                post_ms,
                prior_ms,
                wm_cfg.kl_free_nats,
                wm_cfg.kl_regularizer,
                continue_lp,
                wm_cfg.continue_scale_factor,
            )
            metrics["State/post_entropy"] = Independent(Normal(*post_ms), 1).entropy().mean()
            metrics["State/prior_entropy"] = Independent(Normal(*prior_ms), 1).entropy().mean()
            return rec_loss, (posts, recs, sg(embed), metrics)

        (rec_loss, (posts, recs, embed, wm_metrics)), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            params["world_model"]
        )
        wm_updates, new_wm_opt = wm_opt.update(wm_grads, opt_states["world_model"], params["world_model"])
        new_wm_params = optax.apply_updates(params["world_model"], wm_updates)

        # ---------------------------------------------------- 2. ensembles
        ens_inputs = jnp.concatenate([sg(posts), sg(recs), data["actions"]], -1)
        ens_targets = embed[1:]
        ens_loss_val, ens_grads = jax.value_and_grad(
            lambda p: ensemble_loss_normal(ensemble_mlp, p, ens_inputs, ens_targets)
        )(params["ensembles"])
        ens_updates, new_ens_opt = ens_opt.update(ens_grads, opt_states["ensembles"], params["ensembles"])
        new_ens_params = optax.apply_updates(params["ensembles"], ens_updates)

        # ---------------------------------------------------- 3. exploration behaviour
        prior0 = sg(posts).reshape(T * B, stoch_size)
        rec0 = sg(recs).reshape(T * B, rec_size)
        latent0 = jnp.concatenate([prior0, rec0], -1)

        def expl_actor_loss_fn(actor_params):
            traj, actions = _imagine(actor_params, new_wm_params, prior0, rec0, latent0, k_img_e)
            values = critic.apply(params["critic_exploration"], traj)
            reward = intrinsic_reward(
                ensemble_mlp, new_ens_params, jnp.concatenate([sg(traj), sg(actions)], -1), intr_mult
            )
            continues = _continues(new_wm_params, traj, reward)
            lambda_values = compute_lambda_values(reward, values, continues, lmbda)  # [H-1, N, 1]
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
            )
            loss = -jnp.mean(discount * lambda_values)
            aux = {
                "traj": sg(traj),
                "lambda_values": sg(lambda_values),
                "discount": discount,
                "metrics": {
                    "Rewards/intrinsic": reward.mean(),
                    "Values_exploration/predicted_values": values.mean(),
                    "Values_exploration/lambda_values": lambda_values.mean(),
                },
            }
            return loss, aux

        (policy_loss_expl, expl_aux), expl_grads = jax.value_and_grad(expl_actor_loss_fn, has_aux=True)(
            params["actor_exploration"]
        )
        ae_updates, new_ae_opt = actor_opt.update(
            expl_grads, opt_states["actor_exploration"], params["actor_exploration"]
        )
        new_actor_expl = optax.apply_updates(params["actor_exploration"], ae_updates)

        value_loss_expl, ce_grads = jax.value_and_grad(_critic_loss)(
            params["critic_exploration"], expl_aux["traj"], expl_aux["lambda_values"], expl_aux["discount"]
        )
        ce_updates, new_ce_opt = critic_opt.update(
            ce_grads, opt_states["critic_exploration"], params["critic_exploration"]
        )
        new_critic_expl = optax.apply_updates(params["critic_exploration"], ce_updates)

        # ---------------------------------------------------- 4. task behaviour
        def task_actor_loss_fn(actor_params):
            traj, _ = _imagine(actor_params, new_wm_params, prior0, rec0, latent0, k_img_t)
            values = critic.apply(params["critic_task"], traj)
            reward = world_model.apply(new_wm_params, traj, method=WorldModelV1.reward)
            continues = _continues(new_wm_params, traj, reward)
            lambda_values = compute_lambda_values(reward, values, continues, lmbda)
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
            )
            loss = -jnp.mean(discount * lambda_values)
            aux = {"traj": sg(traj), "lambda_values": sg(lambda_values), "discount": discount}
            return loss, aux

        (policy_loss_task, task_aux), task_grads = jax.value_and_grad(task_actor_loss_fn, has_aux=True)(
            params["actor_task"]
        )
        at_updates, new_at_opt = actor_opt.update(task_grads, opt_states["actor_task"], params["actor_task"])
        new_actor_task = optax.apply_updates(params["actor_task"], at_updates)

        value_loss_task, ct_grads = jax.value_and_grad(_critic_loss)(
            params["critic_task"], task_aux["traj"], task_aux["lambda_values"], task_aux["discount"]
        )
        ct_updates, new_ct_opt = critic_opt.update(ct_grads, opt_states["critic_task"], params["critic_task"])
        new_critic_task = optax.apply_updates(params["critic_task"], ct_updates)

        new_params = {
            "world_model": new_wm_params,
            "actor_task": new_actor_task,
            "critic_task": new_critic_task,
            "actor_exploration": new_actor_expl,
            "critic_exploration": new_critic_expl,
            "ensembles": new_ens_params,
        }
        new_opt_states = {
            "world_model": new_wm_opt,
            "actor_task": new_at_opt,
            "critic_task": new_ct_opt,
            "actor_exploration": new_ae_opt,
            "critic_exploration": new_ce_opt,
            "ensembles": new_ens_opt,
        }
        metrics = dict(wm_metrics)
        metrics.update(expl_aux["metrics"])
        metrics["Loss/ensemble_loss"] = ens_loss_val
        metrics["Loss/policy_loss_exploration"] = policy_loss_expl
        metrics["Loss/value_loss_exploration"] = value_loss_expl
        metrics["Loss/policy_loss_task"] = policy_loss_task
        metrics["Loss/value_loss_task"] = value_loss_task
        if health_enabled(cfg):  # trace-time constant (obs/health.py)
            metrics.update(
                diagnostics(
                    grads={"world_model": wm_grads, "ensembles": ens_grads, "actor_exploration": expl_grads, "critic_exploration": ce_grads, "actor_task": task_grads, "critic_task": ct_grads},
                    params=new_params,
                    updates={"world_model": wm_updates, "ensembles": ens_updates, "actor_exploration": ae_updates, "critic_exploration": ce_updates, "actor_task": at_updates, "critic_task": ct_updates},
                )
            )
        metrics = maybe_inject_nonfinite(cfg, metrics)
        if strict_enabled(cfg):  # trace-time constant: callback exists only in strict runs
            nan_scan(metrics, "p2e_dv1/train_step")
        return new_params, new_opt_states, metrics

    return train_step, init_opt_states


@register_algorithm(name="p2e_dv1_exploration")
def main(ctx, cfg) -> None:
    rank = ctx.process_index
    log_dir = get_log_dir(cfg)
    if ctx.is_global_zero:
        save_config(cfg, Path(log_dir) / "config.yaml")
    logger = get_logger(cfg, log_dir)
    monitor = TrainingMonitor(cfg, log_dir)

    envs = make_vector_env(cfg, cfg.seed, rank, log_dir if cfg.env.capture_video else None)
    obs_space = envs.single_observation_space
    act_space = envs.single_action_space
    is_continuous, actions_dim = parse_actions_dim(act_space)
    act_dim_sum = int(sum(actions_dim))
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    num_envs = cfg.env.num_envs
    world = jax.process_count()

    world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
        ctx, actions_dim, is_continuous, cfg, obs_space
    )
    train_step, init_opt_states = make_train_step(world_model, actor, critic, ensemble_mlp, cfg, cnn_keys, mlp_keys)
    opt_states = ctx.replicate(init_opt_states(params))
    # One jitted scan per iteration's gradient block (utils/blocks.py).
    def _block_step(carry, batch, key, update_target):
        del update_target
        params, opt_states = carry
        params, opt_states, metrics = train_step(params, opt_states, batch, key)
        return (params, opt_states), metrics

    player_step = make_player_step(world_model, actor, actions_dim, is_continuous)
    player_jit = jax.jit(player_step, static_argnames=("greedy",))
    actor_type = cfg.algo.player.get("actor_type", "exploration")
    player_actor_key = "actor_exploration" if actor_type == "exploration" else "actor_task"
    stoch_size = cfg.algo.world_model.stochastic_size
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size

    def player_params():
        return {"world_model": params["world_model"], "actor": params[player_actor_key]}

    def player_state_init(n: int) -> PlayerState:
        return PlayerState(
            recurrent_state=jnp.zeros((n, rec_size)),
            stochastic_state=jnp.zeros((n, stoch_size)),
            actions=jnp.zeros((n, act_dim_sum)),
        )

    buffer_size = max(int(cfg.buffer.size) // max(num_envs * world, 1), 1)
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        buffer_cls=SequentialReplayBuffer,
    )
    rb.seed(cfg.seed + rank)

    # Device-vs-host replay data path, one shared implementation
    # (data/device_buffer.py): HBM mirror + index-only sampling when
    # buffer.device=True, async host prefetch otherwise.
    dispatcher, mirror, prefetcher, _run_block, rb_add = make_device_replay(
        ctx, cfg, rb, cnn_keys, mlp_keys, obs_space, act_dim_sum, _block_step
    )

    aggregator = MetricAggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(AGGREGATOR_KEYS | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    guard = TrainingGuard(cfg, log_dir)
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)

    batch_size = cfg.algo.per_rank_batch_size
    seq_len = cfg.algo.per_rank_sequence_length
    policy_steps_per_iter = num_envs * world * cfg.env.action_repeat
    total_steps = int(cfg.algo.total_steps)
    num_iters = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    expl_cfg = cfg.algo.actor

    start_iter = 1
    policy_step = 0
    last_log = 0
    last_checkpoint = 0
    cumulative_grad_steps = 0
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from,
            templates={"params": jax.device_get(params), "opt_states": jax.device_get(opt_states)},
        )
        params = ctx.replicate(state["params"])
        opt_states = ctx.replicate(state["opt_states"])
        ratio.load_state_dict(state["ratio"])
        start_iter = state["iter_num"] + 1
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)
        cumulative_grad_steps = state.get("cumulative_grad_steps", 0)
        learning_starts += start_iter
        if cfg.buffer.checkpoint and "rb" in state:
            rb.load_state_dict(state["rb"])
            if mirror is not None:
                mirror.load_from(rb)

    def _obs_row(o, idxs=None):
        row = {}
        for k in cnn_keys:
            v = np.asarray(o[k]) if idxs is None else np.asarray(o[k])[idxs]
            row[k] = v.reshape(1, v.shape[0], -1, *v.shape[-2:])
        for k in mlp_keys:
            v = np.asarray(o[k], dtype=np.float32) if idxs is None else np.asarray(o[k], dtype=np.float32)[idxs]
            row[k] = v.reshape(1, v.shape[0], -1)
        return row

    obs, _ = envs.reset(seed=cfg.seed + rank)
    player_state = player_state_init(num_envs)
    step_data: Dict[str, np.ndarray] = _obs_row(obs)
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, num_envs, 1), np.float32)
    is_first_np = np.ones((num_envs, 1), dtype=np.float32)
    prefill_iters = max(learning_starts - 1, 0)

    for iter_num in range(start_iter, num_iters + 1):
        monitor.advance()
        env_t0 = time.perf_counter()
        expl_amount = exploration_amount(
            expl_cfg.get("expl_amount", 0.0), expl_cfg.get("expl_decay", 0.0), expl_cfg.get("expl_min", 0.0), policy_step
        )
        with timer("Time/env_interaction_time"):
            if iter_num <= learning_starts and not cfg.checkpoint.get("resume_from"):
                if is_continuous:
                    stored_actions = np.stack([act_space.sample() for _ in range(num_envs)]).astype(np.float32)
                    env_actions = stored_actions
                else:
                    sampled = np.stack([act_space.sample() for _ in range(num_envs)]).reshape(num_envs, -1)
                    onehots = []
                    for i, d in enumerate(actions_dim):
                        oh = np.zeros((num_envs, d), dtype=np.float32)
                        oh[np.arange(num_envs), sampled[:, i]] = 1.0
                        onehots.append(oh)
                    stored_actions = np.concatenate(onehots, -1)
                    env_actions = sampled.squeeze(-1) if len(actions_dim) == 1 else sampled
                player_state = player_state._replace(actions=jnp.asarray(stored_actions))
            else:
                obs_t = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
                actions, stored, player_state = player_jit(
                    player_params(), player_state, obs_t, jnp.asarray(is_first_np), ctx.local_rng(), jnp.asarray(expl_amount)
                )
                # ONE device_get for everything the host needs (per-array fetches
                # would each pay their own dispatch and device→host sync).
                stored_np, acts_list = jax.device_get((stored, list(actions)))
                stored_actions = np.asarray(stored_np)
                acts_np = [np.asarray(a) for a in acts_list]
                if is_continuous:
                    env_actions = acts_np[0]
                elif len(actions_dim) == 1:
                    env_actions = acts_np[0].argmax(-1)
                else:
                    env_actions = np.stack([a.argmax(-1) for a in acts_np], -1)

            step_data["actions"] = stored_actions.reshape(1, num_envs, -1)
            rb_add(step_data, validate_args=cfg.buffer.validate_args)
        env_time = time.perf_counter() - env_t0

        # Dispatch this iteration's gradient block BEFORE stepping the envs: the
        # device trains while the host walks the environments below (acting above
        # used the previous iteration's params, exactly as the eager ordering did).
        grad_steps = 0
        if iter_num >= learning_starts:
            grad_steps = ratio(
                (policy_step + policy_steps_per_iter - prefill_iters * policy_steps_per_iter) / world
            )
            if grad_steps > 0:
                params, opt_states = _run_block(
                    (params, opt_states), grad_steps, cumulative_grad_steps, stage_next=iter_num < num_iters
                )
                cumulative_grad_steps += grad_steps

        env_t0 = time.perf_counter()
        with timer("Time/env_interaction_time"):
            next_obs, reward, terminated, truncated, info = envs.step(env_actions)
            if cfg.env.clip_rewards:
                reward = np.tanh(reward)
            done = np.logical_or(terminated, truncated)
            reward = np.asarray(reward, dtype=np.float32).reshape(num_envs, 1)

            real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
            if done.any() and "final_obs" in info:
                for i in np.nonzero(done)[0]:
                    if info["final_obs"][i] is not None:
                        for k in obs_keys:
                            real_next_obs[k][i] = np.asarray(info["final_obs"][i][k])

            step_data = _obs_row(next_obs)
            step_data["rewards"] = reward.reshape(1, num_envs, 1).copy()
            step_data["terminated"] = terminated.astype(np.float32).reshape(1, num_envs, 1)
            step_data["truncated"] = truncated.astype(np.float32).reshape(1, num_envs, 1)
            step_data["is_first"] = np.zeros((1, num_envs, 1), np.float32)

            done_idxs = np.nonzero(done)[0].tolist()
            if done_idxs:
                reset_data = _obs_row(real_next_obs, idxs=done_idxs)
                reset_data["rewards"] = step_data["rewards"][:, done_idxs]
                reset_data["terminated"] = step_data["terminated"][:, done_idxs]
                reset_data["truncated"] = step_data["truncated"][:, done_idxs]
                reset_data["actions"] = np.zeros((1, len(done_idxs), act_dim_sum), np.float32)
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                rb_add(reset_data, done_idxs, validate_args=cfg.buffer.validate_args)
                step_data["rewards"][:, done_idxs] = 0.0
                step_data["terminated"][:, done_idxs] = 0.0
                step_data["truncated"][:, done_idxs] = 0.0
                step_data["is_first"][:, done_idxs] = 1.0

            is_first_np = done.astype(np.float32).reshape(num_envs, 1)
            obs = next_obs
            policy_step += policy_steps_per_iter
            record_episode_stats(aggregator, info)
        env_time += time.perf_counter() - env_t0

        if logger is not None and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == num_iters or cfg.dry_run
        ):
            dispatcher.drain(aggregator)  # the window's only blocking device sync
            metrics = aggregator.compute()
            metrics.update(replay_age_metrics(rb))
            window_sps = dispatcher.pop_window_sps()
            if window_sps is not None:
                metrics["Time/sps_train"] = window_sps
            metrics["Time/sps_env_interaction"] = (
                policy_steps_per_iter / world / env_time if env_time > 0 else 0.0
            )
            metrics["Params/replay_ratio"] = (
                cumulative_grad_steps * world / policy_step if policy_step > 0 else 0.0
            )
            metrics["Params/exploration_amount"] = expl_amount
            monitor.log_metrics(logger, metrics, policy_step)
            aggregator.reset()
            last_log = policy_step

        def save_ckpt():
            nonlocal last_checkpoint
            state = {
                "params": params,
                "opt_states": opt_states,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": policy_step,
                "cumulative_grad_steps": cumulative_grad_steps,
            }
            if cfg.buffer.checkpoint:
                state["rb"] = rb.state_dict()
            path = ckpt_manager.save(policy_step, state)
            last_checkpoint = policy_step
            return path

        if (
            cfg.checkpoint.every > 0
            and (policy_step - last_checkpoint) >= cfg.checkpoint.every
            or iter_num == num_iters
            and cfg.checkpoint.save_last
        ):
            save_ckpt()
        guard.boundary(policy_step, save_ckpt)

    monitor.close()
    envs.close()
    if prefetcher is not None:
        prefetcher.close()
    if cfg.algo.run_test and ctx.is_global_zero:
        reward = test(player_step, player_params(), player_state_init, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if logger is not None:
        logger.close()


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): the P2E-DV1
    exploration gradient block (world model + task/exploration heads + intrinsic
    ensembles in one ``make_train_block`` scan) at tiny MLP-only shapes."""
    from sheeprl_tpu.analysis.ir.synth import (
        DREAMER_TINY_OVERRIDES,
        compose_tiny,
        sequence_batch,
        tiny_ctx,
        vector_space,
    )
    from sheeprl_tpu.analysis.ir.types import AuditEntry
    from sheeprl_tpu.utils.blocks import make_train_block

    cfg = compose_tiny(
        [
            "exp=p2e_dv1_dummy",
            "env=discrete_dummy",
            *DREAMER_TINY_OVERRIDES,
            "algo.ensembles.n=2",
            "algo.ensembles.dense_units=8",
            "algo.ensembles.mlp_layers=1",
        ]
    )
    ctx = tiny_ctx(cfg)
    obs_space = vector_space()
    actions_dim, is_continuous = (3,), False
    world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
        ctx, actions_dim, is_continuous, cfg, obs_space
    )
    train_step, init_opt_states = make_train_step(
        world_model, actor, critic, ensemble_mlp, cfg, [], ["state"]
    )
    carry = (params, init_opt_states(params))

    def _block_step(carry, batch, key, update_target):
        del update_target
        params, opt_states = carry
        params, opt_states, metrics = train_step(params, opt_states, batch, key)
        return (params, opt_states), metrics

    block = make_train_block(_block_step, 1, 1)
    batch = sequence_batch(
        {"state": obs_space["state"].shape},
        act_dim=int(sum(actions_dim)),
        T=int(cfg.algo.per_rank_sequence_length),
        B=int(cfg.algo.per_rank_batch_size),
    )
    return [
        AuditEntry(
            name="p2e_dv1/train_block",
            fn=block,
            args=(carry, (batch,), jax.random.PRNGKey(0), 0),
            covers=("p2e_dv1_exploration",),
            precision=str(cfg.mesh.precision),
        )
    ]
