"""DreamerV2 training loop (reference: ``/root/reference/sheeprl/algos/dreamer_v2/dreamer_v2.py``).

Same single-jit structure as the DV3 loop (RSSM unroll + imagination as ``lax.scan``,
three optimizer steps fused, GSPMD data parallelism via sharded batches); the DV2
specifics it encodes from the reference:

* KL balancing with ``kl_balancing_alpha`` (reference ``dreamer_v2.py:185-199``);
* Gaussian (unit variance) observation/reward/value likelihoods — no symlog/two-hot;
* hard target-critic copy every ``per_rank_target_network_update_freq`` gradient steps,
  applied *before* the update (reference ``dreamer_v2.py:696-701``);
* actor objective = ``objective_mix``·REINFORCE + (1-mix)·dynamics-backprop
  (reference ``dreamer_v2.py:308-330``);
* replay buffer type ∈ {sequential, episode} (reference ``dreamer_v2.py:496-517``) —
  the EpisodeBuffer's only consumer, with ``prioritize_ends`` sampling;
* tanh reward clipping (reference ``dreamer_v2.py:434``).
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
from sheeprl_tpu.algos.dreamer_v2.agent import (
    PlayerState,
    WorldModelV2,
    build_agent,
    exploration_amount,
    make_player_step,
    parse_actions_dim,
)
from sheeprl_tpu.algos.dreamer_v2.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v2.utils import (
    AGGREGATOR_KEYS,
    compute_lambda_values,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, EpisodeBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal, OneHotCategorical
from sheeprl_tpu.obs import TrainingMonitor
from sheeprl_tpu.obs.health import diagnostics, health_enabled, replay_age_metrics
from sheeprl_tpu.rollout import rollout_metrics
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, record_episode_stats
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio


def make_train_step(world_model, actor, critic, cfg, cnn_keys, mlp_keys):
    wm_cfg = cfg.algo.world_model
    stoch = wm_cfg.stochastic_size
    discrete = wm_cfg.discrete_size
    stoch_size = stoch * discrete
    rec_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    gamma = cfg.algo.gamma
    lmbda = cfg.algo.lmbda
    ent_coef = cfg.algo.actor.ent_coef
    objective_mix = cfg.algo.actor.objective_mix
    is_continuous = actor.is_continuous
    actions_dim = tuple(actor.actions_dim)
    use_continues = wm_cfg.use_continues

    wm_opt = make_optimizer(wm_cfg.optimizer, wm_cfg.clip_gradients)
    actor_opt = make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_opt = make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)

    def init_opt_states(params):
        return {
            "world_model": wm_opt.init(params["world_model"]),
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
        }

    def train_step(params, opt_states, data, key, update_target: jax.Array):
        T, B = data["rewards"].shape[:2]
        k_wm, k_img, k_a0 = jax.random.split(key, 3)
        sg = jax.lax.stop_gradient

        # Hard target-critic copy BEFORE the update (reference dreamer_v2.py:696-701).
        target_params = jax.lax.cond(
            update_target,
            lambda: jax.tree.map(lambda x: x, params["critic"]),
            lambda: params["target_critic"],
        )

        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        is_first = data["is_first"].at[0].set(1.0)
        # Rows store the action taken FROM their observation; the RSSM consumes the
        # action leading TO it — shift right with a zero first action.
        batch_actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)

        # ------------------------------------------------ world model update
        def wm_loss_fn(wm_params):
            embed = world_model.apply(wm_params, batch_obs, method=WorldModelV2.encode)

            def step(carry, x):
                post, rec = carry
                action, emb, first, k = x
                rec, post, _, post_logits, prior_logits = world_model.apply(
                    wm_params, post, rec, action, emb, first, k, method=WorldModelV2.dynamic
                )
                return (post, rec), (rec, post, post_logits, prior_logits)

            keys = jax.random.split(k_wm, T)
            init = (jnp.zeros((B, stoch_size)), jnp.zeros((B, rec_size)))
            _, (recs, posts, post_logits, prior_logits) = jax.lax.scan(
                step, init, (batch_actions, embed, is_first, keys), unroll=8
            )
            latents = jnp.concatenate([posts, recs], -1)
            recon = world_model.apply(wm_params, latents, method=WorldModelV2.decode)

            # Unit-variance Gaussian likelihoods (reference dreamer_v2.py:167-170).
            obs_lp = 0.0
            for k in cnn_keys:
                target = data[k].astype(jnp.float32) / 255.0 - 0.5
                target = target.reshape(T, B, -1, *target.shape[-2:])
                obs_lp = obs_lp + Independent(Normal(recon[k], jnp.ones_like(recon[k])), 3).log_prob(target)
            for k in mlp_keys:
                obs_lp = obs_lp + Independent(Normal(recon[k], jnp.ones_like(recon[k])), 1).log_prob(data[k])

            reward_lp = Independent(
                Normal(world_model.apply(wm_params, latents, method=WorldModelV2.reward), 1.0), 1
            ).log_prob(data["rewards"])
            continue_lp = None
            if use_continues:
                continue_lp = Independent(
                    BernoulliSafeMode(world_model.apply(wm_params, latents, method=WorldModelV2.continues)), 1
                ).log_prob((1.0 - data["terminated"]) * gamma)

            post_logits_s = post_logits.reshape(T, B, stoch, discrete)
            prior_logits_s = prior_logits.reshape(T, B, stoch, discrete)
            rec_loss, metrics = reconstruction_loss(
                obs_lp,
                reward_lp,
                prior_logits_s,
                post_logits_s,
                wm_cfg.kl_balancing_alpha,
                wm_cfg.kl_free_nats,
                wm_cfg.kl_free_avg,
                wm_cfg.kl_regularizer,
                continue_lp,
                wm_cfg.discount_scale_factor,
            )
            metrics["State/post_entropy"] = Independent(OneHotCategorical(post_logits_s), 1).entropy().mean()
            metrics["State/prior_entropy"] = Independent(OneHotCategorical(prior_logits_s), 1).entropy().mean()
            return rec_loss, (posts, recs, metrics)

        (rec_loss, (posts, recs, wm_metrics)), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            params["world_model"]
        )
        wm_updates, new_wm_opt = wm_opt.update(wm_grads, opt_states["world_model"], params["world_model"])
        new_wm_params = optax.apply_updates(params["world_model"], wm_updates)

        # ------------------------------------------------ imagination + actor
        prior0 = sg(posts).reshape(T * B, stoch_size)
        rec0 = sg(recs).reshape(T * B, rec_size)
        latent0 = jnp.concatenate([prior0, rec0], -1)
        true_continue0 = (1.0 - data["terminated"]).reshape(T * B, 1) * gamma

        def actor_loss_fn(actor_params):
            def img_step(carry, k):
                prior, rec, latent = carry
                k_act, k_dyn = jax.random.split(k)
                acts, _ = actor.apply(actor_params, sg(latent), k_act)
                action = jnp.concatenate(acts, -1)
                prior, rec = world_model.apply(new_wm_params, prior, rec, action, k_dyn, method=WorldModelV2.imagination)
                new_latent = jnp.concatenate([prior, rec], -1)
                return (prior, rec, new_latent), (new_latent, action)

            keys = jax.random.split(k_img, horizon)
            _, (latents_img, actions_img) = jax.lax.scan(img_step, (prior0, rec0, latent0), keys, unroll=5)
            traj = jnp.concatenate([latent0[None], latents_img], 0)  # [H+1, N, L]
            imagined_actions = jnp.concatenate(
                [jnp.zeros_like(actions_img[:1]), actions_img], 0
            )  # [H+1, N, A]; index 0 is the zero action (reference dreamer_v2.py:237)

            target_values = critic.apply(target_params, traj)  # [H+1, N, 1]
            rewards_img = world_model.apply(new_wm_params, traj, method=WorldModelV2.reward)
            if use_continues:
                probs = jax.nn.sigmoid(world_model.apply(new_wm_params, traj, method=WorldModelV2.continues))
                continues = jnp.concatenate([true_continue0[None], probs[1:]], 0)
            else:
                continues = jnp.ones_like(rewards_img) * gamma

            lambda_values = compute_lambda_values(
                rewards_img[:-1], target_values[:-1], continues[:-1], target_values[-1:], lmbda
            )  # [H, N, 1]
            discount = sg(jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-1]], 0), 0))

            _, dists = actor.apply(actor_params, sg(traj[:-2]), None)
            dynamics = lambda_values[1:]
            advantage = sg(lambda_values[1:] - target_values[:-2])
            if is_continuous:
                logpi = dists[0].log_prob(sg(imagined_actions[1:-1])).sum(-1, keepdims=True)
                reinforce = logpi * advantage
                entropy = dists[0].entropy().sum(-1)
            else:
                logpis = []
                ent = 0.0
                offset_a = 0
                for i, d in enumerate(dists):
                    act_i = sg(imagined_actions[1:-1, ..., offset_a : offset_a + actions_dim[i]])
                    logpis.append(d.log_prob(act_i))
                    ent = ent + d.entropy()
                    offset_a += actions_dim[i]
                reinforce = sum(logpis)[..., None] * advantage
                entropy = ent
            objective = objective_mix * reinforce + (1 - objective_mix) * dynamics
            policy_loss = -jnp.mean(discount[:-2] * (objective + ent_coef * entropy[..., None]))
            aux = {"traj": sg(traj), "lambda_values": sg(lambda_values), "discount": discount}
            return policy_loss, aux

        (policy_loss, actor_aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        actor_updates, new_actor_opt = actor_opt.update(actor_grads, opt_states["actor"], params["actor"])
        new_actor_params = optax.apply_updates(params["actor"], actor_updates)

        # ------------------------------------------------ critic
        traj = actor_aux["traj"]
        lambda_values = actor_aux["lambda_values"]
        discount = actor_aux["discount"]

        def critic_loss_fn(critic_params):
            qv = Independent(Normal(critic.apply(critic_params, traj[:-1]), 1.0), 1)
            return -jnp.mean(discount[:-1, ..., 0] * qv.log_prob(lambda_values))

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        critic_updates, new_critic_opt = critic_opt.update(critic_grads, opt_states["critic"], params["critic"])
        new_critic_params = optax.apply_updates(params["critic"], critic_updates)

        new_params = {
            "world_model": new_wm_params,
            "actor": new_actor_params,
            "critic": new_critic_params,
            "target_critic": target_params,
        }
        new_opt_states = {"world_model": new_wm_opt, "actor": new_actor_opt, "critic": new_critic_opt}
        metrics = dict(wm_metrics)
        metrics["Loss/policy_loss"] = policy_loss
        metrics["Loss/value_loss"] = value_loss
        metrics["Grads/world_model"] = optax.global_norm(wm_grads)
        metrics["Grads/actor"] = optax.global_norm(actor_grads)
        metrics["Grads/critic"] = optax.global_norm(critic_grads)
        if health_enabled(cfg):  # trace-time constant (obs/health.py)
            metrics.update(
                diagnostics(
                    grads={"world_model": wm_grads, "actor": actor_grads, "critic": critic_grads},
                    params=new_params,
                    updates={"world_model": wm_updates, "actor": actor_updates, "critic": critic_updates},
                )
            )
        metrics = maybe_inject_nonfinite(cfg, metrics)
        if strict_enabled(cfg):  # trace-time constant: callback exists only in strict runs
            nan_scan(metrics, "dreamer_v2/train_step")
        return new_params, new_opt_states, metrics

    return train_step, init_opt_states


def make_buffer(cfg, num_envs, obs_keys, log_dir, rank, world):
    """sequential | episode buffer switch (reference ``dreamer_v2.py:496-517``)."""
    buffer_size = max(int(cfg.buffer.size) // max(num_envs * world, 1), 1)
    buffer_type = str(cfg.buffer.get("type", "sequential")).lower()
    memmap_dir = os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None
    if buffer_type == "sequential":
        return EnvIndependentReplayBuffer(
            buffer_size,
            n_envs=num_envs,
            obs_keys=obs_keys,
            memmap=cfg.buffer.memmap,
            memmap_dir=memmap_dir,
            buffer_cls=SequentialReplayBuffer,
        )
    if buffer_type == "episode":
        return EpisodeBuffer(
            buffer_size,
            minimum_episode_length=1 if cfg.dry_run else cfg.algo.per_rank_sequence_length,
            n_envs=num_envs,
            obs_keys=obs_keys,
            prioritize_ends=cfg.buffer.get("prioritize_ends", False),
            memmap=cfg.buffer.memmap,
            memmap_dir=memmap_dir,
        )
    raise ValueError(f"Unrecognized buffer type: must be one of `sequential` or `episode`, received: {buffer_type}")


@register_algorithm(name="dreamer_v2")
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

    world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
    train_step, init_opt_states = make_train_step(world_model, actor, critic, cfg, cnn_keys, mlp_keys)
    opt_states = ctx.replicate(init_opt_states(params))
    target_update_freq = cfg.algo.critic.per_rank_target_network_update_freq

    # One jitted scan per iteration's gradient block (utils/blocks.py); DV2's hard
    # target copy tests the count BEFORE the increment (fires on the first step).
    def _block_step(carry, batch, key, update_target):
        params, opt_states = carry
        params, opt_states, metrics = train_step(params, opt_states, batch, key, update_target)
        return (params, opt_states), metrics


    player_step = make_player_step(world_model, actor, actions_dim, is_continuous)
    player_jit = jax.jit(player_step, static_argnames=("greedy",))
    stoch_size = cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size

    def player_state_init(n: int) -> PlayerState:
        return PlayerState(
            recurrent_state=jnp.zeros((n, rec_size)),
            stochastic_state=jnp.zeros((n, stoch_size)),
            actions=jnp.zeros((n, act_dim_sum)),
        )

    rb = make_buffer(cfg, num_envs, obs_keys, log_dir, rank, world)
    rb.seed(cfg.seed + rank)
    # Device-vs-host replay data path, one shared implementation
    # (data/device_buffer.py); DV2's episode buffer stays on host.
    dispatcher, mirror, prefetcher, _run_block, rb_add = make_device_replay(
        ctx,
        cfg,
        rb,
        cnn_keys,
        mlp_keys,
        obs_space,
        act_dim_sum,
        _block_step,
        dispatcher_kwargs=dict(target_update_freq=target_update_freq, count_offset=0),
        require_sequential=True,
    )
    is_episode_buffer = isinstance(rb, EpisodeBuffer)

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
    is_minedojo = "minedojo" in str(cfg.env.get("wrapper", {}).get("_target_", "")).lower()

    for iter_num in range(start_iter, num_iters + 1):
        monitor.advance()
        env_t0 = time.perf_counter()
        expl_amount = exploration_amount(
            expl_cfg.get("expl_amount", 0.0), expl_cfg.get("expl_decay", 0.0), expl_cfg.get("expl_min", 0.0), policy_step
        )
        with timer("Time/env_interaction_time"):
            if iter_num <= learning_starts and not cfg.checkpoint.get("resume_from") and not is_minedojo:
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
                    params, player_state, obs_t, jnp.asarray(is_first_np), ctx.local_rng(), jnp.asarray(expl_amount)
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
                rb_add(reset_data, indices=done_idxs, validate_args=cfg.buffer.validate_args)
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
            metrics.update(replay_age_metrics(rb))
            metrics.update(rollout_metrics(envs))
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
        reward = test(player_step, params, player_state_init, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if logger is not None:
        logger.close()


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): the DreamerV2
    gradient block (``make_train_step`` in the dispatcher's ``make_train_block``
    scan, hard target copies on the DV2 ``count_offset=0`` cadence) at tiny
    MLP-only synthetic shapes."""
    from sheeprl_tpu.analysis.ir.synth import (
        DREAMER_DISCRETE_OVERRIDES,
        DREAMER_TINY_OVERRIDES,
        compose_tiny,
        sequence_batch,
        tiny_ctx,
        vector_space,
    )
    from sheeprl_tpu.analysis.ir.types import AuditEntry
    from sheeprl_tpu.utils.blocks import make_train_block

    cfg = compose_tiny(
        ["exp=dreamer_v2_dummy", "env=discrete_dummy", *DREAMER_TINY_OVERRIDES, *DREAMER_DISCRETE_OVERRIDES]
    )
    ctx = tiny_ctx(cfg)
    obs_space = vector_space()
    actions_dim, is_continuous = (3,), False
    world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
    train_step, init_opt_states = make_train_step(world_model, actor, critic, cfg, [], ["state"])
    carry = (params, init_opt_states(params))

    def _block_step(carry, batch, key, update_target):
        params, opt_states = carry
        params, opt_states, metrics = train_step(params, opt_states, batch, key, update_target)
        return (params, opt_states), metrics

    block = make_train_block(_block_step, cfg.algo.critic.per_rank_target_network_update_freq, 0)
    batch = sequence_batch(
        {"state": obs_space["state"].shape},
        act_dim=int(sum(actions_dim)),
        T=int(cfg.algo.per_rank_sequence_length),
        B=int(cfg.algo.per_rank_batch_size),
    )
    return [
        AuditEntry(
            name="dreamer_v2/train_block",
            fn=block,
            args=(carry, (batch,), jax.random.PRNGKey(0), 0),
            covers=("dreamer_v2", "p2e_dv2_finetuning"),
            precision=str(cfg.mesh.precision),
        )
    ]
