"""P2E-DV3 finetuning (reference: ``/root/reference/sheeprl/algos/p2e_dv3/p2e_dv3_finetuning.py``).

Loads the exploration checkpoint (world model + both actors + task critic + optimizer
states + task Moments, reference ``:130-170``) and finetunes the TASK policy with the
standard DreamerV3 train step — the functional param split makes this literally the DV3
``train_step`` applied to the ``{world_model, actor_task, critic_task,
target_critic_task}`` slice of the Plan2Explore parameter tree.

The player starts acting with the exploration actor and switches to the task actor at
the first gradient step (reference ``:350-352``; ``algo.player.actor_type`` selects the
starting actor).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import PlayerState, make_player_step
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step as make_dv3_train_step
from sheeprl_tpu.algos.p2e import load_exploration_config  # noqa: F401  (re-export for the CLI)
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent, parse_actions_dim
from sheeprl_tpu.algos.p2e_dv3.p2e_dv3_exploration import make_train_step as make_expl_train_step
from sheeprl_tpu.algos.p2e_dv3.utils import AGGREGATOR_KEYS, init_moments, prepare_obs, test
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.obs import TrainingMonitor
from sheeprl_tpu.obs.health import replay_age_metrics
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, record_episode_stats
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio


@register_algorithm(name="p2e_dv3_finetuning")
def main(ctx, cfg, exploration_cfg=None) -> None:
    if exploration_cfg is None:
        exploration_cfg = load_exploration_config(cfg)
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

    critic_cfgs = {
        k: {"weight": v["weight"], "reward_type": v["reward_type"]}
        for k, v in cfg.algo.critics_exploration.items()
        if v["weight"] > 0
    }
    world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
        ctx, actions_dim, is_continuous, cfg, obs_space
    )
    # Exploration-shaped state templates (for loading the exploration checkpoint).
    _, expl_init_opt, expl_init_moments = make_expl_train_step(
        world_model, actor, critic, ensemble_mlp, cfg, cnn_keys, mlp_keys, critic_cfgs
    )
    expl_opt_template = expl_init_opt(params)
    expl_moments_template = expl_init_moments()

    # The finetuning train step IS the DV3 one over the task slice.
    train_step, init_opt_states = make_dv3_train_step(
        world_model, actor, critic, cfg, cnn_keys, mlp_keys, {k: obs_space[k].shape for k in obs_keys}
    )
    # One jitted scan per iteration's gradient block (utils/blocks.py); the EMA
    # target cadence tests the count BEFORE the increment, as the eager loop did.
    def _block_step(carry, batch, key, update_target):
        params, opt_states, moments = carry
        params, opt_states, moments, metrics = train_step(
            params, opt_states, moments, batch, key, update_target
        )
        return (params, opt_states, moments), metrics

    def task_view(p):
        return {
            "world_model": p["world_model"],
            "actor": p["actor_task"],
            "critic": p["critic_task"],
            "target_critic": p["target_critic_task"],
        }

    def merge_task_view(p, view):
        p = dict(p)
        p["world_model"] = view["world_model"]
        p["actor_task"] = view["actor"]
        p["critic_task"] = view["critic"]
        p["target_critic_task"] = view["target_critic"]
        return p

    resume_from = cfg.checkpoint.get("resume_from")
    ckpt_to_load = resume_from or cfg.checkpoint.exploration_ckpt_path
    state = CheckpointManager.load(
        ckpt_to_load,
        templates={
            "params": jax.device_get(params),
            "opt_states": jax.device_get(expl_opt_template),
            "moments": jax.device_get(expl_moments_template),
        },
    )
    params = ctx.shard_params(state["params"])
    loaded_opts = state["opt_states"]
    opt_states = ctx.shard_params(
        {
            "world_model": loaded_opts["world_model"],
            "actor": loaded_opts["actor_task"],
            "critic": loaded_opts["critic_task"],
        }
    )
    moments_state = ctx.replicate(state["moments"]["task"])

    player_step = make_player_step(world_model, actor, actions_dim, cfg.algo.world_model.discrete_size)
    player_jit = jax.jit(player_step, static_argnames=("greedy",))
    actor_type = cfg.algo.player.get("actor_type", "exploration")
    stoch_size = cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size

    def player_params():
        key = "actor_exploration" if actor_type == "exploration" else "actor_task"
        return {"world_model": params["world_model"], "actor": params[key]}

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
    if (resume_from or cfg.buffer.get("load_from_exploration")) and "rb" in state:
        rb.load_state_dict(state["rb"])

    # Device-vs-host replay data path, one shared implementation
    # (data/device_buffer.py); the mirror is rebuilt from the restored host buffer
    # (resume or exploration hand-off) before training starts.
    dispatcher, mirror, prefetcher, _run_block, rb_add = make_device_replay(
        ctx,
        cfg,
        rb,
        cnn_keys,
        mlp_keys,
        obs_space,
        act_dim_sum,
        _block_step,
        dispatcher_kwargs=dict(
            target_update_freq=cfg.algo.critic.per_rank_target_network_update_freq, count_offset=0
        ),
    )
    if mirror is not None and len(rb) > 0:
        mirror.load_from(rb)

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
    target_update_freq = cfg.algo.critic.per_rank_target_network_update_freq

    start_iter = 1
    policy_step = 0
    last_log = 0
    last_checkpoint = 0
    cumulative_grad_steps = 0
    if resume_from:
        ratio.load_state_dict(state["ratio"])
        start_iter = state["iter_num"] + 1
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)
        cumulative_grad_steps = state.get("cumulative_grad_steps", 0)
        learning_starts += start_iter
        actor_type = state.get("actor_type", actor_type)

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
        with timer("Time/env_interaction_time"):
            # The exploration policy (or the loaded task policy) acts from the start —
            # no random prefill, the agent is pretrained (reference :330-:352).
            obs_t = prepare_obs(obs, cnn_keys, mlp_keys, num_envs)
            actions, stored, player_state = player_jit(
                player_params(), player_state, obs_t, jnp.asarray(is_first_np), ctx.local_rng()
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
            # The player switches to the TASK actor at the first training iteration
            # (reference p2e finetuning :350-352).
            if actor_type != "task":
                actor_type = "task"
            grad_steps = ratio(
                (policy_step + policy_steps_per_iter - prefill_iters * policy_steps_per_iter) / world
            )
            if grad_steps > 0:
                view, opt_states, moments_state = _run_block(
                    (task_view(params), opt_states, moments_state),
                    grad_steps,
                    cumulative_grad_steps,
                    stage_next=iter_num < num_iters,
                )
                params = merge_task_view(params, view)
                cumulative_grad_steps += grad_steps

        env_t0 = time.perf_counter()
        with timer("Time/env_interaction_time"):
            next_obs, reward, terminated, truncated, info = envs.step(env_actions)
            if cfg.env.clip_rewards:
                reward = np.clip(reward, -1, 1)
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
            monitor.log_metrics(logger, metrics, policy_step)
            aggregator.reset()
            last_log = policy_step

        def save_ckpt():
            nonlocal last_checkpoint
            full_opts = dict(loaded_opts)
            on_device = jax.device_get(opt_states)
            full_opts["world_model"] = on_device["world_model"]
            full_opts["actor_task"] = on_device["actor"]
            full_opts["critic_task"] = on_device["critic"]
            full_moments = {"task": moments_state, "expl": expl_moments_template}
            ckpt_state = {
                "params": params,
                "opt_states": full_opts,
                "moments": full_moments,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": policy_step,
                "cumulative_grad_steps": cumulative_grad_steps,
                "actor_type": actor_type,
            }
            if cfg.buffer.checkpoint:
                ckpt_state["rb"] = rb.state_dict()
            path = ckpt_manager.save(policy_step, ckpt_state)
            last_checkpoint = policy_step
            return path

        if (
            cfg.checkpoint.every > 0
            and (policy_step - last_checkpoint) >= cfg.checkpoint.every
            or iter_num == num_iters
            and cfg.checkpoint.save_last
        ):
            # Save the exploration-shaped state so both resume (this entry) and
            # evaluation can reload it with the same templates; untrained entries
            # keep the optimizer moments loaded from the exploration checkpoint.
            save_ckpt()
        guard.boundary(policy_step, save_ckpt)

    monitor.close()
    envs.close()
    if prefetcher is not None:
        prefetcher.close()
    if cfg.algo.run_test and ctx.is_global_zero:
        reward = test(
            player_step,
            {"world_model": params["world_model"], "actor": params["actor_task"]},
            player_state_init,
            ctx,
            cfg,
            log_dir,
        )
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if logger is not None:
        logger.close()
