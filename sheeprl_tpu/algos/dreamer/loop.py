"""The host loop of the Dreamer family: one for DreamerV1/V2/V3 and the six Plan2Explore
entry points built on them.

An entry point builds its agent and its train step, says in an :class:`Entry` what is
its own, and calls :func:`run`.  The loop owns everything else: the environments, the
replay ring and its device mirror, the aggregator, checkpoints and resume, the guard,
and the iteration itself, in this order: act (or sample while prefilling), commit the
pending row, dispatch the gradient block, *then* step the environments, so that the
device runs the block under the host's walk through the environments.

The train state is held as one ``Packed`` (``utils/packed.py``) for every entry point;
the block and the player see the tree inside their jits, a checkpoint keeps the tree's
format.  The loop never asks which algorithm it serves: a difference between entry
points is a field of :class:`Entry`.
"""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import AbstractSet, Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import PlayerState, parse_actions_dim
from sheeprl_tpu.algos.dreamer_v3.utils import prepare_obs, test
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.obs import TrainingMonitor
from sheeprl_tpu.obs.health import replay_age_metrics
from sheeprl_tpu.rollout import PipelinedPlayer, rollout_metrics
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import make_aggregator, record_episode_stats
from sheeprl_tpu.utils.packed import pack
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio


def sequential_buffer(cfg, num_envs, obs_keys, log_dir, rank, world):
    """The ring every entry point samples sequences from: one sequential buffer an env."""
    return EnvIndependentReplayBuffer(
        max(int(cfg.buffer.size) // max(num_envs * world, 1), 1),
        n_envs=num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        buffer_cls=SequentialReplayBuffer,
    )


def _clip(reward):
    return np.clip(reward, -1, 1)


def _first(tree, aux):
    return tree[0]


@dataclasses.dataclass(frozen=True)
class Entry:
    """What an entry point hands the loop: plain values and callables.

    ``carry`` is the train state its block steps, a tuple of trees already placed on the
    mesh, and ``ckpt_names`` the name each part takes in a checkpoint.  ``aux`` is a
    device tree the player may read beside the carry and the block never sees (the
    exploration actor of a finetuning run)."""

    carry: Tuple[Any, ...]
    ckpt_names: Tuple[str, ...]
    #: ``block_step(carry, batch, key, update_target) -> (carry, metrics)`` and the
    #: dispatcher's cadence options (``target_update_freq``, ``count_offset``)
    block_step: Callable
    dispatcher_kwargs: Dict[str, Any]
    #: ``player_step(params, state, obs, is_first, key[, exploration amount])`` and the
    #: width of the (flat) stochastic state its ``PlayerState`` starts from
    player_step: Callable
    stochastic_size: int
    aggregator_keys: AbstractSet[str]
    #: how a restored tree is placed: ``ctx.shard_params`` or ``ctx.replicate``
    place: Callable[[Any], Any]
    #: ``(carry tree, aux) -> the player's parameters``, traced inside the player's jit
    player_params: Callable[[Any, Any], Any] = _first
    #: the same for the iterations before the first that trains (a finetuning run
    #: starts on the exploration actor); ``None``: ``player_params`` from the start
    starting_player_params: Optional[Callable[[Any, Any], Any]] = None
    aux: Any = None
    #: ``policy_step -> amount``: the extra acting argument of DV1/DV2, logged as
    #: ``Params/exploration_amount``
    exploration_amount: Optional[Callable[[int], float]] = None
    #: what ``env.clip_rewards`` applies
    clip_reward: Callable[[np.ndarray], np.ndarray] = _clip
    make_buffer: Callable = sequential_buffer
    #: sample actions at random until ``learning_starts`` (a pretrained or a MineDojo
    #: agent acts from the first step)
    random_prefill: bool = True
    #: ``(carry tree, aux, learning) -> checkpoint entries`` on host copies, where the
    #: checkpoint is not just the carry's parts by name; ``learning``: an iteration trained
    to_ckpt: Optional[Callable[[Any, Any, bool], Dict[str, Any]]] = None
    #: the checkpoint ``carry`` was built from, already read (a finetuning run starts
    #: from its exploration run's); ``None``: the loop reads ``checkpoint.resume_from``
    restored: Optional[Dict[str, Any]] = None


def run(ctx, cfg, setup: Callable[[Any, bool, Tuple[int, ...]], Entry], make_device_replay: Callable) -> None:
    """Train one entry point.  ``setup(obs_space, is_continuous, actions_dim)`` is called
    once the environments exist and returns the :class:`Entry`; ``make_device_replay`` is
    ``data/device_buffer.py``'s, passed by the caller so that the caller's module decides
    which one runs."""
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

    entry = setup(obs_space, is_continuous, actions_dim)
    tree, aux, state = entry.carry, entry.aux, entry.restored
    # From here on the loop holds the one reference to the train state: one left in the
    # entry would keep the initial state on the device beside the trained one for the
    # whole run (2.4 GiB at DreamerV3-XL).
    entry = dataclasses.replace(entry, carry=(), restored=None)

    # The player takes the loop's packed carry (below) and unpacks inside its own jit:
    # it reads only the rows of the parameters it uses.
    def _player_jit(view):
        return jax.jit(lambda carry, aux, *args: entry.player_step(view(carry.unpack(), aux), *args))

    player_jit = _player_jit(entry.player_params)
    starting_player_jit = player_jit
    if entry.starting_player_params is not None:
        starting_player_jit = _player_jit(entry.starting_player_params)
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size

    def player_state_init(n: int) -> PlayerState:
        return PlayerState(
            recurrent_state=jnp.zeros((n, rec_size)),
            stochastic_state=jnp.zeros((n, entry.stochastic_size)),
            actions=jnp.zeros((n, act_dim_sum)),
        )

    rb = entry.make_buffer(cfg, num_envs, obs_keys, log_dir, rank, world)
    rb.seed(cfg.seed + rank)

    # Device-resident replay (buffer.device): rows live in HBM, the host ships only
    # (env, start) indices, and each scan step gathers its batch in-jit — removes
    # the host→device batch traffic that otherwise floors e2e throughput.  Under
    # data parallelism the ring's env axis is sharded over the `data` mesh axis
    # (per-shard sampling + shard_map gather); multi-process runs keep the fast
    # path too via per-process local rings + a zero-copy global view
    # (data/device_buffer.py: MultiProcessDeviceReplayMirror).  Otherwise: async host
    # prefetch.  The whole iteration's gradient steps run as ONE jitted scan
    # (utils/blocks.py): one dispatch per iteration, per-step keys split inside the
    # jit, target cadence computed from the running step count.
    dispatcher, mirror, prefetcher, _run_block, rb_add = make_device_replay(
        ctx,
        cfg,
        rb,
        cnn_keys,
        mlp_keys,
        obs_space,
        act_dim_sum,
        entry.block_step,
        dispatcher_kwargs=entry.dispatcher_kwargs,
        # only the sequential ring has a device mirror; another kind stays on the host
        require_sequential=entry.make_buffer is not sequential_buffer,
    )

    # rank-independent (cross-process gathering) when multi-host
    aggregator = make_aggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(entry.aggregator_keys | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    guard = TrainingGuard(cfg, log_dir)
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)

    policy_steps_per_iter = num_envs * world * cfg.env.action_repeat
    total_steps = int(cfg.algo.total_steps)
    num_iters = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0

    def to_ckpt(tree, aux, learning):
        if entry.to_ckpt is not None:
            return entry.to_ckpt(tree, aux, learning)
        return dict(zip(entry.ckpt_names, tree))

    start_iter = 1
    policy_step = 0
    last_log = 0
    last_checkpoint = 0
    cumulative_grad_steps = 0
    resume_from = cfg.checkpoint.get("resume_from")
    if resume_from and state is None:
        state = CheckpointManager.load(resume_from, templates=to_ckpt(*jax.device_get((tree, aux)), False))
        tree = tuple(entry.place(state[name]) for name in entry.ckpt_names)
    if resume_from:
        ratio.load_state_dict(state["ratio"])
        start_iter = state["iter_num"] + 1
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)
        cumulative_grad_steps = state.get("cumulative_grad_steps", 0)
        learning_starts += start_iter
    # the ring a resumed run saved, or the one the checkpoint a run starts from hands on
    if state is not None and "rb" in state and (cfg.buffer.checkpoint or not resume_from):
        rb.load_state_dict(state["rb"])
        if mirror is not None:
            mirror.load_from(rb)
    del state
    # From here on the train state is one ``Packed`` (utils/packed.py): the hundreds of
    # small leaves stacked into one buffer a shape and dtype, the large ones as they are,
    # so the block's call hands back 150 buffers at DreamerV3-XL and not 552 (each costs
    # the host ~48 us on a v5e).  The block and the player see the tree inside their jits;
    # the checkpoint on disk keeps the tree's format.
    carry = pack(tree)
    del tree

    # Pending-row storage (reference ``dreamer_v3.py:538-651``): row t holds obs_t
    # together with the reward/terminated/truncated received when ARRIVING at obs_t
    # (zeros + is_first=1 after a reset); the action taken FROM obs_t is filled in just
    # before the row is committed.  On episode end an extra terminal row stores the
    # true final observation with a zero action.
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
    learning = False  # an iteration of this run has reached ``learning_starts``
    acting_extra: Tuple[Any, ...] = ()
    expl_amount = None

    # Acting pipeline (sheeprl_tpu/rollout): depth 0 is the historical synchronous
    # dispatch -> one device_get -> env.step path, bit-for-bit; depth>=1 overlaps
    # the policy jit and the action fetch with the workers' env step (policy lag).
    def _pipeline_policy(cur_obs):
        nonlocal player_state
        obs_t = prepare_obs(cur_obs, cnn_keys, mlp_keys, num_envs)
        actions, stored, player_state = (player_jit if learning else starting_player_jit)(
            carry, aux, player_state, obs_t, jnp.asarray(is_first_np), ctx.local_rng(), *acting_extra
        )
        return (stored, list(actions))

    def _pipeline_post(fetched):
        # ONE device_get for everything the host needs (per-array fetches would
        # each pay their own dispatch and device→host sync).
        stored_np, acts_list = fetched
        stored_actions = np.asarray(stored_np)
        acts_np = [np.asarray(a) for a in acts_list]
        if is_continuous:
            env_actions = acts_np[0]
        elif len(actions_dim) == 1:
            env_actions = acts_np[0].argmax(-1)
        else:
            env_actions = np.stack([a.argmax(-1) for a in acts_np], -1)
        return env_actions, stored_actions

    rollout_player = PipelinedPlayer(
        envs, _pipeline_policy, _pipeline_post, depth=int((cfg.get("rollout") or {}).get("pipeline_depth", 0))
    )

    step_data: Dict[str, np.ndarray] = _obs_row(obs)
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones((1, num_envs, 1), np.float32)
    is_first_np = np.ones((num_envs, 1), dtype=np.float32)
    prefill_iters = max(learning_starts - 1, 0)

    def save_ckpt():
        nonlocal last_checkpoint
        # the tree on the host, from one fetch of the packed buffers (process 0 writes it)
        trees = {}
        if ctx.is_global_zero:
            carry_host, aux_host = jax.device_get((carry, aux))
            trees = to_ckpt(carry_host.unpack(), aux_host, learning)
        state = {
            **trees,
            "ratio": ratio.state_dict(),
            "iter_num": iter_num,
            "policy_step": policy_step,
            "last_log": last_log,
            "last_checkpoint": policy_step,
            "cumulative_grad_steps": cumulative_grad_steps,
        }
        with monitor.phase("checkpoint"):
            if cfg.buffer.checkpoint:
                state["rb"] = rb.state_dict()
            path = ckpt_manager.save(policy_step, state)
        last_checkpoint = policy_step
        return path

    try:
        for iter_num in range(start_iter, num_iters + 1):
            monitor.advance()
            env_time = 0.0
            env_t0 = time.perf_counter()
            if entry.exploration_amount is not None:
                expl_amount = entry.exploration_amount(policy_step)
                acting_extra = (jnp.asarray(expl_amount),)
            with timer("Time/env_interaction_time"), monitor.phase("player"):
                if entry.random_prefill and iter_num <= learning_starts and not resume_from:
                    if is_continuous:
                        stored_actions = np.stack([act_space.sample() for _ in range(num_envs)]).astype(np.float32)
                        env_actions = stored_actions
                    else:
                        sampled = np.stack([act_space.sample() for _ in range(num_envs)])
                        sampled = sampled.reshape(num_envs, -1)
                        onehots = []
                        for i, d in enumerate(actions_dim):
                            oh = np.zeros((num_envs, d), dtype=np.float32)
                            oh[np.arange(num_envs), sampled[:, i]] = 1.0
                            onehots.append(oh)
                        stored_actions = np.concatenate(onehots, -1)
                        env_actions = sampled.squeeze(-1) if len(actions_dim) == 1 else sampled
                    # keep the player state in sync with the executed action
                    player_state = player_state._replace(actions=jnp.asarray(stored_actions))
                else:
                    env_actions, stored_actions = rollout_player.act(obs)

                # Commit the pending row with the action taken from its observation
                # (under the prefetcher's lock: the sampler thread must not read rows
                # mid-write).
                step_data["actions"] = stored_actions.reshape(1, num_envs, -1)
                with monitor.phase("buffer_add"):
                    rb_add(step_data, validate_args=cfg.buffer.validate_args)
            env_time += time.perf_counter() - env_t0

            # ---- dispatch this iteration's gradient block BEFORE stepping the envs:
            # the device executes it while the host walks the environments below
            # (acting above used the params from the end of the previous iteration,
            # exactly as the eager ordering did).  No device_get here — metrics are
            # futures, fetched at the log cadence.
            grad_steps = 0
            if iter_num >= learning_starts:
                # a player with a starting actor switches at the first training
                # iteration (reference p2e finetuning :350-352)
                learning = True
                grad_steps = ratio(
                    (policy_step + policy_steps_per_iter - prefill_iters * policy_steps_per_iter) / world
                )
                if grad_steps > 0:
                    with monitor.phase("dispatch"):
                        carry = _run_block(
                            carry, grad_steps, cumulative_grad_steps, stage_next=iter_num < num_iters
                        )
                    cumulative_grad_steps += grad_steps

            env_t0 = time.perf_counter()
            with timer("Time/env_interaction_time"), monitor.phase("env_step"):
                next_obs, reward, terminated, truncated, info = rollout_player.env_step(env_actions)
                if cfg.env.clip_rewards:
                    reward = entry.clip_reward(reward)
                done = np.logical_or(terminated, truncated)
                reward = np.asarray(reward, dtype=np.float32).reshape(num_envs, 1)

                # True final observation for done envs (SAME_STEP autoreset returns the
                # reset obs; the final one lives in info["final_obs"]).
                real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
                if done.any() and "final_obs" in info:
                    for i in np.nonzero(done)[0]:
                        if info["final_obs"][i] is not None:
                            for k in obs_keys:
                                real_next_obs[k][i] = np.asarray(info["final_obs"][i][k])

                # Build the next pending row: obs_{t+1} + arrival reward/flags.
                step_data = _obs_row(next_obs)
                step_data["rewards"] = reward.reshape(1, num_envs, 1).copy()
                step_data["terminated"] = terminated.astype(np.float32).reshape(1, num_envs, 1)
                step_data["truncated"] = truncated.astype(np.float32).reshape(1, num_envs, 1)
                step_data["is_first"] = np.zeros((1, num_envs, 1), np.float32)

                done_idxs = np.nonzero(done)[0].tolist()
                if done_idxs:
                    # Terminal row: final obs + arrival reward/flags + zero action.
                    reset_data = _obs_row(real_next_obs, idxs=done_idxs)
                    reset_data["rewards"] = step_data["rewards"][:, done_idxs]
                    reset_data["terminated"] = step_data["terminated"][:, done_idxs]
                    reset_data["truncated"] = step_data["truncated"][:, done_idxs]
                    reset_data["actions"] = np.zeros((1, len(done_idxs), act_dim_sum), np.float32)
                    reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                    rb_add(reset_data, indices=done_idxs, validate_args=cfg.buffer.validate_args)
                    # The pending row for reset envs starts a fresh episode.
                    step_data["rewards"][:, done_idxs] = 0.0
                    step_data["terminated"][:, done_idxs] = 0.0
                    step_data["truncated"][:, done_idxs] = 0.0
                    step_data["is_first"][:, done_idxs] = 1.0

                is_first_np = done.astype(np.float32).reshape(num_envs, 1)
                obs = next_obs
                policy_step += policy_steps_per_iter
                record_episode_stats(aggregator, info)
            env_time += time.perf_counter() - env_t0

            # Checkpoint BEFORE the log flush so phase_checkpoint lands in the
            # window it was paid in (and the final save_last is not dropped from
            # the breakdown).
            if (
                cfg.checkpoint.every > 0
                and (policy_step - last_checkpoint) >= cfg.checkpoint.every
                or iter_num == num_iters
                and cfg.checkpoint.save_last
            ):
                save_ckpt()

            if logger is not None and (
                policy_step - last_log >= cfg.metric.log_every or iter_num == num_iters or cfg.dry_run
            ):
                # The drain below is the window's only blocking sync: it waits for
                # every gradient block dispatched in the window, so the window
                # wall-clock is an honest end-to-end grad-steps/s denominator.
                with monitor.phase("drain"):
                    dispatcher.drain(aggregator)
                metrics = aggregator.compute()
                # The per-phase Time/phase_* breakdown is folded in by
                # monitor.log_metrics (the nested player timer includes
                # buffer_add — subtract when reading).
                window_sps = dispatcher.pop_window_sps()
                if window_sps is not None:
                    metrics["Time/sps_train"] = window_sps
                metrics["Time/sps_env_interaction"] = (
                    policy_steps_per_iter / world / env_time if env_time > 0 else 0.0
                )
                metrics["Params/replay_ratio"] = (
                    cumulative_grad_steps * world / policy_step if policy_step > 0 else 0.0
                )
                if expl_amount is not None:
                    metrics["Params/exploration_amount"] = expl_amount
                metrics.update(replay_age_metrics(rb))
                metrics.update(rollout_metrics(envs))
                monitor.log_metrics(logger, metrics, policy_step)
                aggregator.reset()
                last_log = policy_step
            guard.boundary(policy_step, save_ckpt)

    finally:
        monitor.close()
        envs.close()
        if prefetcher is not None:
            prefetcher.close()
    if cfg.algo.run_test and ctx.is_global_zero:
        reward = test(entry.player_step, entry.player_params(tuple(carry), aux), player_state_init, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if not cfg.get("model_manager", {}).get("disabled", True) and ctx.is_global_zero:
        from sheeprl_tpu.utils.model_manager import maybe_register_models

        maybe_register_models(cfg, log_dir)
    if logger is not None:
        logger.close()
