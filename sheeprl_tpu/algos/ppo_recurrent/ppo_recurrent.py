"""Recurrent PPO training loop (reference: ``algos/ppo_recurrent/ppo_recurrent.py:120-…``).

Rollout carries the sequence model's state per env (reset at episode starts); the update
runs BPTT over the fixed ``[rollout_steps, num_envs]`` sequences from the stored initial
state, minibatching over the env/sequence axis — ``update_epochs`` × sequence-minibatches
in one jitted ``lax.scan`` chain, like the feed-forward PPO.

The carry is one tree for every ``algo.sequence_model``: the LSTM's ``(c, h)``, the
attention variant's ``(window, valid)``, the decoder's per-layer caches and convolution
tails (``models/decoder.py``).  ``act_fn``, the rollout and ``train_fn`` pass it as one
argument; the update reads the carry of the rollout's start as a constant.  An acting
step crosses the host-device boundary once each way: host arrays in (``StepInputs``),
the sampling key beside the state in ``act_fn``'s donated argument, one buffer of
per-env results out.  The
decoder variant (``exp=ppo_recurrent_decoder``) reads and writes token ids, keeps its
context across rollouts, and forms the head's loss in token chunks."""

from __future__ import annotations

import math
import os
import re
import time
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.algos.ppo.utils import AGGREGATOR_KEYS, prepare_obs, sample_actions
from sheeprl_tpu.algos.ppo_recurrent.agent import (
    DecoderPPOAgent,
    RecurrentPPOAgent,
    build_agent,
    evaluate_sequences,
    make_zero_state,
)
from sheeprl_tpu.models.decoder import cast_matmul_weights, hold_buffers
from sheeprl_tpu.analysis.strict import assert_finite, maybe_inject_nonfinite, strict_guard
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.obs import perf as obs_perf
from sheeprl_tpu.obs import TrainingMonitor, flight_recorder
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.obs.tracer import span
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, record_episode_stats
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import gae, normalize_tensor, polynomial_decay


def _onehot_actions(env_act: np.ndarray, actions_dim, is_continuous: bool, as_ids: bool = False) -> np.ndarray:
    """The previous action as the sequence model reads it: continuous values, one-hot
    columns, or (``as_ids``, the decoder) the ids themselves."""
    if is_continuous:
        return env_act.astype(np.float32)
    n = env_act.shape[0]
    if as_ids:
        return env_act.reshape(n, -1).astype(np.int32)
    out = []
    acts = env_act.reshape(n, -1)
    for i, d in enumerate(actions_dim):
        oh = np.zeros((n, d), dtype=np.float32)
        oh[np.arange(n), acts[:, i].astype(int)] = 1.0
        out.append(oh)
    return np.concatenate(out, -1)


def agent_step(agent, p, obs, prev_actions, is_first, state):
    """One env-side step of either agent: ``(actor_out, value [B, 1], new_state)``."""
    if isinstance(agent, DecoderPPOAgent):
        (tokens,) = obs.values()
        return agent.apply(
            p, tokens[:, 0].astype(jnp.int32), prev_actions[:, 0], is_first, state, method=DecoderPPOAgent.step
        )
    return agent.apply(p, obs, prev_actions, is_first, state, method=RecurrentPPOAgent.step)


class StepInputs:
    """What a jitted step of the rollout takes of the host, and how it reads it.

    Every host array that goes into a jitted call is a transfer of its own (~0.1 ms of host
    time each on a TPU v5e's host, PERF.md section 6, PR 31), so the per-env vectors of a step
    (the vector observation keys, the previous action, ``is_first``) go in as one array a
    dtype, ``[envs, -1]`` each and side by side; image keys stay buffers of their own.
    ``is_first``, a 0/1 flag, travels in the dtype of the previous action it gates (the
    decoder's three vectors are one int32 ``[envs, 3]``).  The layout is read off the first
    arrays; nothing here asks which sequence model runs."""

    def __init__(self, cnn_keys, mlp_keys, obs, prev_actions):
        self.cnn_keys = tuple(cnn_keys)
        vectors = {**{k: obs[k] for k in mlp_keys}, "prev_actions": prev_actions, "is_first": prev_actions[:, :1]}
        self.groups: Dict[str, list] = {}  # dtype -> [(name, shape an env), ...], side by side in that order
        for name, v in vectors.items():
            self.groups.setdefault(v.dtype.name, []).append((name, v.shape[1:]))

    def host(self, obs, prev_actions, is_first):
        """``(images, vectors)`` for a jitted step: host arrays, the vectors one a dtype."""
        vectors = {**obs, "prev_actions": prev_actions, "is_first": is_first.astype(prev_actions.dtype)}
        n = len(is_first)
        packed = {
            dtype: np.concatenate([vectors[name].reshape(n, -1) for name, _ in members], -1)
            for dtype, members in self.groups.items()
        }
        return {k: obs[k] for k in self.cnn_keys}, packed

    def device(self, images, packed):
        """Inside the jitted step: ``(obs, prev_actions, is_first)`` as the agent reads them
        (vector keys cast to float32 here, as ``prepare_obs`` does on the host elsewhere)."""
        rows = {}
        for dtype, members in self.groups.items():
            start = 0
            for name, shape in members:
                stop = start + math.prod(shape)
                rows[name] = packed[dtype][:, start:stop].reshape(-1, *shape)
                start = stop
        prev_actions, is_first = rows.pop("prev_actions"), rows.pop("is_first").astype(jnp.float32)
        return {**images, **{k: v.astype(jnp.float32) for k, v in rows.items()}}, prev_actions, is_first


def make_acting_fns(agent, inputs: StepInputs):
    """The two jitted calls of the rollout, over ``inputs.host(...)``'s two arguments:
    ``act(p, images, vectors, (state, key))`` -> ``(results, (state, key))`` and
    ``value(p, images, vectors, state)`` -> values ``[envs]``, which writes nothing.

    ``act``'s last argument is donated: the state is written where it lies (the decoder's
    caches are GBs), and the sampling key lives beside it, split here a step, so no key is
    drawn on the host.  ``results`` is the one buffer that comes back, float32
    ``[envs, A + 2]``: the environment's actions (ids are exact in float32), the
    log-probability and the value."""
    is_continuous = agent.is_continuous

    def act(p, images, vectors, carry):
        state, key = carry
        key, sub = jax.random.split(key)
        actor_out, value, state = agent_step(agent, p, *inputs.device(images, vectors), state)
        env_act, _, logprob = sample_actions(sub, actor_out, is_continuous)
        results = [env_act.astype(jnp.float32).reshape(len(logprob), -1), logprob[:, None], value]
        return jnp.concatenate(results, -1), (state, key)

    def value(p, images, vectors, state):
        return agent_step(agent, p, *inputs.device(images, vectors), state)[1][..., 0]

    return jax.jit(act, donate_argnums=(3,)), jax.jit(value)


def acting_boundary(compiled, args) -> Dict[str, int]:
    """What crosses the jit boundary a call of ``compiled`` with ``args``: the arguments
    that are host arrays, the leaves of donated arguments whose place an output takes,
    and the outputs that get a buffer of their own."""
    head = compiled.as_text().split("\n", 1)[0]
    aliased = len(re.findall(r"(?:may|must)-alias", head))
    return {
        "host_arrays_in": sum(isinstance(x, np.ndarray) for x in jax.tree.leaves(args)),
        "donated_aliased": aliased,
        "fresh_out": len(jax.tree.leaves(compiled.out_info)) - aliased,
    }


def epoch_keys(key: jax.Array, epochs: int) -> jax.Array:
    """The key of each of an update's ``epochs``, from the update's own ``key``."""
    return jax.random.split(key, epochs)


def epoch_minibatches(epoch_key: jax.Array, num_envs: int, num_batches: int) -> jax.Array:
    """``[num_batches, num_envs // num_batches]``: the envs of each sequence minibatch of one
    epoch, a permutation of the envs drawn from the epoch's key."""
    return jax.random.permutation(epoch_key, num_envs).reshape(num_batches, num_envs // num_batches)


def make_ppo_recurrent_train_fn(ctx, agent, cfg, obs_keys):
    """Optimizer + the jitted BPTT sequence-minibatch update
    ``train_fn(params, opt_state, seq_data, state0, key, clip_coef, ent_coef)``; ``state0``
    is the carry at the rollout's start, a tree with the env axis leading every leaf.

    Module-level (rather than a closure in ``main``) so the IR audit
    (``sheeprl_tpu.analysis.ir``) can AOT-lower the exact update the entry point
    jits — the same reason ``make_a2c_train_fn`` moved out for the flight
    recorder."""
    opt = make_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    is_decoder = isinstance(agent, DecoderPPOAgent)
    health = health_enabled(cfg)  # trace-time constant (obs/health.py)
    num_envs = cfg.env.num_envs
    num_batches = max(int(cfg.algo.per_rank_num_batches), 1)
    if num_envs % num_batches != 0:
        raise ValueError(
            f"env.num_envs ({num_envs}) must be divisible by algo.per_rank_num_batches "
            f"({num_batches}): sequence minibatches must be equally sized for static shapes."
        )
    mb_envs = num_envs // num_batches

    def seq_loss_fn(p, batch, state0, clip_coef, ent_coef):
        logprob, entropy, values, counters = evaluate_sequences(agent, p, batch, obs_keys, state0)
        adv = batch["advantages"]
        if cfg.algo.normalize_advantages:
            adv = normalize_tensor(adv)
        pg = policy_loss(logprob, batch["logprobs"], adv, clip_coef, "mean")
        vf = value_loss(values, batch["values"], batch["returns"], clip_coef, cfg.algo.clip_vloss, "mean")
        ent = entropy_loss(entropy, cfg.algo.loss_reduction)
        total = pg + cfg.algo.vf_coef * vf + cfg.algo.ent_coef * ent
        aux = {"Loss/policy_loss": pg, "Loss/value_loss": vf, "Loss/entropy_loss": -ent, **counters}
        if is_decoder:  # new over old probabilities; 1 before the first step of an update
            aux["Health/ratio_first_epoch"] = jnp.exp(logprob - batch["logprobs"]).mean()
        if health:
            aux["Health/policy_entropy"] = entropy.mean()
            aux["Health/value_mean"] = values.mean()
        return total, aux

    # Shard each [T, mb_envs, ...] minibatch over the data axis (same pattern as
    # ppo.py:134,171) so gradient computation is data-parallel under GSPMD.
    dp_ok = ctx.data_parallel_size > 1 and mb_envs % ctx.data_parallel_size == 0
    mb_sharding = ctx.sharding(None, "data")

    def train_fn(p, o_state, seq_data, state0, key, clip_coef, ent_coef):
        def mb_step(carry, env_idx):
            p, o_state = carry
            batch = jax.tree.map(lambda x: x[:, env_idx], seq_data)
            if dp_ok:
                batch = jax.tree.map(lambda x: jax.lax.with_sharding_constraint(x, mb_sharding), batch)
            mb_state = jax.tree.map(lambda x: x[env_idx], state0)
            (_, aux), grads = jax.value_and_grad(seq_loss_fn, has_aux=True)(p, batch, mb_state, clip_coef, ent_coef)
            with obs_perf.scope("policy_optimizer"):
                updates, o_state = opt.update(grads, o_state, p)
                if is_decoder:  # a router's selection bias is no trained weight: Adam leaves it as it is
                    updates = hold_buffers(updates)
                p = optax.apply_updates(p, updates)
            if health:  # per-module norms/ratios, averaged by the scans below
                with obs_perf.scope("health"):
                    aux = {**aux, **diagnostics(grads=grads, params=p, updates=updates)}
            return (p, o_state), aux

        def epoch_step(carry, ekey):
            perm = epoch_minibatches(ekey, num_envs, num_batches)
            carry, auxs = jax.lax.scan(mb_step, carry, perm)
            means = jax.tree.map(jnp.mean, auxs)
            if is_decoder:  # the minibatch before the epoch's first step, not the mean over them
                means["Health/ratio_first_epoch"] = auxs["Health/ratio_first_epoch"][0]
            return carry, means

        keys = epoch_keys(key, cfg.algo.update_epochs)
        (p, o_state), per_epoch = jax.lax.scan(epoch_step, (p, o_state), keys)
        metrics = jax.tree.map(jnp.mean, per_epoch)
        if is_decoder:
            metrics["Health/ratio_first_epoch"] = per_epoch["Health/ratio_first_epoch"][0]
        return p, o_state, maybe_inject_nonfinite(cfg, metrics)

    # The decoder's parameters and Adam's moments are most of the chip's memory: the
    # update writes them where they lie.
    return opt, jax.jit(train_fn, donate_argnums=(0, 1) if is_decoder else ())


@register_algorithm(name="ppo_recurrent")
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
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    agent, params = build_agent(ctx, act_space, obs_space, cfg)
    is_continuous = agent.is_continuous
    actions_dim = agent.action_dims
    is_decoder = isinstance(agent, DecoderPPOAgent)
    # the previous action as the model reads it: one-hot columns, or the decoder's one id
    prev_shape, prev_dtype = ((1,), np.int32) if is_decoder else ((int(sum(actions_dim)),), np.float32)

    opt, train_fn = make_ppo_recurrent_train_fn(ctx, agent, cfg, obs_keys)
    opt_state = ctx.replicate(opt.init(params))

    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    world = jax.process_count()
    policy_steps_per_iter = int(num_envs * rollout_steps * world)
    num_updates = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1) if not cfg.dry_run else 1
    num_batches = max(int(cfg.algo.per_rank_num_batches), 1)

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

    gamma, gae_lambda = cfg.algo.gamma, cfg.algo.gae_lambda

    # the decoder's acting steps read its matmul weights in the compute dtype, cast once an update
    acting_params = jax.jit(lambda p: cast_matmul_weights(p, ctx.compute_dtype)) if is_decoder else (lambda p: p)
    not_first = np.zeros((num_envs, 1), np.float32)

    gae_fn = jax.jit(lambda r, v, d, nv: gae(r, v, d, nv, rollout_steps, gamma, gae_lambda))

    # analysis.strict: signature guard on the jitted update (drift -> hard error)
    train_fn = obs_perf.instrument(cfg, "ppo_recurrent/train_fn", strict_guard(cfg, "ppo_recurrent/train_fn", train_fn))

    # Flight recorder: no replay builder for the recurrent update yet — staging
    # still dumps the offending batch + state for forensics.
    recorder = flight_recorder.get_active()

    start_update, policy_step, last_log, last_checkpoint = 1, 0, 0, 0
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from,
            templates={"params": jax.device_get(params), "opt_state": jax.device_get(opt_state)},
        )
        params = ctx.replicate(state["params"])
        opt_state = ctx.replicate(state["opt_state"])
        start_update = state["update"] + 1
        policy_step = state["policy_step"]
        last_log = state.get("last_log", 0)
        last_checkpoint = state.get("last_checkpoint", 0)

    def host_obs(o):  # the keys the policy reads, as the host arrays the jitted calls take
        return {k: np.asarray(o[k]) for k in obs_keys}

    obs = host_obs(envs.reset(seed=cfg.seed + rank)[0])
    prev_stored = np.zeros((num_envs, *prev_shape), dtype=prev_dtype)
    is_first_np = np.ones((num_envs, 1), dtype=np.float32)
    # The carry is written where it lies (donated), the sampling key inside it.  A value
    # that must leave it as it is (a truncated episode's last observation, the rollout's
    # bootstrap) comes from a call of its own over all the rows: static shapes, so no count
    # of truncated envs compiles anew.
    inputs = StepInputs(cnn_keys, mlp_keys, obs, prev_stored)
    act_jit, value_fn = make_acting_fns(agent, inputs)
    act_fn = obs_perf.instrument(cfg, "ppo_recurrent/act_fn", act_jit)

    def note_boundary(*args):
        """Before the first acting call: what crosses its boundary, counted from the compiled
        call (which that call finds in jit's cache).  No reference to ``args`` outlives this:
        the acting parameters are a copy that must go before the update needs the room."""
        obs_perf.note("acting_boundary", acting_boundary(act_jit.lower(*args).compile(), args))

    zero_state = make_zero_state(cfg, ctx.compute_dtype)
    is_attention = cfg.algo.get("sequence_model", "lstm") == "attention"
    # what act_fn overwrites a step: the sequence model's state and the sampling key, one
    # draw off this process's chain for the whole run
    carry = (zero_state(num_envs), ctx.local_rng())
    step_data: Dict[str, np.ndarray] = {}

    for update in range(start_update, num_updates + 1):
        monitor.advance()
        # Every host phase of the cycle is a span, so that a capture names the device's idle
        # time under each; `monitor.advance` stays outside them, since it closes and opens the
        # update's own annotations (and may start or stop a capture)
        with span("Time/rollout_prep"):
            if is_attention:
                # The attention context never crosses a rollout boundary: training
                # attends within the rollout only, so acting resets its window here —
                # the policies stay EXACTLY on-policy.
                carry = (zero_state(num_envs), carry[1])
            # the state at the rollout's start, which the update reads: a copy, since the acting
            # steps overwrite theirs
            state0 = jax.tree.map(jnp.copy, carry[0])
            act_params = acting_params(params)
            if update == start_update and obs_perf.perf_enabled(cfg):
                note_boundary(act_params, *inputs.host(obs, prev_stored, is_first_np), carry)
        env_t0 = time.perf_counter()
        with timer("Time/env_interaction_time"):
            for _ in range(rollout_steps):
                with span("Rollout/act_call"):  # host arrays in (the call's own transfer), one buffer out
                    results, carry = act_fn(act_params, *inputs.host(obs, prev_stored, is_first_np), carry)
                with span("Rollout/action_fetch"):  # the one fetch of the step: it waits for the step
                    results_np = np.asarray(jax.device_get(results))
                env_act_np, logprob_np, value_np = results_np[:, :-2], results_np[:, -2], results_np[:, -1]
                if is_continuous:
                    low, high = act_space.low, act_space.high
                    env_actions = np.clip(env_act_np, low, high) if np.isfinite(low).all() else env_act_np
                else:
                    env_act_np = env_act_np.astype(np.int32)  # ids come back in the float32 buffer, exact
                    env_actions = env_act_np[..., 0] if len(actions_dim) == 1 else env_act_np
                with span("Rollout/env_step"):
                    next_obs, reward, terminated, truncated, info = envs.step(env_actions)
                    done = np.logical_or(terminated, truncated)
                    reward = np.asarray(reward, dtype=np.float32).reshape(num_envs)

                # Bootstrap truncated episodes with V(final_obs) under the current
                # recurrent state (reference ppo_recurrent.py:309-335).  Every row goes
                # through the model (nothing is written) and the truncated rows' values are
                # kept; the previous action is the one just taken.
                if truncated.any() and "final_obs" in info:
                    with span("Rollout/truncation_value"):  # a device call over all rows and a fetch that waits for it
                        trunc_idx = np.nonzero(truncated)[0]
                        final_obs = {k: np.array(next_obs[k]) for k in obs_keys}
                        for k in obs_keys:
                            final_obs[k][trunc_idx] = np.stack([np.asarray(info["final_obs"][i][k]) for i in trunc_idx])
                        taken = _onehot_actions(env_act_np, actions_dim, is_continuous, as_ids=is_decoder)
                        v_final = value_fn(act_params, *inputs.host(final_obs, taken, not_first), carry[0])
                        reward[trunc_idx] += gamma * np.asarray(jax.device_get(v_final))[trunc_idx]

                with span("Rollout/store"):  # the row, and what the next acting call reads of this step
                    for k in obs_keys:
                        step_data[k] = obs[k][None]
                    step_data["actions"] = env_act_np.reshape(num_envs, -1).astype(np.float32)[None]
                    step_data["prev_actions"] = prev_stored[None].copy()
                    step_data["is_first"] = is_first_np[None].copy()
                    step_data["logprobs"] = logprob_np.reshape(num_envs, 1)[None]
                    step_data["values"] = value_np.reshape(num_envs, 1)[None]
                    step_data["rewards"] = reward.reshape(num_envs, 1)[None]
                    step_data["dones"] = done.astype(np.float32).reshape(num_envs, 1)[None]
                    rb.add(step_data, validate_args=cfg.buffer.validate_args)

                    prev_stored = _onehot_actions(env_act_np, actions_dim, is_continuous, as_ids=is_decoder)
                    prev_stored[done] = 0
                    is_first_np = done.astype(np.float32).reshape(num_envs, 1)
                    obs = host_obs(next_obs)
                    policy_step += num_envs * world
                    record_episode_stats(aggregator, info)
        env_time = time.perf_counter() - env_t0

        with span("Time/update_prep"):  # the rollout's rows, the bootstrap and the advantages, the update's key
            local = rb.to_tensor()
            next_value = value_fn(act_params, *inputs.host(obs, prev_stored, is_first_np), carry[0])
            act_params = None  # the decoder's copy goes before the update needs the room
            returns, advantages = gae_fn(local["rewards"], local["values"], local["dones"], next_value[:, None])
            seq_data = {
                **{k: local[k] for k in obs_keys},
                "actions": local["actions"],
                "prev_actions": local["prev_actions"],
                "is_first": local["is_first"],
                "logprobs": local["logprobs"][..., 0],
                "values": local["values"][..., 0],
                "returns": returns[..., 0],
                "advantages": advantages[..., 0],
            }

            clip_coef = cfg.algo.clip_coef
            ent_coef = cfg.algo.ent_coef
            if cfg.algo.anneal_clip_coef:
                clip_coef = polynomial_decay(update, initial=clip_coef, final=0.0, max_decay_steps=num_updates)
            if cfg.algo.anneal_ent_coef:
                ent_coef = polynomial_decay(update, initial=ent_coef, final=0.0, max_decay_steps=num_updates)

            key = ctx.rng()
            if recorder is not None:  # device-array references only: no host sync
                recorder.stage_step(
                    batch=seq_data,
                    # the decoder's update is given its parameters and moments to overwrite: no reference to them survives it
                    carry={} if is_decoder else {"params": params, "opt_state": opt_state, "state0": state0},
                    key=key,
                    scalars={"clip_coef": float(clip_coef), "ent_coef": float(ent_coef), "update": update},
                )
        with timer("Time/train_time"), monitor.phase("dispatch"):
            t0 = time.perf_counter()
            with span("Time/update_call"):
                params, opt_state, train_metrics = train_fn(
                    params, opt_state, seq_data, state0, key, clip_coef, ent_coef
                )
            with span("Time/update_fetch"):  # waits for the update
                train_metrics = jax.device_get(train_metrics)
            train_time = time.perf_counter() - t0
        with span("Time/update_after"):
            assert_finite(cfg, train_metrics, "ppo_recurrent/update")
            for k, v in train_metrics.items():
                aggregator.update(k, float(v))

            if logger is not None and (policy_step - last_log >= cfg.metric.log_every or update == num_updates or cfg.dry_run):
                metrics = aggregator.compute()
                metrics["Time/sps_train"] = (
                    cfg.algo.update_epochs * num_batches / train_time if train_time > 0 else 0.0
                )
                metrics["Time/sps_env_interaction"] = policy_steps_per_iter / world / env_time if env_time > 0 else 0.0
                monitor.log_metrics(logger, metrics, policy_step)
                aggregator.reset()
                last_log = policy_step

            def save_ckpt():
                nonlocal last_checkpoint
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
    if logger is not None:
        logger.close()


def test(agent, params, ctx, cfg, log_dir: str, greedy: bool = True) -> float:
    """Greedy single-env evaluation with carried LSTM state."""
    from sheeprl_tpu.utils.env import make_env

    env = make_env(cfg, cfg.seed, 0, log_dir, "test")()
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    is_decoder = isinstance(agent, DecoderPPOAgent)

    @jax.jit
    def policy(p, obs, prev_actions, is_first, state, key):
        actor_out, _, new_state = agent_step(agent, p, obs, prev_actions, is_first, state)
        env_act, _, _ = sample_actions(key, actor_out, agent.is_continuous, greedy=greedy)
        return env_act, new_state

    obs, _ = env.reset(seed=cfg.seed)
    state = make_zero_state(cfg, ctx.compute_dtype)(1)
    prev = np.zeros((1, 1), np.int32) if is_decoder else np.zeros((1, int(sum(agent.action_dims))), np.float32)
    is_first = np.ones((1, 1), dtype=np.float32)
    done, cum_reward = False, 0.0
    while not done:
        obs_t = prepare_obs({k: np.asarray(v)[None] for k, v in obs.items()}, cnn_keys, mlp_keys)
        act, state = policy(params, obs_t, jnp.asarray(prev), jnp.asarray(is_first), state, ctx.rng())
        act_np = np.asarray(jax.device_get(act))
        prev = _onehot_actions(act_np, agent.action_dims, agent.is_continuous, as_ids=is_decoder)
        is_first = np.zeros((1, 1), dtype=np.float32)
        if agent.is_continuous:
            env_action = act_np[0]
        elif len(agent.action_dims) == 1:
            env_action = int(act_np[0, 0])
        else:
            env_action = act_np[0]
        obs, reward, terminated, truncated, _ = env.step(env_action)
        done = bool(terminated or truncated)
        cum_reward += float(reward)
    env.close()
    return cum_reward


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): the jitted BPTT
    update at tiny synthetic shapes, through ``make_ppo_recurrent_train_fn``."""
    import gymnasium as gym

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
            "exp=ppo_recurrent",
            "env=discrete_dummy",
            "algo.mlp_keys.encoder=[state]",
            "algo.rollout_steps=4",
            "algo.per_rank_num_batches=2",
            "algo.update_epochs=1",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.encoder.mlp_features_dim=8",
            "algo.rnn.lstm.hidden_size=8",
            "env.num_envs=2",
        ]
    )
    ctx = tiny_ctx(cfg)
    obs_space = vector_space()
    act_space = discrete_act_space()
    agent, params = build_agent(ctx, act_space, obs_space, cfg)
    opt, train_fn = make_ppo_recurrent_train_fn(ctx, agent, cfg, ["state"])
    opt_state = opt.init(params)
    T, N = int(cfg.algo.rollout_steps), int(cfg.env.num_envs)
    act_sum = int(sum(agent.action_dims))
    hidden = int(cfg.algo.rnn.lstm.hidden_size)
    seq_data = {
        "state": zeros((T, N, 5)),
        "actions": zeros((T, N, 1)),
        "prev_actions": zeros((T, N, act_sum)),
        "is_first": zeros((T, N, 1)),
        "logprobs": zeros((T, N)),
        "values": zeros((T, N)),
        "returns": zeros((T, N)),
        "advantages": zeros((T, N)),
    }
    entries = [
        AuditEntry(
            name="ppo_recurrent/train_fn",
            fn=train_fn,
            args=(params, opt_state, seq_data, (zeros((N, hidden)), zeros((N, hidden))), jax.random.PRNGKey(0), 0.2, 0.0),
            covers=("ppo_recurrent",),
            precision=str(cfg.mesh.precision),
        )
    ]

    # the decoder variant's update: two layers (one of each kind), two of four experts held
    cfg = compose_tiny(
        [
            "exp=ppo_recurrent_decoder",
            "algo.rollout_steps=4",
            "algo.update_epochs=1",
            "algo.decoder.hidden_size=16",
            "algo.decoder.head_dim=8",
            "algo.decoder.heads_held=2",
            "algo.decoder.kv_heads_held=1",
            "algo.decoder.moe_num_primary_experts=4",
            "algo.decoder.experts_held=2",
            "algo.decoder.moe_ffn_hidden_size=8",
            "algo.decoder.vocab_held=16",
            "algo.decoder.layers=2",
            "algo.decoder.sliding_window_size=4",
            "algo.decoder.cache_capacity=8",
            "env.num_envs=2",
        ]
    )
    ctx = tiny_ctx(cfg)
    V = int(cfg.algo.decoder.vocab_held)
    agent, params = build_agent(
        ctx, gym.spaces.Discrete(V), gym.spaces.Dict({"token": gym.spaces.Box(0, V - 1, (1,), np.int32)}), cfg
    )
    opt, train_fn = make_ppo_recurrent_train_fn(ctx, agent, cfg, ["token"])
    T, N = int(cfg.algo.rollout_steps), int(cfg.env.num_envs)
    seq_data = {
        "token": zeros((T, N, 1)),
        "actions": zeros((T, N, 1)),
        "prev_actions": zeros((T, N, 1), jnp.int32),
        "is_first": zeros((T, N, 1)),
        "logprobs": zeros((T, N)),
        "values": zeros((T, N)),
        "returns": zeros((T, N)),
        "advantages": zeros((T, N)),
    }
    entries.append(
        AuditEntry(
            name="ppo_recurrent/train_fn_decoder",
            fn=train_fn,
            args=(params, opt.init(params), seq_data, make_zero_state(cfg)(N), jax.random.PRNGKey(0), 0.2, 0.0),
            covers=("ppo_recurrent",),
            precision=str(cfg.mesh.precision),
        )
    )
    return entries
