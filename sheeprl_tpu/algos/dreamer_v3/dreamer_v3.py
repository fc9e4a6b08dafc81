"""DreamerV3 training loop (reference: ``/root/reference/sheeprl/algos/dreamer_v3/dreamer_v3.py``).

TPU-first structure — the reference's hot loops (SURVEY §3.1) become scans inside ONE
jitted ``train_step``:

* the 64-step RSSM unroll (reference python loop ``dreamer_v3.py:134-145``) is a
  ``lax.scan`` inside the world-model loss;
* the 15-step imagination rollout (``:235-241``) is a ``lax.scan`` inside the actor
  loss (differentiable through the dynamics for the continuous/backprop objective);
* world-model, actor and critic optimizer steps + the EMA target-critic update +
  the ``Moments`` percentile-normalizer update all run in the same jit;
* gradient sync over the ``data`` mesh axis is GSPMD-inserted (batch sharded, params
  replicated, losses are global means) — no explicit collectives.

Environment interaction timeline matches the reference exactly
(``dreamer_v3.py:82-91``): ``obs[t]`` precedes ``action[t]``; stored actions are
shifted right by one inside the train step with a zero first action."""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer_v3.agent import (
    PlayerState,
    WorldModel,
    build_agent,
    make_player_step,
    parse_actions_dim,
)
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    AGGREGATOR_KEYS,
    init_moments,
    prepare_obs,
    test,
    update_moments,
)
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.fault.guard import TrainingGuard
from sheeprl_tpu.config.core import save_config
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.obs import TrainingMonitor, flight_recorder
from sheeprl_tpu.obs.health import diagnostics, health_enabled, replay_age_metrics
from sheeprl_tpu.obs.perf import note, scope
from sheeprl_tpu.ops.scan_wgrad import dense_scan
from sheeprl_tpu.rollout import PipelinedPlayer, rollout_metrics
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, make_aggregator, record_episode_stats
from sheeprl_tpu.utils.packed import pack
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio


def rssm_unroll(world_model, wm_params, embed, actions, is_first, key):
    """The RSSM over a ``[T, B]`` batch of embeddings, (shifted) actions and ``is_first``
    flags: ``(posteriors, recurrent states, posterior logits, prior logits)``.

    The scan's steps apply the RSSM alone, 88 M of XL's kernels to 16 rows each:
    ``dense_scan`` forms those kernels' gradients once after the backward loop, not in
    its carry (``ops/scan_wgrad.py``), and what it deferred is noted to the perf plane.
    unroll: the per-step GRU work is tiny at batch B, so amortising the loop structure
    over several steps keeps the MXU fed."""
    T, B = embed.shape[:2]
    rec_size = world_model.recurrent_state_size
    rssm_vars = {"params": {"rssm": wm_params["params"]["rssm"]}}
    if world_model.decoupled_rssm:
        # DecoupledRSSM (reference agent.py:501-593): q(z|o) has no recurrent
        # dependency, so the WHOLE posterior batch is one vectorized call and
        # only the prior chain runs in the scan.
        k_repr, k_scan = jax.random.split(key)
        post_logits, post_samples = world_model.apply(
            wm_params, embed, k_repr, method=WorldModel.representation_from_embed
        )
        posts = post_samples.reshape(T, B, -1)
        prev_posts = jnp.concatenate([jnp.zeros_like(posts[:1]), posts[:-1]], 0)

        def step(rssm_vars, rec, x):
            prev_post, action, first, k = x
            rec, _, prior_logits = world_model.apply(
                rssm_vars, prev_post, rec, action, first, k, method=WorldModel.dynamic
            )
            return rec, (rec, prior_logits)

        xs = (prev_posts, actions, is_first, jax.random.split(k_scan, T))
        _, (recs, prior_logits), deferred = dense_scan(step, rssm_vars, jnp.zeros((B, rec_size)), xs, unroll=8)
    else:

        def step(rssm_vars, carry, x):
            post, rec = carry
            action, emb, first, k = x
            rec, post, _, post_logits, prior_logits = world_model.apply(
                rssm_vars, post, rec, action, emb, first, k, method=WorldModel.dynamic
            )
            return (post, rec), (rec, post, post_logits, prior_logits)

        init = (jnp.zeros((B, world_model.stochastic_size * world_model.discrete_size)), jnp.zeros((B, rec_size)))
        xs = (actions, embed, is_first, jax.random.split(key, T))
        _, (recs, posts, post_logits, prior_logits), deferred = dense_scan(step, rssm_vars, init, xs, unroll=8)
    note("deferred_wgrad", {"kernels": len(deferred), "parameters": sum(i * o for i, o in deferred.values())})
    return posts, recs, post_logits, prior_logits


def make_train_step(world_model, actor, critic, cfg, cnn_keys, mlp_keys, obs_shapes):
    """Build the single-jit train step closure."""
    wm_cfg = cfg.algo.world_model
    stoch = wm_cfg.stochastic_size
    discrete = wm_cfg.discrete_size
    stoch_size = stoch * discrete
    rec_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    gamma = cfg.algo.gamma
    lmbda = cfg.algo.lmbda
    ent_coef = cfg.algo.actor.ent_coef
    is_continuous = actor.is_continuous
    actions_dim = tuple(actor.actions_dim)
    tau = cfg.algo.critic.tau
    moments_cfg = cfg.algo.actor.moments

    wm_opt = make_optimizer(wm_cfg.optimizer, wm_cfg.clip_gradients)
    actor_opt = make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_opt = make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)

    def init_opt_states(params):
        return {
            "world_model": wm_opt.init(params["world_model"]),
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
        }

    def train_step(params, opt_states, moments_state, data, key, update_target: bool):
        T, B = data["rewards"].shape[:2]
        k_wm, k_img, k_a0 = jax.random.split(key, 3)

        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        is_first = data["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)

        # ------------------------------------------------ world model update
        def wm_loss_fn(wm_params):
            with scope("world_model/encoder"):
                embed = world_model.apply(wm_params, batch_obs, method=WorldModel.encode)  # [T,B,E]
            with scope("world_model/rssm"):
                posts, recs, post_logits, prior_logits = rssm_unroll(
                    world_model, wm_params, embed, batch_actions, is_first, k_wm
                )
            latents = jnp.concatenate([posts, recs], -1)  # [T,B,L]
            with scope("world_model/heads"):
                recon = world_model.apply(wm_params, latents, method=WorldModel.decode)
                reward_logits = world_model.apply(wm_params, latents, method=WorldModel.reward)
                continue_logits = world_model.apply(wm_params, latents, method=WorldModel.continues)
            with scope("world_model/loss"):
                rec_loss, metrics = wm_loss(recon, reward_logits, continue_logits, post_logits, prior_logits)
            return rec_loss, (posts, recs, metrics)

        def wm_loss(recon, reward_logits, continue_logits, post_logits, prior_logits):
            obs_lp = 0.0
            for k in cnn_keys:
                target = data[k].astype(jnp.float32) / 255.0 - 0.5
                target = target.reshape(T, B, -1, *target.shape[-2:])
                obs_lp = obs_lp + MSEDistribution(recon[k], dims=3).log_prob(target)
            for k in mlp_keys:
                obs_lp = obs_lp + SymlogDistribution(recon[k], dims=1).log_prob(data[k])

            reward_lp = TwoHotEncodingDistribution(reward_logits, dims=1).log_prob(data["rewards"])
            continue_lp = Independent(BernoulliSafeMode(continue_logits), 1).log_prob(1.0 - data["terminated"])

            post_logits_s = post_logits.reshape(T, B, stoch, discrete)
            prior_logits_s = prior_logits.reshape(T, B, stoch, discrete)
            rec_loss, metrics = reconstruction_loss(
                obs_lp,
                reward_lp,
                prior_logits_s,
                post_logits_s,
                wm_cfg.kl_dynamic,
                wm_cfg.kl_representation,
                wm_cfg.kl_free_nats,
                wm_cfg.kl_regularizer,
                continue_lp,
                wm_cfg.continue_scale_factor,
            )
            metrics["State/post_entropy"] = (
                Independent(OneHotCategorical(post_logits_s), 1).entropy().mean()
            )
            metrics["State/prior_entropy"] = (
                Independent(OneHotCategorical(prior_logits_s), 1).entropy().mean()
            )
            return rec_loss, metrics

        (rec_loss, (posts, recs, wm_metrics)), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            params["world_model"]
        )
        with scope("wm_optimizer"):
            wm_updates, new_wm_opt = wm_opt.update(wm_grads, opt_states["world_model"], params["world_model"])
            new_wm_params = optax.apply_updates(params["world_model"], wm_updates)

        # ------------------------------------------------ imagination + actor
        latent0 = jax.lax.stop_gradient(jnp.concatenate([posts, recs], -1)).reshape(T * B, -1)
        prior0 = jax.lax.stop_gradient(posts).reshape(T * B, stoch_size)
        rec0 = jax.lax.stop_gradient(recs).reshape(T * B, rec_size)
        true_continue0 = (1.0 - data["terminated"]).reshape(T * B, 1)

        def actor_loss_fn(actor_params):
            with scope("imagination"):
                traj, imagined_actions = imagine(actor_params)
            with scope("actor"):
                return actor_loss(actor_params, traj, imagined_actions)

        def imagine(actor_params):
            a0_tuple, _ = actor.apply(actor_params, latent0, k_a0)
            a0 = jnp.concatenate(a0_tuple, -1)

            def img_step(carry, k):
                prior, rec, action = carry
                k_dyn, k_act = jax.random.split(k)
                prior, rec = world_model.apply(new_wm_params, prior, rec, action, k_dyn, method=WorldModel.imagination)
                latent = jnp.concatenate([prior, rec], -1)
                acts, _ = actor.apply(actor_params, jax.lax.stop_gradient(latent), k_act)
                action = jnp.concatenate(acts, -1)
                return (prior, rec, action), (latent, action)

            keys = jax.random.split(k_img, horizon)
            _, (latents_img, actions_img) = jax.lax.scan(img_step, (prior0, rec0, a0), keys, unroll=5)
            traj = jnp.concatenate([latent0[None], latents_img], 0)  # [H+1, TB, L]
            imagined_actions = jnp.concatenate([a0[None], actions_img], 0)  # [H+1, TB, A]
            return traj, imagined_actions

        def actor_loss(actor_params, traj, imagined_actions):
            values = TwoHotEncodingDistribution(critic.apply(params["critic"], traj), dims=1).mean
            rewards_img = TwoHotEncodingDistribution(
                world_model.apply(new_wm_params, traj, method=WorldModel.reward), dims=1
            ).mean
            continues = BernoulliSafeMode(
                world_model.apply(new_wm_params, traj, method=WorldModel.continues)
            ).mode  # [H+1, TB, 1]
            continues = jnp.concatenate([true_continue0[None], continues[1:]], 0)

            # λ-returns over the imagined trajectory (reference utils.py:66-77).
            interm = rewards_img[1:] + continues[1:] * gamma * values[1:] * (1 - lmbda)

            def lam_step(carry, x):
                it, ct = x
                carry = it + ct * gamma * lmbda * carry
                return carry, carry

            _, lambda_values = jax.lax.scan(
                lam_step, values[-1], (interm, continues[1:]), reverse=True, unroll=8
            )

            discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, 0) / gamma)

            offset, invscale, new_moments = update_moments(
                moments_state,
                lambda_values,
                decay=moments_cfg.decay,
                max_=moments_cfg.max,
                percentile_low=moments_cfg.percentile.low,
                percentile_high=moments_cfg.percentile.high,
            )
            normed_lambda = (lambda_values - offset) / invscale
            normed_baseline = (values[:-1] - offset) / invscale
            advantage = normed_lambda - normed_baseline

            _, dists = actor.apply(actor_params, jax.lax.stop_gradient(traj), None)
            if is_continuous:
                objective = advantage
                entropy = ent_coef * dists[0].entropy().sum(-1)
            else:
                logpis = []
                offset_a = 0
                for i, d in enumerate(dists):
                    act_i = jax.lax.stop_gradient(imagined_actions[..., offset_a : offset_a + actions_dim[i]])
                    logpis.append(d.log_prob(act_i)[:-1])
                    offset_a += actions_dim[i]
                objective = sum(logpis)[..., None] * jax.lax.stop_gradient(advantage)
                entropy = ent_coef * sum(d.entropy() for d in dists)
            policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1][..., None]))
            aux = {
                "traj": jax.lax.stop_gradient(traj),
                "lambda_values": jax.lax.stop_gradient(lambda_values),
                "discount": discount,
                "moments": new_moments,
            }
            return policy_loss, aux

        (policy_loss, actor_aux), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        with scope("actor_optimizer"):
            actor_updates, new_actor_opt = actor_opt.update(actor_grads, opt_states["actor"], params["actor"])
            new_actor_params = optax.apply_updates(params["actor"], actor_updates)

        # ------------------------------------------------ critic
        traj = actor_aux["traj"]
        lambda_values = actor_aux["lambda_values"]
        discount = actor_aux["discount"]

        def critic_loss_fn(critic_params):
            qv = TwoHotEncodingDistribution(critic.apply(critic_params, traj[:-1]), dims=1)
            target_values = TwoHotEncodingDistribution(
                critic.apply(params["target_critic"], traj[:-1]), dims=1
            ).mean
            loss = -qv.log_prob(lambda_values) - qv.log_prob(jax.lax.stop_gradient(target_values))
            return jnp.mean(loss * discount[:-1][..., 0])

        with scope("critic"):
            value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        with scope("critic_optimizer"):
            critic_updates, new_critic_opt = critic_opt.update(critic_grads, opt_states["critic"], params["critic"])
            new_critic_params = optax.apply_updates(params["critic"], critic_updates)

        # EMA target critic (reference dreamer_v3.py:674-680).
        with scope("target_ema"):
            new_target = jax.lax.cond(
                update_target,
                lambda: jax.tree.map(
                    lambda tp, cp: (1 - tau) * tp + tau * cp, params["target_critic"], new_critic_params
                ),
                lambda: params["target_critic"],
            )

        new_params = {
            "world_model": new_wm_params,
            "actor": new_actor_params,
            "critic": new_critic_params,
            "target_critic": new_target,
        }
        new_opt_states = {"world_model": new_wm_opt, "actor": new_actor_opt, "critic": new_critic_opt}
        metrics = dict(wm_metrics)
        metrics["Loss/policy_loss"] = policy_loss
        metrics["Loss/value_loss"] = value_loss
        # default-on instrumentation under one name, so that a capture prices it
        with scope("health"):
            metrics["Grads/world_model"] = optax.global_norm(wm_grads)
            metrics["Grads/actor"] = optax.global_norm(actor_grads)
            metrics["Grads/critic"] = optax.global_norm(critic_grads)
            if health_enabled(cfg):  # trace-time constant (obs/health.py)
                metrics.update(
                    diagnostics(
                        grads={"world_model": wm_grads, "actor": actor_grads, "critic": critic_grads},
                        params=new_params,
                        updates={"world_model": wm_updates, "actor": actor_updates, "critic": critic_updates},
                        aux={"critic_value_mean": lambda_values.mean(), "critic_value_std": lambda_values.std()},
                    )
                )
            metrics = maybe_inject_nonfinite(cfg, metrics)
            if strict_enabled(cfg):  # trace-time constant: callback exists only in strict runs
                nan_scan(metrics, "dreamer_v3/train_step")
        return new_params, new_opt_states, actor_aux["moments"], metrics

    return train_step, init_opt_states


@register_algorithm(name="dreamer_v3")
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

    world_model, actor, critic, params, latent_size = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
    train_step, init_opt_states = make_train_step(
        world_model, actor, critic, cfg, cnn_keys, mlp_keys, {k: obs_space[k].shape for k in obs_keys}
    )
    # Flight recorder: replay_update rebuilds this exact train step from the dump.
    recorder = flight_recorder.get_active()
    if recorder is not None:
        recorder.arm_replay(
            "sheeprl_tpu.algos.dreamer_v3.dreamer_v3:replay_update",
            obs_space=obs_space,
            actions_dim=tuple(int(d) for d in actions_dim),
            is_continuous=bool(is_continuous),
        )
    # opt states mirror the params' (possibly tensor-parallel) placement
    opt_states = ctx.shard_params(init_opt_states(params))
    moments_state = ctx.replicate(init_moments())
    target_update_freq = cfg.algo.critic.per_rank_target_network_update_freq

    # The whole iteration's gradient steps run as ONE jitted scan (utils/blocks.py):
    # one dispatch per iteration, per-step keys split inside the jit, target-critic
    # cadence computed from the running step count.
    def _block_step(carry, batch, key, update_target):
        params, opt_states, moments = carry
        params, opt_states, moments, metrics = train_step(
            params, opt_states, moments, batch, key, update_target
        )
        return (params, opt_states, moments), metrics

    # Device-resident replay (buffer.device): rows live in HBM, the host ships only
    # (env, start) indices, and each scan step gathers its batch in-jit — removes
    # the host→device batch traffic that otherwise floors e2e throughput.  Under
    # data parallelism the ring's env axis is sharded over the `data` mesh axis
    # (per-shard sampling + shard_map gather); multi-process runs keep the fast
    # path too via per-process local rings + a zero-copy global view
    # (data/device_buffer.py: MultiProcessDeviceReplayMirror).

    # The player takes the loop's packed carry (below) and unpacks inside its own jit:
    # it reads only the rows of the parameters it uses.
    player_step = make_player_step(world_model, actor, actions_dim, cfg.algo.world_model.discrete_size)
    player_jit = jax.jit(
        lambda carry, *args, **kwargs: player_step(carry.unpack()[0], *args, **kwargs), static_argnames=("greedy",)
    )
    stoch_size = cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size

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
    # buffer.device=True on a single chip, async host prefetch otherwise.
    dispatcher, mirror, prefetcher, _run_block, rb_add = make_device_replay(
        ctx,
        cfg,
        rb,
        cnn_keys,
        mlp_keys,
        obs_space,
        act_dim_sum,
        _block_step,
        dispatcher_kwargs=dict(target_update_freq=target_update_freq),
    )

    # rank-independent (cross-process gathering) when multi-host
    aggregator = make_aggregator(cfg.metric.aggregator.get("metrics", {}))
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

    start_iter = 1
    policy_step = 0
    last_log = 0
    last_checkpoint = 0
    cumulative_grad_steps = 0
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from,
            templates={
                "params": jax.device_get(params),
                "opt_states": jax.device_get(opt_states),
                "moments": jax.device_get(moments_state),
            },
        )
        params = ctx.shard_params(state["params"])
        opt_states = ctx.shard_params(state["opt_states"])
        moments_state = ctx.replicate(state["moments"])
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
    # From here on the train state is one ``Packed`` (utils/packed.py): the hundreds of
    # small leaves stacked into one buffer a shape and dtype, the large ones as they are,
    # so the block's call hands back 150 buffers at XL and not 552 (each costs the host
    # ~48 us on a v5e).  The block and the player see the tree inside their jits; the
    # checkpoint on disk keeps the tree's format.
    carry = pack((params, opt_states, moments_state))
    del params, opt_states, moments_state

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

    # Acting pipeline (sheeprl_tpu/rollout): depth 0 is the historical synchronous
    # dispatch -> one device_get -> env.step path, bit-for-bit; depth>=1 overlaps
    # the policy jit and the action fetch with the workers' env step (policy lag).
    def _pipeline_policy(cur_obs):
        nonlocal player_state
        obs_t = prepare_obs(cur_obs, cnn_keys, mlp_keys, num_envs)
        actions, stored, player_state = player_jit(
            carry, player_state, obs_t, jnp.asarray(is_first_np), ctx.local_rng()
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

    try:
        for iter_num in range(start_iter, num_iters + 1):
            monitor.advance()
            env_time = 0.0
            env_t0 = time.perf_counter()
            with timer("Time/env_interaction_time"), monitor.phase("player"):
                if iter_num <= learning_starts and not cfg.checkpoint.get("resume_from"):
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
                    reward = np.clip(reward, -1, 1)
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
            def save_ckpt():
                nonlocal last_checkpoint
                # the tree on the host, from one fetch of the packed buffers (process 0 writes it)
                params, opt_states, moments_state = (
                    jax.device_get(carry).unpack() if ctx.is_global_zero else (None, None, None)
                )
                state = {
                    "params": params,
                    "opt_states": opt_states,
                    "moments": moments_state,
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
        reward = test(player_step, carry[0], player_state_init, ctx, cfg, log_dir)
        if logger is not None:
            logger.log_metrics({"Test/cumulative_reward": reward}, policy_step)
    if not cfg.get("model_manager", {}).get("disabled", True) and ctx.is_global_zero:
        from sheeprl_tpu.utils.model_manager import maybe_register_models

        maybe_register_models(cfg, log_dir)
    if logger is not None:
        logger.close()


def replay_update(cfg, dump_dir):
    """Flight-recorder replay builder: re-execute the dumped DreamerV3 gradient
    block on CPU — the same ``make_train_block`` chunking the dispatcher used, fed
    the dumped per-step batches, carry and base key, so the re-execution is
    bit-equivalent to the crashed dispatch."""
    from sheeprl_tpu.obs import replay_blackbox
    from sheeprl_tpu.parallel.mesh import make_mesh_context
    from sheeprl_tpu.utils.blocks import chunk_sizes, make_train_block

    ctx = make_mesh_context(cfg)
    raw = replay_blackbox.load_state(dump_dir)
    statics = raw["statics"]
    obs_space = statics["obs_space"]
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    world_model, actor, critic, params0, _ = build_agent(
        ctx, tuple(statics["actions_dim"]), statics["is_continuous"], cfg, obs_space
    )
    train_step, init_opt_states = make_train_step(
        world_model, actor, critic, cfg, cnn_keys, mlp_keys, {k: obs_space[k].shape for k in obs_keys}
    )
    carry0 = (params0, init_opt_states(params0), init_moments())
    state = replay_blackbox.load_state(dump_dir, templates={"carry": jax.device_get(carry0)})
    batches = replay_blackbox.as_step_list(state["batches"])
    bk = dict(statics.get("block_kwargs") or {})

    def _block_step(carry, batch, key, update_target):
        params, opt_states, moments = carry
        params, opt_states, moments, metrics = train_step(
            params, opt_states, moments, batch, key, update_target
        )
        return (params, opt_states, moments), metrics

    block = make_train_block(_block_step, bk.get("target_update_freq", 1), bk.get("count_offset", 1))
    carry = tuple(state["carry"])
    start_count = int(state["scalars"]["start_count"])
    base_key = jnp.asarray(state["base_key"])
    last_metrics, offset = {}, 0
    for size in chunk_sizes(len(batches), bk.get("max_chunk", 8)):
        chunk = tuple(batches[offset : offset + size])
        offset += size
        carry, metrics = block(carry, chunk, base_key, start_count)
        start_count += size
        last_metrics = jax.device_get(metrics)
    return {"metrics": last_metrics}


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): the DreamerV3
    gradient block — ``make_train_step`` wrapped in the same ``make_train_block``
    scan the dispatcher jits — at tiny MLP-only synthetic shapes."""
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
        ["exp=dreamer_v3_dummy", "env=discrete_dummy", *DREAMER_TINY_OVERRIDES, *DREAMER_DISCRETE_OVERRIDES]
    )
    ctx = tiny_ctx(cfg)
    obs_space = vector_space()
    actions_dim, is_continuous = (3,), False
    world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
    train_step, init_opt_states = make_train_step(
        world_model, actor, critic, cfg, [], ["state"], {"state": obs_space["state"].shape}
    )
    carry = (params, init_opt_states(params), init_moments())

    def _block_step(carry, batch, key, update_target):
        params, opt_states, moments = carry
        params, opt_states, moments, metrics = train_step(
            params, opt_states, moments, batch, key, update_target
        )
        return (params, opt_states, moments), metrics

    block = make_train_block(_block_step, cfg.algo.critic.per_rank_target_network_update_freq, 1)
    batch = sequence_batch(
        {"state": obs_space["state"].shape},
        act_dim=int(sum(actions_dim)),
        T=int(cfg.algo.per_rank_sequence_length),
        B=int(cfg.algo.per_rank_batch_size),
    )
    return [
        AuditEntry(
            name="dreamer_v3/train_block",
            fn=block,
            args=(carry, (batch,), jax.random.PRNGKey(0), 0),
            covers=("dreamer_v3", "p2e_dv3_finetuning"),
            precision=str(cfg.mesh.precision),
        )
    ]
