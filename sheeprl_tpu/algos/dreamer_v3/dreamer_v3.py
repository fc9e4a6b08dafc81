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

import jax
import jax.numpy as jnp
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop
from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, build_agent, make_player_step
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import AGGREGATOR_KEYS, init_moments, update_moments
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.obs import flight_recorder
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.obs.perf import note, scope
from sheeprl_tpu.ops.scan_wgrad import dense_scan
from sheeprl_tpu.utils.registry import register_algorithm


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


def block_step_of(train_step):
    """``train_step`` as the dispatcher's per-step closure over the carry
    ``(params, opt_states, moments)`` (``utils/blocks.py``)."""

    def _block_step(carry, batch, key, update_target):
        params, opt_states, moments = carry
        params, opt_states, moments, metrics = train_step(
            params, opt_states, moments, batch, key, update_target
        )
        return (params, opt_states, moments), metrics

    return _block_step


@register_algorithm(name="dreamer_v3")
def main(ctx, cfg) -> None:
    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        cnn_keys = list(cfg.algo.cnn_keys.encoder)
        mlp_keys = list(cfg.algo.mlp_keys.encoder)
        world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
        train_step, init_opt_states = make_train_step(
            world_model, actor, critic, cfg, cnn_keys, mlp_keys, {k: obs_space[k].shape for k in cnn_keys + mlp_keys}
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
        return Entry(
            carry=(params, opt_states, ctx.replicate(init_moments())),
            ckpt_names=("params", "opt_states", "moments"),
            block_step=block_step_of(train_step),
            dispatcher_kwargs=dict(target_update_freq=cfg.algo.critic.per_rank_target_network_update_freq),
            player_step=make_player_step(world_model, actor, actions_dim, cfg.algo.world_model.discrete_size),
            stochastic_size=cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size,
            aggregator_keys=AGGREGATOR_KEYS,
            place=ctx.shard_params,
        )

    # ``build_agent`` and ``make_device_replay`` are this module's globals, read when
    # ``main`` runs: what stands there then is what the run uses.
    run_loop(ctx, cfg, setup, make_device_replay=make_device_replay)


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

    block = make_train_block(block_step_of(train_step), bk.get("target_update_freq", 1), bk.get("count_offset", 1))
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

    block = make_train_block(block_step_of(train_step), cfg.algo.critic.per_rank_target_network_update_freq, 1)
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
