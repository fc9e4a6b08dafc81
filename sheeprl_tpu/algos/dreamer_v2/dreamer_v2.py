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

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop, sequential_buffer
from sheeprl_tpu.algos.dreamer_v2.agent import WorldModelV2, build_agent, exploration_schedule, make_player_step
from sheeprl_tpu.algos.dreamer_v2.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v2.utils import AGGREGATOR_KEYS, compute_lambda_values
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.data.buffers import EpisodeBuffer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal, OneHotCategorical
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.utils.registry import register_algorithm


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
    buffer_type = str(cfg.buffer.get("type", "sequential")).lower()
    if buffer_type == "sequential":
        return sequential_buffer(cfg, num_envs, obs_keys, log_dir, rank, world)
    if buffer_type == "episode":
        return EpisodeBuffer(
            max(int(cfg.buffer.size) // max(num_envs * world, 1), 1),
            minimum_episode_length=1 if cfg.dry_run else cfg.algo.per_rank_sequence_length,
            n_envs=num_envs,
            obs_keys=obs_keys,
            prioritize_ends=cfg.buffer.get("prioritize_ends", False),
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
        )
    raise ValueError(f"Unrecognized buffer type: must be one of `sequential` or `episode`, received: {buffer_type}")


def block_step_of(train_step):
    """``train_step`` as the dispatcher's per-step closure over the carry
    ``(params, opt_states)`` (``utils/blocks.py``)."""

    def _block_step(carry, batch, key, update_target):
        params, opt_states = carry
        params, opt_states, metrics = train_step(params, opt_states, batch, key, update_target)
        return (params, opt_states), metrics

    return _block_step


@register_algorithm(name="dreamer_v2")
def main(ctx, cfg) -> None:
    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
        train_step, init_opt_states = make_train_step(
            world_model, actor, critic, cfg, list(cfg.algo.cnn_keys.encoder), list(cfg.algo.mlp_keys.encoder)
        )
        return Entry(
            carry=(params, ctx.replicate(init_opt_states(params))),
            ckpt_names=("params", "opt_states"),
            # DV2's hard target copy tests the count BEFORE the increment (fires on the first step)
            block_step=block_step_of(train_step),
            dispatcher_kwargs=dict(
                target_update_freq=cfg.algo.critic.per_rank_target_network_update_freq, count_offset=0
            ),
            player_step=make_player_step(world_model, actor, actions_dim, is_continuous),
            stochastic_size=cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size,
            aggregator_keys=AGGREGATOR_KEYS,
            place=ctx.replicate,
            exploration_amount=exploration_schedule(cfg),
            clip_reward=np.tanh,
            # DV2's episode buffer stays on host
            make_buffer=make_buffer,
            # MineDojo's masked actor plays from the first step
            random_prefill="minedojo" not in str(cfg.env.get("wrapper", {}).get("_target_", "")).lower(),
        )

    run_loop(ctx, cfg, setup, make_device_replay=make_device_replay)


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

    block = make_train_block(block_step_of(train_step), cfg.algo.critic.per_rank_target_network_update_freq, 0)
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
