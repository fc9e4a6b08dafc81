"""DreamerV1 training loop (reference: ``/root/reference/sheeprl/algos/dreamer_v1/dreamer_v1.py``).

Single-jit train step (same scan structure as DV2/DV3); the DV1 specifics:

* continuous Gaussian latents — ELBO with Normal KL + free nats (reference
  ``loss.py:41-95``), no KL balancing, no target critic;
* imagined trajectory EXCLUDES the starting posterior latent (reference
  ``dreamer_v1.py:234-248``); λ-targets per ``dreamer_v1/utils.py:42-78``;
* actor loss = ``-mean(discount · λ-values)`` — pure dynamics backpropagation
  (reference ``loss.py:27-38``);
* ε-exploration noise on the player with the Hafner half-life decay
  (reference ``agent.py:278-300``, config ``expl_amount=0.3``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop
from sheeprl_tpu.algos.dreamer_v1.agent import WorldModelV1, build_agent, make_player_step
from sheeprl_tpu.algos.dreamer_v1.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v1.utils import AGGREGATOR_KEYS, compute_lambda_values
from sheeprl_tpu.algos.dreamer_v2.agent import exploration_schedule
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.utils.registry import register_algorithm


def make_train_step(world_model, actor, critic, cfg, cnn_keys, mlp_keys):
    wm_cfg = cfg.algo.world_model
    stoch_size = wm_cfg.stochastic_size
    rec_size = wm_cfg.recurrent_model.recurrent_state_size
    horizon = cfg.algo.horizon
    gamma = cfg.algo.gamma
    lmbda = cfg.algo.lmbda
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

    def train_step(params, opt_states, data, key):
        T, B = data["rewards"].shape[:2]
        k_wm, k_img, _ = jax.random.split(key, 3)
        sg = jax.lax.stop_gradient

        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        batch_actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)

        # ------------------------------------------------ world model update
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
                Normal(world_model.apply(wm_params, latents, method=WorldModelV1.reward), 1.0), 1
            ).log_prob(data["rewards"])
            continue_lp = None
            if use_continues:
                continue_lp = Independent(
                    BernoulliSafeMode(world_model.apply(wm_params, latents, method=WorldModelV1.continues)), 1
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

        def actor_loss_fn(actor_params):
            def img_step(carry, k):
                prior, rec, latent = carry
                k_act, k_dyn = jax.random.split(k)
                acts, _ = actor.apply(actor_params, sg(latent), k_act)
                action = jnp.concatenate(acts, -1)
                prior, rec = world_model.apply(new_wm_params, prior, rec, action, k_dyn, method=WorldModelV1.imagination)
                new_latent = jnp.concatenate([prior, rec], -1)
                return (prior, rec, new_latent), new_latent

            keys = jax.random.split(k_img, horizon)
            _, traj = jax.lax.scan(img_step, (prior0, rec0, latent0), keys, unroll=5)  # [H, N, L] (no initial latent)

            values = critic.apply(params["critic"], traj)
            rewards_img = world_model.apply(new_wm_params, traj, method=WorldModelV1.reward)
            if use_continues:
                continues = jax.nn.sigmoid(world_model.apply(new_wm_params, traj, method=WorldModelV1.continues))
            else:
                continues = jnp.ones_like(rewards_img) * gamma

            lambda_values = compute_lambda_values(rewards_img, values, continues, lmbda)  # [H-1, N, 1]
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
            )  # [H-1, N, 1]
            policy_loss = -jnp.mean(discount * lambda_values)
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
            return -jnp.mean(discount[..., 0] * qv.log_prob(lambda_values))

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        critic_updates, new_critic_opt = critic_opt.update(critic_grads, opt_states["critic"], params["critic"])
        new_critic_params = optax.apply_updates(params["critic"], critic_updates)

        new_params = {
            "world_model": new_wm_params,
            "actor": new_actor_params,
            "critic": new_critic_params,
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
            nan_scan(metrics, "dreamer_v1/train_step")
        return new_params, new_opt_states, metrics

    return train_step, init_opt_states


def block_step_of(train_step):
    """``train_step`` as the dispatcher's per-step closure over the carry
    ``(params, opt_states)`` (``utils/blocks.py``)."""

    def _block_step(carry, batch, key, update_target):
        del update_target  # DV1 has no target network
        params, opt_states = carry
        params, opt_states, metrics = train_step(params, opt_states, batch, key)
        return (params, opt_states), metrics

    return _block_step


@register_algorithm(name="dreamer_v1")
def main(ctx, cfg) -> None:
    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
        train_step, init_opt_states = make_train_step(
            world_model, actor, critic, cfg, list(cfg.algo.cnn_keys.encoder), list(cfg.algo.mlp_keys.encoder)
        )
        return Entry(
            carry=(params, ctx.replicate(init_opt_states(params))),
            ckpt_names=("params", "opt_states"),
            block_step=block_step_of(train_step),
            dispatcher_kwargs={},
            player_step=make_player_step(world_model, actor, actions_dim, is_continuous),
            stochastic_size=cfg.algo.world_model.stochastic_size,
            aggregator_keys=AGGREGATOR_KEYS,
            place=ctx.replicate,
            exploration_amount=exploration_schedule(cfg),
            clip_reward=np.tanh,
        )

    run_loop(ctx, cfg, setup, make_device_replay=make_device_replay)


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): the DreamerV1
    gradient block (``make_train_step`` in the dispatcher's ``make_train_block``
    scan; DV1 has no target network) at tiny MLP-only synthetic shapes."""
    from sheeprl_tpu.analysis.ir.synth import (
        DREAMER_TINY_OVERRIDES,
        compose_tiny,
        sequence_batch,
        tiny_ctx,
        vector_space,
    )
    from sheeprl_tpu.analysis.ir.types import AuditEntry
    from sheeprl_tpu.utils.blocks import make_train_block

    cfg = compose_tiny(["exp=dreamer_v1_dummy", "env=discrete_dummy", *DREAMER_TINY_OVERRIDES])
    ctx = tiny_ctx(cfg)
    obs_space = vector_space()
    actions_dim, is_continuous = (3,), False
    world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, is_continuous, cfg, obs_space)
    train_step, init_opt_states = make_train_step(world_model, actor, critic, cfg, [], ["state"])
    carry = (params, init_opt_states(params))

    block = make_train_block(block_step_of(train_step), 1, 1)
    batch = sequence_batch(
        {"state": obs_space["state"].shape},
        act_dim=int(sum(actions_dim)),
        T=int(cfg.algo.per_rank_sequence_length),
        B=int(cfg.algo.per_rank_batch_size),
    )
    return [
        AuditEntry(
            name="dreamer_v1/train_block",
            fn=block,
            args=(carry, (batch,), jax.random.PRNGKey(0), 0),
            covers=("dreamer_v1", "p2e_dv1_finetuning"),
            precision=str(cfg.mesh.precision),
        )
    ]
