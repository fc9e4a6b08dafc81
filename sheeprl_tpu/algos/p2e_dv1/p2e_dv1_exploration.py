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

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop
from sheeprl_tpu.algos.dreamer_v1.agent import WorldModelV1
from sheeprl_tpu.algos.dreamer_v1.dreamer_v1 import block_step_of
from sheeprl_tpu.algos.dreamer_v1.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v2.agent import exploration_schedule
from sheeprl_tpu.algos.p2e import ensemble_loss_normal, exploring_actor_view, intrinsic_reward
from sheeprl_tpu.algos.p2e_dv1.agent import build_agent, make_player_step
from sheeprl_tpu.algos.p2e_dv1.utils import AGGREGATOR_KEYS, compute_lambda_values
from sheeprl_tpu.algos.ppo.ppo import make_optimizer
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.distributions import BernoulliSafeMode, Independent, Normal
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.utils.registry import register_algorithm


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
    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
            ctx, actions_dim, is_continuous, cfg, obs_space
        )
        train_step, init_opt_states = make_train_step(
            world_model, actor, critic, ensemble_mlp, cfg, list(cfg.algo.cnn_keys.encoder), list(cfg.algo.mlp_keys.encoder)
        )
        return Entry(
            carry=(params, ctx.replicate(init_opt_states(params))),
            ckpt_names=("params", "opt_states"),
            block_step=block_step_of(train_step),
            dispatcher_kwargs={},
            player_step=make_player_step(world_model, actor, actions_dim, is_continuous),
            player_params=exploring_actor_view(cfg),
            stochastic_size=cfg.algo.world_model.stochastic_size,
            aggregator_keys=AGGREGATOR_KEYS,
            place=ctx.replicate,
            exploration_amount=exploration_schedule(cfg),
            clip_reward=np.tanh,
        )

    run_loop(ctx, cfg, setup, make_device_replay=make_device_replay)


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

    block = make_train_block(block_step_of(train_step), 1, 1)
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
