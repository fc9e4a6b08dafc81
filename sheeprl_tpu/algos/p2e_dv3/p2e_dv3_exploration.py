"""P2E-DV3 exploration (reference: ``/root/reference/sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py``).

Plan2Explore on the DreamerV3 stack, as ONE jitted train step with four phases
(reference ``train``, ``p2e_dv3_exploration.py:41-…``):

1. **Dynamic learning** — the DV3 world-model update, except the reward/continue heads
   train on *detached* latents (reference ``:160,163``);
2. **Ensemble learning** — N vmapped MLPs learn to predict the next stochastic state
   from ``(posterior, recurrent, action)`` (reference ``:205-230``);
3. **Exploration behaviour** — the exploration actor maximises a weighted mix of
   per-critic advantages; intrinsic critics use the ensemble-disagreement reward
   (``next_state_embedding.var(0).mean(-1) × multiplier``, reference ``:270-287``),
   task-reward critics use the learned reward model; each critic has its own Moments
   normaliser and EMA target (reference ``:261-369``);
4. **Task behaviour (zero-shot)** — the standard DV3 actor/critic update on the task
   reward, trained on the exploration data (reference ``:374-…``).

The env-interaction loop is the DV3 one; the player acts with the exploration actor
(``algo.player.actor_type: exploration``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from sheeprl_tpu.analysis.strict import maybe_inject_nonfinite, nan_scan, strict_enabled
from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop
from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, make_player_step
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import block_step_of
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.p2e import ensemble_loss, exploring_actor_view, intrinsic_reward
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.algos.p2e_dv3.utils import AGGREGATOR_KEYS, init_moments, update_moments
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
from sheeprl_tpu.obs.health import diagnostics, health_enabled
from sheeprl_tpu.utils.registry import register_algorithm


def make_train_step(world_model, actor, critic, ensemble_mlp, cfg, cnn_keys, mlp_keys, critic_cfgs):
    """``critic_cfgs``: static ``{name: {"weight", "reward_type"}}`` of the enabled
    exploration critics (config iteration is static under jit)."""
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
    intr_mult = cfg.algo.intrinsic_reward_multiplier
    weights_sum = sum(c["weight"] for c in critic_cfgs.values())

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
            "critics_exploration": {
                k: critic_opt.init(params["critics_exploration"][k]["module"]) for k in critic_cfgs
            },
            "ensembles": ens_opt.init(params["ensembles"]),
        }

    def init_moments_state():
        return {"task": init_moments(), "expl": {k: init_moments() for k in critic_cfgs}}

    def _moments(mstate, lambda_values):
        return update_moments(
            mstate,
            lambda_values,
            decay=moments_cfg.decay,
            max_=moments_cfg.max,
            percentile_low=moments_cfg.percentile.low,
            percentile_high=moments_cfg.percentile.high,
        )

    def _lambda_values(reward, values, continues):
        interm = reward[1:] + continues[1:] * gamma * values[1:] * (1 - lmbda)

        def lam_step(carry, x):
            it, ct = x
            carry = it + ct * gamma * lmbda * carry
            return carry, carry

        _, lv = jax.lax.scan(lam_step, values[-1], (interm, continues[1:]), reverse=True, unroll=8)
        return lv

    def _imagine(actor_params, wm_params, prior0, rec0, latent0, k_img, k_a0):
        """DV3-style imagination rollout returning [H+1] latents + actions."""
        a0_tuple, _ = actor.apply(actor_params, latent0, k_a0)
        a0 = jnp.concatenate(a0_tuple, -1)

        def img_step(carry, k):
            prior, rec, action = carry
            k_dyn, k_act = jax.random.split(k)
            prior, rec = world_model.apply(wm_params, prior, rec, action, k_dyn, method=WorldModel.imagination)
            latent = jnp.concatenate([prior, rec], -1)
            acts, _ = actor.apply(actor_params, jax.lax.stop_gradient(latent), k_act)
            action = jnp.concatenate(acts, -1)
            return (prior, rec, action), (latent, action)

        keys = jax.random.split(k_img, horizon)
        _, (latents_img, actions_img) = jax.lax.scan(img_step, (prior0, rec0, a0), keys, unroll=5)
        traj = jnp.concatenate([latent0[None], latents_img], 0)
        imagined_actions = jnp.concatenate([a0[None], actions_img], 0)
        return traj, imagined_actions

    def _policy_loss(actor_params, traj, imagined_actions, advantage, discount):
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
        return -jnp.mean(discount[:-1] * (objective + entropy[:-1][..., None]))

    def _critic_loss(critic_params, target_params, traj, lambda_values, discount):
        qv = TwoHotEncodingDistribution(critic.apply(critic_params, traj[:-1]), dims=1)
        target_values = TwoHotEncodingDistribution(critic.apply(target_params, traj[:-1]), dims=1).mean
        loss = -qv.log_prob(lambda_values) - qv.log_prob(jax.lax.stop_gradient(target_values))
        return jnp.mean(loss * discount[:-1][..., 0])

    def train_step(params, opt_states, moments_state, data, key, update_target):
        T, B = data["rewards"].shape[:2]
        k_wm, k_img_e, k_a0_e, k_img_t, k_a0_t = jax.random.split(key, 5)
        sg = jax.lax.stop_gradient

        batch_obs = {k: data[k] for k in cnn_keys + mlp_keys}
        is_first = data["is_first"].at[0].set(1.0)
        batch_actions = jnp.concatenate([jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], 0)

        # ---------------------------------------------------- 1. world model
        def wm_loss_fn(wm_params):
            embed = world_model.apply(wm_params, batch_obs, method=WorldModel.encode)

            def step(carry, x):
                post, rec = carry
                action, emb, first, k = x
                rec, post, _, post_logits, prior_logits = world_model.apply(
                    wm_params, post, rec, action, emb, first, k, method=WorldModel.dynamic
                )
                return (post, rec), (rec, post, post_logits, prior_logits)

            keys = jax.random.split(k_wm, T)
            init = (jnp.zeros((B, stoch_size)), jnp.zeros((B, rec_size)))
            _, (recs, posts, post_logits, prior_logits) = jax.lax.scan(
                step, init, (batch_actions, embed, is_first, keys), unroll=8
            )
            latents = jnp.concatenate([posts, recs], -1)
            recon = world_model.apply(wm_params, latents, method=WorldModel.decode)

            obs_lp = 0.0
            for k in cnn_keys:
                target = data[k].astype(jnp.float32) / 255.0 - 0.5
                target = target.reshape(T, B, -1, *target.shape[-2:])
                obs_lp = obs_lp + MSEDistribution(recon[k], dims=3).log_prob(target)
            for k in mlp_keys:
                obs_lp = obs_lp + SymlogDistribution(recon[k], dims=1).log_prob(data[k])

            # Reward/continue heads train on DETACHED latents (reference :160,:163).
            reward_lp = TwoHotEncodingDistribution(
                world_model.apply(wm_params, sg(latents), method=WorldModel.reward), dims=1
            ).log_prob(data["rewards"])
            continue_lp = Independent(
                BernoulliSafeMode(world_model.apply(wm_params, sg(latents), method=WorldModel.continues)), 1
            ).log_prob(1.0 - data["terminated"])

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
            metrics["State/post_entropy"] = Independent(OneHotCategorical(post_logits_s), 1).entropy().mean()
            metrics["State/prior_entropy"] = Independent(OneHotCategorical(prior_logits_s), 1).entropy().mean()
            return rec_loss, (posts, recs, metrics)

        (rec_loss, (posts, recs, wm_metrics)), wm_grads = jax.value_and_grad(wm_loss_fn, has_aux=True)(
            params["world_model"]
        )
        wm_updates, new_wm_opt = wm_opt.update(wm_grads, opt_states["world_model"], params["world_model"])
        new_wm_params = optax.apply_updates(params["world_model"], wm_updates)

        # ---------------------------------------------------- 2. ensembles
        ens_inputs = jnp.concatenate([sg(posts), sg(recs), data["actions"]], -1)
        ens_targets = sg(posts)[1:]

        def ens_loss_fn(ens_params):
            return ensemble_loss(ensemble_mlp, ens_params, ens_inputs, ens_targets)

        ens_loss_val, ens_grads = jax.value_and_grad(ens_loss_fn)(params["ensembles"])
        ens_updates, new_ens_opt = ens_opt.update(ens_grads, opt_states["ensembles"], params["ensembles"])
        new_ens_params = optax.apply_updates(params["ensembles"], ens_updates)

        # ---------------------------------------------------- 3. exploration behaviour
        latent0 = sg(jnp.concatenate([posts, recs], -1)).reshape(T * B, -1)
        prior0 = sg(posts).reshape(T * B, stoch_size)
        rec0 = sg(recs).reshape(T * B, rec_size)
        true_continue0 = (1.0 - data["terminated"]).reshape(T * B, 1)

        def expl_actor_loss_fn(actor_params):
            traj, imagined_actions = _imagine(actor_params, new_wm_params, prior0, rec0, latent0, k_img_e, k_a0_e)
            continues = BernoulliSafeMode(
                world_model.apply(new_wm_params, traj, method=WorldModel.continues)
            ).mode
            continues = jnp.concatenate([true_continue0[None], continues[1:]], 0)
            discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)

            advantages = []
            per_critic = {}
            new_moments_expl = {}
            metrics = {}
            for k, ccfg in critic_cfgs.items():
                values = TwoHotEncodingDistribution(
                    critic.apply(params["critics_exploration"][k]["module"], traj), dims=1
                ).mean
                if ccfg["reward_type"] == "intrinsic":
                    reward = intrinsic_reward(
                        ensemble_mlp,
                        new_ens_params,
                        jnp.concatenate([sg(traj), sg(imagined_actions)], -1),
                        intr_mult,
                    )
                    metrics[f"Rewards/intrinsic_{k}"] = reward.mean()
                else:
                    reward = TwoHotEncodingDistribution(
                        world_model.apply(new_wm_params, traj, method=WorldModel.reward), dims=1
                    ).mean
                lambda_values = _lambda_values(reward, values, continues)
                offset, invscale, new_m = _moments(moments_state["expl"][k], lambda_values)
                advantages.append(
                    (((lambda_values - offset) / invscale) - ((values[:-1] - offset) / invscale))
                    * ccfg["weight"]
                    / weights_sum
                )
                per_critic[k] = sg(lambda_values)
                new_moments_expl[k] = new_m
                metrics[f"Values_exploration/predicted_values_{k}"] = values.mean()
                metrics[f"Values_exploration/lambda_values_{k}"] = lambda_values.mean()

            advantage = sum(advantages)
            loss = _policy_loss(actor_params, traj, imagined_actions, advantage, discount)
            aux = {
                "traj": sg(traj),
                "discount": discount,
                "lambda_values": per_critic,
                "moments": new_moments_expl,
                "metrics": metrics,
            }
            return loss, aux

        (policy_loss_expl, expl_aux), expl_grads = jax.value_and_grad(expl_actor_loss_fn, has_aux=True)(
            params["actor_exploration"]
        )
        ae_updates, new_ae_opt = actor_opt.update(
            expl_grads, opt_states["actor_exploration"], params["actor_exploration"]
        )
        new_actor_expl = optax.apply_updates(params["actor_exploration"], ae_updates)

        new_critics_expl = {}
        new_critic_expl_opts = {}
        critic_metrics = {}
        for k in critic_cfgs:
            cur = params["critics_exploration"][k]
            loss_k, grads_k = jax.value_and_grad(_critic_loss)(
                cur["module"], cur["target"], expl_aux["traj"], expl_aux["lambda_values"][k], expl_aux["discount"]
            )
            upd_k, new_opt_k = critic_opt.update(grads_k, opt_states["critics_exploration"][k], cur["module"])
            new_module = optax.apply_updates(cur["module"], upd_k)
            new_target = jax.lax.cond(
                update_target,
                lambda nm=new_module, tg=cur["target"]: jax.tree.map(
                    lambda tp, cp: (1 - tau) * tp + tau * cp, tg, nm
                ),
                lambda tg=cur["target"]: tg,
            )
            new_critics_expl[k] = {"module": new_module, "target": new_target}
            new_critic_expl_opts[k] = new_opt_k
            critic_metrics[f"Loss/value_loss_exploration_{k}"] = loss_k

        # ---------------------------------------------------- 4. task behaviour
        def task_actor_loss_fn(actor_params):
            traj, imagined_actions = _imagine(actor_params, new_wm_params, prior0, rec0, latent0, k_img_t, k_a0_t)
            values = TwoHotEncodingDistribution(critic.apply(params["critic_task"], traj), dims=1).mean
            rewards_img = TwoHotEncodingDistribution(
                world_model.apply(new_wm_params, traj, method=WorldModel.reward), dims=1
            ).mean
            continues = BernoulliSafeMode(
                world_model.apply(new_wm_params, traj, method=WorldModel.continues)
            ).mode
            continues = jnp.concatenate([true_continue0[None], continues[1:]], 0)
            discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)

            lambda_values = _lambda_values(rewards_img, values, continues)
            offset, invscale, new_m = _moments(moments_state["task"], lambda_values)
            advantage = ((lambda_values - offset) / invscale) - ((values[:-1] - offset) / invscale)
            loss = _policy_loss(actor_params, traj, imagined_actions, advantage, discount)
            aux = {
                "traj": sg(traj),
                "discount": discount,
                "lambda_values": sg(lambda_values),
                "moments": new_m,
            }
            return loss, aux

        (policy_loss_task, task_aux), task_grads = jax.value_and_grad(task_actor_loss_fn, has_aux=True)(
            params["actor_task"]
        )
        at_updates, new_at_opt = actor_opt.update(task_grads, opt_states["actor_task"], params["actor_task"])
        new_actor_task = optax.apply_updates(params["actor_task"], at_updates)

        value_loss_task, ct_grads = jax.value_and_grad(_critic_loss)(
            params["critic_task"],
            params["target_critic_task"],
            task_aux["traj"],
            task_aux["lambda_values"],
            task_aux["discount"],
        )
        ct_updates, new_ct_opt = critic_opt.update(ct_grads, opt_states["critic_task"], params["critic_task"])
        new_critic_task = optax.apply_updates(params["critic_task"], ct_updates)
        new_target_task = jax.lax.cond(
            update_target,
            lambda: jax.tree.map(
                lambda tp, cp: (1 - tau) * tp + tau * cp, params["target_critic_task"], new_critic_task
            ),
            lambda: params["target_critic_task"],
        )

        new_params = {
            "world_model": new_wm_params,
            "actor_task": new_actor_task,
            "critic_task": new_critic_task,
            "target_critic_task": new_target_task,
            "actor_exploration": new_actor_expl,
            "critics_exploration": new_critics_expl,
            "ensembles": new_ens_params,
        }
        new_opt_states = {
            "world_model": new_wm_opt,
            "actor_task": new_at_opt,
            "critic_task": new_ct_opt,
            "actor_exploration": new_ae_opt,
            "critics_exploration": new_critic_expl_opts,
            "ensembles": new_ens_opt,
        }
        new_moments = {"task": task_aux["moments"], "expl": expl_aux["moments"]}
        metrics = dict(wm_metrics)
        metrics.update(expl_aux["metrics"])
        metrics.update(critic_metrics)
        metrics["Loss/ensemble_loss"] = ens_loss_val
        metrics["Loss/policy_loss_exploration"] = policy_loss_expl
        metrics["Loss/policy_loss_task"] = policy_loss_task
        metrics["Loss/value_loss_task"] = value_loss_task
        if health_enabled(cfg):  # trace-time constant (obs/health.py)
            metrics.update(
                diagnostics(
                    grads={
                        "world_model": wm_grads,
                        "ensembles": ens_grads,
                        "actor_exploration": expl_grads,
                        "actor_task": task_grads,
                        "critic_task": ct_grads,
                    },
                    params=new_params,
                    updates={
                        "world_model": wm_updates,
                        "ensembles": ens_updates,
                        "actor_exploration": ae_updates,
                        "actor_task": at_updates,
                        "critic_task": ct_updates,
                    },
                )
            )
        metrics = maybe_inject_nonfinite(cfg, metrics)
        if strict_enabled(cfg):  # trace-time constant: callback exists only in strict runs
            nan_scan(metrics, "p2e_dv3/train_step")
        return new_params, new_opt_states, new_moments, metrics

    return train_step, init_opt_states, init_moments_state


def enabled_critics(cfg):
    """The static ``{name: {"weight", "reward_type"}}`` of the exploration critics in use."""
    return {
        k: {"weight": v["weight"], "reward_type": v["reward_type"]}
        for k, v in cfg.algo.critics_exploration.items()
        if v["weight"] > 0
    }


@register_algorithm(name="p2e_dv3_exploration")
def main(ctx, cfg) -> None:
    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
            ctx, actions_dim, is_continuous, cfg, obs_space
        )
        train_step, init_opt_states, init_moments_state = make_train_step(
            world_model,
            actor,
            critic,
            ensemble_mlp,
            cfg,
            list(cfg.algo.cnn_keys.encoder),
            list(cfg.algo.mlp_keys.encoder),
            enabled_critics(cfg),
        )
        return Entry(
            carry=(params, ctx.shard_params(init_opt_states(params)), ctx.replicate(init_moments_state())),
            ckpt_names=("params", "opt_states", "moments"),
            # the EMA target cadence tests the count BEFORE the increment, as the eager loop did
            block_step=block_step_of(train_step),
            dispatcher_kwargs=dict(
                target_update_freq=cfg.algo.critic.per_rank_target_network_update_freq, count_offset=0
            ),
            player_step=make_player_step(world_model, actor, actions_dim, cfg.algo.world_model.discrete_size),
            player_params=exploring_actor_view(cfg),
            stochastic_size=cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size,
            aggregator_keys=AGGREGATOR_KEYS,
            place=ctx.shard_params,
        )

    run_loop(ctx, cfg, setup, make_device_replay=make_device_replay)


def lower_for_audit():
    """IR-audit hook (``python -m sheeprl_tpu.analysis.ir``): the P2E-DV3
    exploration gradient block (DV3 world model + task head + per-critic
    exploration heads/moments + intrinsic ensembles) at tiny MLP-only shapes."""
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
        [
            "exp=p2e_dv3_dummy",
            "env=discrete_dummy",
            *DREAMER_TINY_OVERRIDES,
            *DREAMER_DISCRETE_OVERRIDES,
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
    train_step, init_opt_states, init_moments_state = make_train_step(
        world_model, actor, critic, ensemble_mlp, cfg, [], ["state"], enabled_critics(cfg)
    )
    carry = (params, init_opt_states(params), init_moments_state())

    block = make_train_block(block_step_of(train_step), cfg.algo.critic.per_rank_target_network_update_freq, 0)
    batch = sequence_batch(
        {"state": obs_space["state"].shape},
        act_dim=int(sum(actions_dim)),
        T=int(cfg.algo.per_rank_sequence_length),
        B=int(cfg.algo.per_rank_batch_size),
    )
    return [
        AuditEntry(
            name="p2e_dv3/train_block",
            fn=block,
            args=(carry, (batch,), jax.random.PRNGKey(0), 0),
            covers=("p2e_dv3_exploration",),
            precision=str(cfg.mesh.precision),
        )
    ]
