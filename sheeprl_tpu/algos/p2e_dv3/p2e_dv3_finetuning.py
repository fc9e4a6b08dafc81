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

from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop
from sheeprl_tpu.algos.dreamer_v3.agent import make_player_step
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import block_step_of, make_train_step as make_dv3_train_step
from sheeprl_tpu.algos.p2e import finetuning_fields, load_exploration_config
from sheeprl_tpu.algos.p2e_dv3.agent import build_agent
from sheeprl_tpu.algos.p2e_dv3.p2e_dv3_exploration import enabled_critics, make_train_step as make_expl_train_step
from sheeprl_tpu.algos.p2e_dv3.utils import AGGREGATOR_KEYS
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.utils.registry import register_algorithm


@register_algorithm(name="p2e_dv3_finetuning")
def main(ctx, cfg, exploration_cfg=None) -> None:
    if exploration_cfg is None:
        exploration_cfg = load_exploration_config(cfg)

    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        cnn_keys = list(cfg.algo.cnn_keys.encoder)
        mlp_keys = list(cfg.algo.mlp_keys.encoder)
        world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
            ctx, actions_dim, is_continuous, cfg, obs_space
        )
        # Exploration-shaped state templates (for loading the exploration checkpoint).
        _, expl_init_opt, expl_init_moments = make_expl_train_step(
            world_model, actor, critic, ensemble_mlp, cfg, cnn_keys, mlp_keys, enabled_critics(cfg)
        )
        # The finetuning train step IS the DV3 one over the task slice.
        train_step, _ = make_dv3_train_step(
            world_model, actor, critic, cfg, cnn_keys, mlp_keys, {k: obs_space[k].shape for k in cnn_keys + mlp_keys}
        )
        return Entry(
            **finetuning_fields(
                ctx,
                cfg,
                ctx.shard_params,
                {"params": params, "opt_states": expl_init_opt(params), "moments": expl_init_moments()},
                {
                    "world_model": "world_model",
                    "actor": "actor_task",
                    "critic": "critic_task",
                    "target_critic": "target_critic_task",
                },
            ),
            # the EMA target cadence tests the count BEFORE the increment, as the eager loop did
            block_step=block_step_of(train_step),
            dispatcher_kwargs=dict(
                target_update_freq=cfg.algo.critic.per_rank_target_network_update_freq, count_offset=0
            ),
            player_step=make_player_step(world_model, actor, actions_dim, cfg.algo.world_model.discrete_size),
            stochastic_size=cfg.algo.world_model.stochastic_size * cfg.algo.world_model.discrete_size,
            aggregator_keys=AGGREGATOR_KEYS,
            place=ctx.shard_params,
        )

    run_loop(ctx, cfg, setup, make_device_replay=make_device_replay)
