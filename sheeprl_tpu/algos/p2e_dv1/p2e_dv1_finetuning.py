"""P2E-DV1 finetuning (reference: ``/root/reference/sheeprl/algos/p2e_dv1/p2e_dv1_finetuning.py``).

Loads the exploration checkpoint and finetunes the task policy with the standard
DreamerV1 train step applied to the ``{world_model, actor_task, critic_task}`` slice."""

from __future__ import annotations

import numpy as np

from sheeprl_tpu.algos.dreamer.loop import Entry, run as run_loop
from sheeprl_tpu.algos.dreamer_v1.dreamer_v1 import block_step_of, make_train_step as make_dv1_train_step
from sheeprl_tpu.algos.dreamer_v2.agent import exploration_schedule
from sheeprl_tpu.algos.p2e import finetuning_fields, load_exploration_config
from sheeprl_tpu.algos.p2e_dv1.agent import build_agent, make_player_step
from sheeprl_tpu.algos.p2e_dv1.p2e_dv1_exploration import make_train_step as make_expl_train_step
from sheeprl_tpu.algos.p2e_dv1.utils import AGGREGATOR_KEYS
from sheeprl_tpu.data.device_buffer import make_device_replay
from sheeprl_tpu.utils.registry import register_algorithm


@register_algorithm(name="p2e_dv1_finetuning")
def main(ctx, cfg, exploration_cfg=None) -> None:
    if exploration_cfg is None:
        exploration_cfg = load_exploration_config(cfg)

    def setup(obs_space, is_continuous, actions_dim) -> Entry:
        cnn_keys = list(cfg.algo.cnn_keys.encoder)
        mlp_keys = list(cfg.algo.mlp_keys.encoder)
        world_model, actor, critic, ensemble_mlp, params, _ = build_agent(
            ctx, actions_dim, is_continuous, cfg, obs_space
        )
        # Exploration-shaped optimizer template (for loading the exploration checkpoint).
        _, expl_init_opt = make_expl_train_step(world_model, actor, critic, ensemble_mlp, cfg, cnn_keys, mlp_keys)
        # The finetuning train step IS the DV1 one over the task slice.
        train_step, _ = make_dv1_train_step(world_model, actor, critic, cfg, cnn_keys, mlp_keys)
        return Entry(
            **finetuning_fields(
                ctx,
                cfg,
                ctx.replicate,
                {"params": params, "opt_states": expl_init_opt(params)},
                {"world_model": "world_model", "actor": "actor_task", "critic": "critic_task"},
            ),
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
