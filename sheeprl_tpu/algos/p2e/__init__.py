"""Plan2Explore shared machinery (reference: ``/root/reference/sheeprl/algos/p2e_dv{1,2,3}``).

The reference builds its disagreement ensemble as a python list of N independent MLPs
iterated one-by-one (``p2e_dv3/agent.py:175-204``, ``p2e_dv3_exploration.py:208-230``).
TPU-native version: ONE MLP definition with N **stacked** parameter pytrees driven by
``jax.vmap`` — every ensemble member's matmul fuses into a single batched MXU op, for
both the training loss and the intrinsic-reward variance, instead of N small kernels.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models.blocks import MLP


def build_ensembles(
    rng_key: jax.Array,
    n: int,
    input_dim: int,
    output_dim: int,
    dense_units: int,
    mlp_layers: int,
    activation: str,
    layer_norm: bool,
    dtype: Any,
) -> Tuple[MLP, Any]:
    """N ensemble members as one module + stacked params (reference seeds each member
    differently, ``p2e_dv3/agent.py:178-199``; here each member gets its own PRNG key)."""
    mlp = MLP(
        hidden_sizes=(dense_units,) * mlp_layers,
        output_dim=output_dim,
        activation=activation,
        layer_norm=layer_norm,
        dtype=dtype,
    )
    keys = jax.random.split(rng_key, n)
    dummy = jnp.zeros((1, input_dim))
    stacked = jax.vmap(lambda k: mlp.init(k, dummy))(keys)
    return mlp, stacked


def ensemble_apply(mlp: MLP, stacked_params: Any, x: jax.Array) -> jax.Array:
    """[N, ...] predictions from all members in one vmapped (batched-matmul) pass."""
    return jax.vmap(lambda p: mlp.apply(p, x))(stacked_params)


def ensemble_loss(mlp: MLP, stacked_params: Any, inputs: jax.Array, targets: jax.Array) -> jax.Array:
    """Sum over members of the per-member MSE 'log-prob' loss (reference
    ``p2e_dv3_exploration.py:206-221``: ``-MSEDistribution(out[:-1], 1).log_prob(next)``)."""
    preds = ensemble_apply(mlp, stacked_params, inputs)[:, :-1]  # [N, T-1, B, D]
    sq = jnp.sum((preds - targets[None]) ** 2, -1)  # MSEDistribution dims=1 log_prob = -Σ(err²)
    return jnp.mean(sq, axis=(1, 2)).sum()


def ensemble_loss_normal(mlp: MLP, stacked_params: Any, inputs: jax.Array, targets: jax.Array) -> jax.Array:
    """DV1/DV2 variant: unit-variance Gaussian NLL instead of raw MSE (reference
    ``p2e_dv2_exploration.py:198-210``, ``p2e_dv1_exploration.py:168-174``)."""
    preds = ensemble_apply(mlp, stacked_params, inputs)[:, :-1]  # [N, T-1, B, D]
    dim = targets.shape[-1]
    log_norm = 0.5 * dim * jnp.log(2 * jnp.pi)
    nll = 0.5 * jnp.sum((preds - targets[None]) ** 2, -1) + log_norm
    return jnp.mean(nll, axis=(1, 2)).sum()


def intrinsic_reward(
    mlp: MLP, stacked_params: Any, inputs: jax.Array, multiplier: float
) -> jax.Array:
    """Ensemble-disagreement intrinsic reward (reference ``p2e_dv3_exploration.py:270-287``):
    variance across members of the predicted next-state embedding, mean over features."""
    preds = ensemble_apply(mlp, stacked_params, jax.lax.stop_gradient(inputs))  # [N, H+1, TB, D]
    return preds.var(0).mean(-1, keepdims=True) * multiplier


def load_exploration_config(cfg) -> Any:
    """Load + validate the exploration run's config for finetuning
    (reference ``cli.py:117-148``)."""
    from pathlib import Path

    from sheeprl_tpu.config.core import load_config

    ckpt_path = Path(cfg.checkpoint.exploration_ckpt_path)
    run_dir = ckpt_path.parent.parent if ckpt_path.is_dir() else ckpt_path.parent
    cfg_path = run_dir / "config.yaml"
    if not cfg_path.is_file():
        cfg_path = ckpt_path.parent / "config.yaml"
    if not cfg_path.is_file():
        raise FileNotFoundError(f"No config.yaml found alongside exploration checkpoint {ckpt_path}")
    exploration_cfg = load_config(cfg_path)
    if exploration_cfg.env.id != cfg.env.id:
        raise ValueError(
            "This experiment is run with a different environment from the one of the "
            f"exploration you want to finetune. Got '{cfg.env.id}', but the environment "
            f"used during exploration was {exploration_cfg.env.id}."
        )
    # Environment geometry must match the exploration world model.
    for key in (
        "frame_stack",
        "screen_size",
        "action_repeat",
        "grayscale",
        "clip_rewards",
        "frame_stack_dilation",
        "max_episode_steps",
        "reward_as_observation",
        # Minecraft adapters (reference cli.py:139-145)
        "max_pitch",
        "min_pitch",
        "sticky_jump",
        "sticky_attack",
        "break_speed_multiplier",
    ):
        if key in exploration_cfg.env:
            cfg.env[key] = exploration_cfg.env[key]
    # The finetuned models must be built exactly like the exploration ones, or the
    # checkpoint cannot be loaded (reference p2e_dv3_finetuning.py:46-69).
    for key in (
        "gamma",
        "lmbda",
        "horizon",
        "layer_norm",
        "dense_units",
        "mlp_layers",
        "dense_act",
        "cnn_act",
        "unimix",
        "hafner_initialization",
        "world_model",
        "actor",
        "critic",
        "critics_exploration",
        "ensembles",
        "cnn_keys",
        "mlp_keys",
        "intrinsic_reward_multiplier",
    ):
        if key in exploration_cfg.algo:
            cfg.algo[key] = exploration_cfg.algo[key]
    # Reusing the exploration buffer requires the same env count (see reference note).
    if cfg.buffer.get("load_from_exploration") and exploration_cfg.buffer.checkpoint:
        cfg.env.num_envs = exploration_cfg.env.num_envs
    return exploration_cfg


def actor_view(actor: str) -> Callable[[Any, Any], Any]:
    """The Dreamer loop's ``player_params`` (``algos/dreamer/loop.py::Entry``) for a player
    that acts with the world model and the actor named ``actor`` of the carry's parameters."""
    return lambda tree, aux: {"world_model": tree[0]["world_model"], "actor": tree[0][actor]}


def exploring_actor_view(cfg) -> Callable[[Any, Any], Any]:
    """The player of an exploration run: the actor ``algo.player.actor_type`` names."""
    exploring = cfg.algo.player.get("actor_type", "exploration") == "exploration"
    return actor_view("actor_exploration" if exploring else "actor_task")


def finetuning_fields(ctx, cfg, place: Callable[[Any], Any], templates: Any, task_view: Any) -> dict:
    """What the three finetuning entry points hand the Dreamer loop
    (``algos/dreamer/loop.py::Entry``) alike, whatever the task's train step is.

    The run starts from the exploration run's checkpoint, or from its own when it resumes:
    ``templates`` are the exploration-shaped device trees by checkpoint name (``params``,
    ``opt_states`` and, where the train step has them, ``moments``).  The functional
    parameter split makes the task's train step the plain Dreamer one over a slice:
    ``task_view`` maps each name of its trees to its name in the Plan2Explore tree, and
    that slice alone is the carry the block steps.  The player starts on the actor
    ``algo.player.actor_type`` names and switches to the TASK actor at the first
    training iteration (reference p2e finetuning ``:350-352``); there is no random
    prefill, the agent is pretrained.  A checkpoint is written exploration-shaped again, so
    that both resume and evaluation reload it with the same templates; what was not
    trained on keeps the values loaded from the exploration checkpoint."""
    from sheeprl_tpu.checkpoint.manager import CheckpointManager

    resume_from = cfg.checkpoint.get("resume_from")
    state = CheckpointManager.load(
        resume_from or cfg.checkpoint.exploration_ckpt_path, templates=jax.device_get(templates)
    )
    loaded, loaded_opts = state["params"], state["opt_states"]
    if not resume_from and not cfg.buffer.get("load_from_exploration"):
        state.pop("rb", None)  # the exploration run's ring is handed on only where asked for
    actor_type = cfg.algo.player.get("actor_type", "exploration")
    if resume_from:
        actor_type = state.get("actor_type", actor_type)
    exploring = actor_type == "exploration"
    carry = [
        place({k: loaded[name] for k, name in task_view.items()}),
        place({k: loaded_opts[task_view[k]] for k in ("world_model", "actor", "critic")}),
    ]
    if "moments" in templates:
        carry.append(ctx.replicate(state["moments"]["task"]))

    def to_ckpt(tree, aux, learning):
        view, opts, *moments = tree
        entries = {
            "params": {**loaded, **{name: view[k] for k, name in task_view.items()}},
            "opt_states": {**loaded_opts, **{task_view[k]: v for k, v in opts.items()}},
            "actor_type": "task" if learning else actor_type,
        }
        if moments:
            entries["moments"] = {"task": moments[0], "expl": templates["moments"]["expl"]}
        return entries

    return dict(
        carry=tuple(carry),
        aux=place(loaded["actor_exploration"]) if exploring else None,
        ckpt_names=tuple(templates),
        to_ckpt=to_ckpt,
        restored=state,
        player_params=actor_view("actor"),
        starting_player_params=(
            (lambda tree, aux: {"world_model": tree[0]["world_model"], "actor": aux}) if exploring else None
        ),
        random_prefill=False,
    )
