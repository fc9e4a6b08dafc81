"""DreamerV2 agent modules (reference: ``/root/reference/sheeprl/algos/dreamer_v2/agent.py``).

Differences from the DreamerV3 family (``sheeprl_tpu/algos/dreamer_v3/agent.py``) that
this module encodes, matching the reference:

* ELU activations and *optional* LayerNorm (reference defaults ``layer_norm=False``,
  ``agent.py:56,108``) instead of always-on LN+SiLU;
* VALID-padding conv stages in the encoder (k=4, s=2, ``agent.py:62-74``) and the
  Hafner DV2 decoder geometry (1×1 → k=5,5,6,6 s=2 → 64×64, ``agent.py:166-187``);
* no unimix on the categorical latents (``agent.py:383,395``);
* zero (not learned) initial recurrent/posterior state — ``is_first`` masking multiplies
  the carried state by ``(1 - is_first)`` (``agent.py:362-365``);
* actor with ``trunc_normal`` default for continuous actions (``agent.py:472-476``) and
  train-time exploration noise (``agent.py:558-574``);
* critic/reward heads emit a single Gaussian mean (no two-hot).

All recurrent unrolls happen in ``lax.scan`` inside the jitted train step — the modules
expose pure single-step methods for the scan bodies, like the DV3 agent.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import PlayerState, parse_actions_dim
from sheeprl_tpu.distributions import (
    Normal,
    OneHotCategorical,
    OneHotCategoricalStraightThrough,
    TanhNormal,
    TruncatedNormal,
)
from sheeprl_tpu.models.blocks import MLP, LayerNormGRUCell

Dtype = Any


def compute_stochastic_state(key: Optional[jax.Array], logits: jax.Array, discrete: int = 32, sample: bool = True) -> jax.Array:
    """One-hot straight-through sample WITHOUT unimix (reference ``dreamer_v2/utils.py:80-96``)."""
    shaped = logits.reshape(*logits.shape[:-1], -1, discrete)
    dist = OneHotCategoricalStraightThrough(shaped)
    return dist.rsample(key) if sample else dist.mode


class CNNEncoderV2(nn.Module):
    """4× (conv k=4 s=2 VALID → [LN] → act); 64×64 → 2×2×8m (reference ``agent.py:62-76``)."""

    channels_multiplier: int = 48
    activation: str = "elu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from sheeprl_tpu.models.blocks import _activation

        act = _activation(self.activation)
        lead = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:]).astype(self.dtype)
        for i in range(4):
            ch = self.channels_multiplier * (2**i)
            x = nn.Conv(ch, (4, 4), strides=(2, 2), padding="VALID", use_bias=not self.layer_norm, dtype=self.dtype)(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype)(x)
            x = act(x)
        return x.reshape(*lead, -1)


class MLPEncoderV2(nn.Module):
    """Plain dense stack, no symlog (reference ``agent.py:102-126``)."""

    dense_units: int = 400
    mlp_layers: int = 4
    activation: str = "elu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(x)


class EncoderV2(nn.Module):
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_channels_multiplier: int = 48
    dense_units: int = 400
    mlp_layers: int = 4
    activation: str = "elu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        if self.cnn_keys:
            imgs = []
            for k in self.cnn_keys:
                img = obs[k]
                if img.dtype == jnp.uint8:
                    img = img.astype(jnp.float32) / 255.0 - 0.5
                imgs.append(jnp.moveaxis(img, -3, -1))
            x = jnp.concatenate(imgs, axis=-1)
            feats.append(
                CNNEncoderV2(
                    channels_multiplier=self.cnn_channels_multiplier,
                    activation=self.activation,
                    layer_norm=self.layer_norm,
                    dtype=self.dtype,
                    name="cnn_encoder",
                )(x)
            )
        if self.mlp_keys:
            vec = jnp.concatenate([obs[k].astype(jnp.float32) for k in self.mlp_keys], axis=-1)
            feats.append(
                MLPEncoderV2(
                    dense_units=self.dense_units,
                    mlp_layers=self.mlp_layers,
                    activation=self.activation,
                    layer_norm=self.layer_norm,
                    dtype=self.dtype,
                    name="mlp_encoder",
                )(vec)
            )
        return jnp.concatenate(feats, axis=-1).astype(jnp.float32)


class CNNDecoderV2(nn.Module):
    """latent → dense → 1×1 feature map → 4 VALID deconvs (k=5,5,6,6 s=2) → 64×64
    channel-first reconstruction (reference ``agent.py:166-195``)."""

    output_shapes: Dict[str, Tuple[int, ...]]  # per-key [C, H, W]
    cnn_encoder_output_dim: int
    channels_multiplier: int = 48
    activation: str = "elu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, z: jax.Array) -> Dict[str, jax.Array]:
        from sheeprl_tpu.models.blocks import _activation

        act = _activation(self.activation)
        total_c = sum(s[0] for s in self.output_shapes.values())
        x = nn.Dense(self.cnn_encoder_output_dim, dtype=self.dtype, name="latent_proj")(z.astype(self.dtype))
        lead = x.shape[:-1]
        x = x.reshape(-1, 1, 1, self.cnn_encoder_output_dim)
        channels = [self.channels_multiplier * 4, self.channels_multiplier * 2, self.channels_multiplier]
        kernels = [5, 5, 6, 6]
        for i, ch in enumerate(channels):
            x = nn.ConvTranspose(
                ch, (kernels[i], kernels[i]), strides=(2, 2), padding="VALID",
                use_bias=not self.layer_norm, dtype=self.dtype,
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype)(x)
            x = act(x)
        x = nn.ConvTranspose(total_c, (kernels[-1], kernels[-1]), strides=(2, 2), padding="VALID", dtype=self.dtype, name="head")(x)
        x = jnp.moveaxis(x, -1, -3).astype(jnp.float32)
        x = x.reshape(*lead, *x.shape[-3:])
        out, offset = {}, 0
        for k, shape in self.output_shapes.items():
            out[k] = x[..., offset : offset + shape[0], :, :]
            offset += shape[0]
        return out


class MLPDecoderV2(nn.Module):
    output_shapes: Dict[str, Tuple[int, ...]]
    dense_units: int = 400
    mlp_layers: int = 4
    activation: str = "elu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, z: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(z)
        return {
            k: nn.Dense(int(np.prod(shape)), dtype=self.dtype, name=f"head_{k}")(x).astype(jnp.float32)
            for k, shape in self.output_shapes.items()
        }


class RecurrentModelV2(nn.Module):
    """Dense(+LN)+act → LayerNormGRUCell (reference ``agent.py:264-298``)."""

    recurrent_state_size: int
    dense_units: int = 400
    activation: str = "elu"
    layer_norm: bool = True  # the GRU projection LN (reference config recurrent_model.layer_norm)
    dtype: Dtype = jnp.float32

    def setup(self):
        self.mlp = MLP(
            hidden_sizes=(self.dense_units,),
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
            name="input_proj",
        )
        self.rnn = LayerNormGRUCell(hidden_size=self.recurrent_state_size, layer_norm=True, dtype=self.dtype)

    def __call__(self, x: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = self.mlp(x)
        h, _ = self.rnn(recurrent_state, feat)
        return h.astype(jnp.float32)


class RSSMV2(nn.Module):
    """Discrete RSSM, no unimix, zero initial state (reference ``agent.py:301-413``)."""

    stochastic_size: int = 32
    discrete_size: int = 32
    recurrent_state_size: int = 600
    dense_units: int = 400
    transition_hidden_size: int = 600
    representation_hidden_size: int = 600
    activation: str = "elu"
    layer_norm: bool = False
    recurrent_layer_norm: bool = True
    dtype: Dtype = jnp.float32

    def setup(self):
        stoch_out = self.stochastic_size * self.discrete_size
        self.recurrent_model = RecurrentModelV2(
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.dense_units,
            activation=self.activation,
            layer_norm=self.recurrent_layer_norm,
            dtype=self.dtype,
        )
        self.representation_model = nn.Sequential(
            [
                MLP(
                    hidden_sizes=(self.representation_hidden_size,),
                    activation=self.activation,
                    layer_norm=self.layer_norm,
                    dtype=self.dtype,
                ),
                nn.Dense(stoch_out, dtype=self.dtype, name="repr_logits"),
            ]
        )
        self.transition_model = nn.Sequential(
            [
                MLP(
                    hidden_sizes=(self.transition_hidden_size,),
                    activation=self.activation,
                    layer_norm=self.layer_norm,
                    dtype=self.dtype,
                ),
                nn.Dense(stoch_out, dtype=self.dtype, name="trans_logits"),
            ]
        )

    def _representation(self, recurrent_state: jax.Array, embedded_obs: jax.Array, key: Optional[jax.Array], sample: bool = True):
        logits = self.representation_model(jnp.concatenate([recurrent_state, embedded_obs], -1)).astype(jnp.float32)
        return logits, compute_stochastic_state(key, logits, self.discrete_size, sample)

    def _transition(self, recurrent_state: jax.Array, key: Optional[jax.Array], sample: bool = True):
        logits = self.transition_model(recurrent_state).astype(jnp.float32)
        return logits, compute_stochastic_state(key, logits, self.discrete_size, sample)

    def dynamic(
        self,
        posterior: jax.Array,  # [B, stoch*discrete] flattened
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        is_first: jax.Array,
        key: jax.Array,
    ):
        """One posterior step with zero-resets on ``is_first`` (reference ``agent.py:333-368``)."""
        action = (1 - is_first) * action
        posterior = (1 - is_first) * posterior
        recurrent_state = (1 - is_first) * recurrent_state
        recurrent_state = self.recurrent_model(jnp.concatenate([posterior, action], -1), recurrent_state)
        k1, k2 = jax.random.split(key)
        prior_logits, prior = self._transition(recurrent_state, k1)
        posterior_logits, posterior_sample = self._representation(recurrent_state, embedded_obs, k2)
        posterior_flat = posterior_sample.reshape(*posterior_sample.shape[:-2], -1)
        return recurrent_state, posterior_flat, prior, posterior_logits, prior_logits

    def imagination(self, prior: jax.Array, recurrent_state: jax.Array, actions: jax.Array, key: jax.Array):
        recurrent_state = self.recurrent_model(jnp.concatenate([prior, actions], -1), recurrent_state)
        _, imagined = self._transition(recurrent_state, key)
        return imagined.reshape(*imagined.shape[:-2], -1), recurrent_state


class WorldModelV2(nn.Module):
    """Encoder + RSSM + decoders + Gaussian reward head + optional continue head
    (reference ``build_agent``, ``agent.py:673-…``)."""

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_shapes: Dict[str, Tuple[int, ...]]
    mlp_shapes: Dict[str, Tuple[int, ...]]
    cnn_channels_multiplier: int = 48
    dense_units: int = 400
    mlp_layers: int = 4
    stochastic_size: int = 32
    discrete_size: int = 32
    recurrent_state_size: int = 600
    transition_hidden_size: int = 600
    representation_hidden_size: int = 600
    activation: str = "elu"
    layer_norm: bool = False
    recurrent_layer_norm: bool = True
    use_continues: bool = False
    image_size: int = 64
    dtype: Dtype = jnp.float32

    def setup(self):
        self.encoder = EncoderV2(
            cnn_keys=self.cnn_keys,
            mlp_keys=self.mlp_keys,
            cnn_channels_multiplier=self.cnn_channels_multiplier,
            dense_units=self.dense_units,
            mlp_layers=self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )
        self.rssm = RSSMV2(
            stochastic_size=self.stochastic_size,
            discrete_size=self.discrete_size,
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.dense_units,
            transition_hidden_size=self.transition_hidden_size,
            representation_hidden_size=self.representation_hidden_size,
            activation=self.activation,
            layer_norm=self.layer_norm,
            recurrent_layer_norm=self.recurrent_layer_norm,
            dtype=self.dtype,
        )
        if self.cnn_keys:
            # VALID 4-stage encoder on a 64×64 input ends at 2×2×8m.
            final = (self.image_size - 4) // 2 + 1
            for _ in range(3):
                final = (final - 4) // 2 + 1
            self.observation_model_cnn = CNNDecoderV2(
                output_shapes=self.cnn_shapes,
                cnn_encoder_output_dim=final * final * self.cnn_channels_multiplier * 8,
                channels_multiplier=self.cnn_channels_multiplier,
                activation=self.activation,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )
        if self.mlp_keys:
            self.observation_model_mlp = MLPDecoderV2(
                output_shapes=self.mlp_shapes,
                dense_units=self.dense_units,
                mlp_layers=self.mlp_layers,
                activation=self.activation,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )
        self.reward_model = nn.Sequential(
            [
                MLP(
                    hidden_sizes=(self.dense_units,) * self.mlp_layers,
                    activation=self.activation,
                    layer_norm=self.layer_norm,
                    dtype=self.dtype,
                ),
                nn.Dense(1, dtype=self.dtype, name="reward_head"),
            ]
        )
        if self.use_continues:
            self.continue_model = nn.Sequential(
                [
                    MLP(
                        hidden_sizes=(self.dense_units,) * self.mlp_layers,
                        activation=self.activation,
                        layer_norm=self.layer_norm,
                        dtype=self.dtype,
                    ),
                    nn.Dense(1, dtype=self.dtype, name="continue_head"),
                ]
            )

    def encode(self, obs: Dict[str, jax.Array]) -> jax.Array:
        return self.encoder(obs)

    def decode(self, latent: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_keys:
            out.update(self.observation_model_cnn(latent))
        if self.mlp_keys:
            out.update(self.observation_model_mlp(latent))
        return out

    def reward(self, latent: jax.Array) -> jax.Array:
        return self.reward_model(latent).astype(jnp.float32)

    def continues(self, latent: jax.Array) -> jax.Array:
        return self.continue_model(latent).astype(jnp.float32)

    def dynamic(self, *args, **kwargs):
        return self.rssm.dynamic(*args, **kwargs)

    def imagination(self, *args, **kwargs):
        return self.rssm.imagination(*args, **kwargs)

    def representation(self, recurrent_state, embedded_obs, key, sample=True):
        return self.rssm._representation(recurrent_state, embedded_obs, key, sample)

    def __call__(self, obs: Dict[str, jax.Array], action: jax.Array, key: jax.Array):
        embed = self.encoder(obs)
        batch_shape = embed.shape[:-1]
        h0 = jnp.zeros((*batch_shape, self.recurrent_state_size))
        z0 = jnp.zeros((*batch_shape, self.stochastic_size * self.discrete_size))
        h, z, prior, post_logits, prior_logits = self.rssm.dynamic(
            z0, h0, action, embed, jnp.ones((*batch_shape, 1)), key
        )
        latent = jnp.concatenate([z, h], -1)
        recon = self.decode(latent)
        out = self.reward(latent)
        if self.use_continues:
            out = out + 0.0 * self.continues(latent)
        return out, recon


class ActorV2(nn.Module):
    """DV2 policy head (reference ``agent.py:416-574``): ``trunc_normal`` default for
    continuous actions, one-hot straight-through (no unimix) for discrete."""

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str = "auto"
    dense_units: int = 400
    mlp_layers: int = 4
    activation: str = "elu"
    layer_norm: bool = False
    init_std: float = 0.0
    min_std: float = 0.1
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, state: jax.Array, key: Optional[jax.Array] = None, greedy: bool = False, mask=None):
        dist_type = self.distribution
        if dist_type == "auto":
            dist_type = "trunc_normal" if self.is_continuous else "discrete"
        supported = ("discrete",) if not self.is_continuous else ("tanh_normal", "normal", "trunc_normal")
        if dist_type not in supported:
            raise ValueError(f"distribution.type={dist_type!r} not supported for this action space; use one of {supported}")
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(state)
        if self.is_continuous:
            out = nn.Dense(2 * sum(self.actions_dim), dtype=self.dtype, name="head")(x).astype(jnp.float32)
            mean, std = jnp.split(out, 2, -1)
            if dist_type == "tanh_normal":
                mean = 5 * jnp.tanh(mean / 5)
                std = jax.nn.softplus(std + self.init_std) + self.min_std
                dist = TanhNormal(mean, std)
            elif dist_type == "normal":
                dist = Normal(mean, std)
            else:  # trunc_normal
                std = 2 * jax.nn.sigmoid((std + self.init_std) / 2) + self.min_std
                dist = TruncatedNormal(jnp.tanh(mean), std, -1.0, 1.0)
            actions = dist.mode if (greedy or key is None) else dist.rsample(key)
            return (actions,), (dist,)
        heads = [nn.Dense(d, dtype=self.dtype, name=f"head_{i}")(x).astype(jnp.float32) for i, d in enumerate(self.actions_dim)]
        actions, dists = [], []
        keys = jax.random.split(key, len(heads)) if key is not None else [None] * len(heads)
        for logits, k in zip(heads, keys):
            d = OneHotCategoricalStraightThrough(logits)
            dists.append(d)
            actions.append(d.mode if (greedy or k is None) else d.rsample(k))
        return tuple(actions), tuple(dists)


class MinedojoActorV2(nn.Module):
    """Hierarchical masked MineDojo actor for the DV1/DV2 families (reference
    ``dreamer_v2/agent.py:577-…``; DV1 reuses it via ``dreamer_v1/agent.py:16-27``).
    Same conditional-mask scheme as the DV3 ``MinedojoActor`` — vectorized
    ``jnp.where`` selects instead of the reference's [T, B] python loops — with the
    family's ELU trunk and no unimix."""

    actions_dim: Sequence[int]  # (action-type, craft-arg, item-arg)
    is_continuous: bool = False
    distribution: str = "auto"
    dense_units: int = 400
    mlp_layers: int = 4
    activation: str = "elu"
    layer_norm: bool = False
    init_std: float = 0.0
    min_std: float = 0.1
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, state: jax.Array, key: Optional[jax.Array] = None, greedy: bool = False, mask=None):
        if self.is_continuous:
            raise ValueError("MinedojoActorV2 only supports the functional MultiDiscrete action space")
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(state)
        heads = [nn.Dense(d, dtype=self.dtype, name=f"head_{i}")(x).astype(jnp.float32) for i, d in enumerate(self.actions_dim)]
        keys = jax.random.split(key, len(heads)) if key is not None else [None] * len(heads)
        neg_inf = jnp.finfo(jnp.float32).min

        actions, dists = [], []
        functional_action = None
        for i, logits in enumerate(heads):
            if mask is not None:
                if i == 0:
                    logits = jnp.where(mask["mask_action_type"], logits, neg_inf)
                elif i == 1:
                    is_craft = (functional_action == 15)[..., None]
                    allowed = jnp.where(is_craft, mask["mask_craft_smelt"], True)
                    logits = jnp.where(allowed, logits, neg_inf)
                elif i == 2:
                    is_equip_place = jnp.logical_or(functional_action == 16, functional_action == 17)[..., None]
                    is_destroy = (functional_action == 18)[..., None]
                    allowed = jnp.where(is_equip_place, mask["mask_equip_place"], True)
                    allowed = jnp.where(is_destroy, mask["mask_destroy"], allowed)
                    logits = jnp.where(allowed, logits, neg_inf)
            d = OneHotCategoricalStraightThrough(logits)
            dists.append(d)
            actions.append(d.mode if (greedy or keys[i] is None) else d.rsample(keys[i]))
            if functional_action is None:
                functional_action = actions[0].argmax(-1)
        return tuple(actions), tuple(dists)


class CriticV2(nn.Module):
    """Single Gaussian-mean value head (reference ``build_agent`` critic)."""

    dense_units: int = 400
    mlp_layers: int = 4
    activation: str = "elu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, state: jax.Array) -> jax.Array:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(state)
        return nn.Dense(1, dtype=self.dtype, name="head")(x).astype(jnp.float32)


def exploration_amount(expl_amount: float, expl_decay: float, expl_min: float, step: int) -> float:
    """Exploration schedule (reference ``agent.py:499-503``; the reference expression
    ``amount *= 0.5 ** float(step) / decay`` is an operator-precedence slip — the
    intended Hafner schedule is ``amount * 0.5 ** (step / decay)``, used here)."""
    amount = expl_amount
    if expl_decay:
        amount *= 0.5 ** (float(step) / expl_decay)
    return max(amount, expl_min)


def exploration_schedule(cfg) -> Callable[[int], float]:
    """``policy_step -> amount`` by ``algo.actor.expl_{amount,decay,min}``: the extra
    acting argument of the DV1/DV2 players."""
    expl = cfg.algo.actor
    return functools.partial(
        exploration_amount, expl.get("expl_amount", 0.0), expl.get("expl_decay", 0.0), expl.get("expl_min", 0.0)
    )


def add_exploration_noise(
    actions: Tuple[jax.Array, ...],
    expl_amount: jax.Array,
    key: jax.Array,
    is_continuous: bool,
) -> Tuple[jax.Array, ...]:
    """Pure-JAX exploration noise (reference ``agent.py:558-574``): Gaussian jitter
    clipped to [-1, 1] for continuous actions; ε-uniform resampling for discrete."""
    if is_continuous:
        cat = jnp.concatenate(actions, -1)
        noisy = jnp.clip(cat + expl_amount * jax.random.normal(key, cat.shape), -1.0, 1.0)
        out = jnp.where(expl_amount > 0.0, noisy, cat)
        return (out,)
    noisy_actions = []
    for act in actions:
        key, k_sample, k_mask = jax.random.split(key, 3)
        rand = OneHotCategorical(jnp.zeros_like(act)).sample(k_sample)
        take_random = jax.random.uniform(k_mask, act.shape[:1]) < expl_amount
        noisy_actions.append(jnp.where(take_random[..., None], rand, act))
    return tuple(noisy_actions)


def _xavier_normal_init(params: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Xavier-normal re-init of all kernels, zero biases (reference
    ``dreamer_v2/utils.py:101-118`` ``init_weights``, applied in ``build_agent``)."""
    import flax

    flat = flax.traverse_util.flatten_dict(params)
    keys = jax.random.split(key, len(flat))
    new = {}
    for i, (path, value) in enumerate(flat.items()):
        leaf = str(path[-1])
        if leaf == "kernel" and value.ndim >= 2:
            # torch.nn.init.xavier_normal_ counts the conv receptive field in BOTH
            # fans (kernel layout here is [*rf, in, out]).
            receptive_field = int(np.prod(value.shape[:-2])) if value.ndim > 2 else 1
            fan_in = receptive_field * int(value.shape[-2])
            fan_out = receptive_field * int(value.shape[-1])
            std = float(np.sqrt(2.0 / (fan_in + fan_out)))
            new[path] = std * jax.random.normal(keys[i], value.shape, value.dtype)
        elif leaf == "bias":
            new[path] = jnp.zeros_like(value)
        else:
            new[path] = value
    return flax.traverse_util.unflatten_dict(new)


def build_agent(
    ctx,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
):
    """Construct DV2 world model / actor / critic (+ target critic) and params."""
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_shapes = {k: tuple(obs_space[k].shape) for k in cnn_keys}
    mlp_shapes = {k: tuple(obs_space[k].shape) for k in mlp_keys}
    wm_cfg = cfg.algo.world_model

    world_model = WorldModelV2(
        cnn_keys=cnn_keys,
        mlp_keys=mlp_keys,
        cnn_shapes=cnn_shapes,
        mlp_shapes=mlp_shapes,
        cnn_channels_multiplier=wm_cfg.encoder.cnn_channels_multiplier,
        dense_units=cfg.algo.dense_units,
        mlp_layers=cfg.algo.mlp_layers,
        stochastic_size=wm_cfg.stochastic_size,
        discrete_size=wm_cfg.discrete_size,
        recurrent_state_size=wm_cfg.recurrent_model.recurrent_state_size,
        transition_hidden_size=wm_cfg.transition_model.hidden_size,
        representation_hidden_size=wm_cfg.representation_model.hidden_size,
        activation=cfg.algo.dense_act,
        layer_norm=cfg.algo.layer_norm,
        recurrent_layer_norm=wm_cfg.recurrent_model.get("layer_norm", True),
        use_continues=wm_cfg.use_continues,
        image_size=cfg.env.screen_size,
        dtype=ctx.compute_dtype,
    )
    latent_size = wm_cfg.stochastic_size * wm_cfg.discrete_size + wm_cfg.recurrent_model.recurrent_state_size
    is_minedojo = "minedojo" in str(cfg.env.get("wrapper", {}).get("_target_", "")).lower()
    actor_cls = MinedojoActorV2 if is_minedojo else ActorV2
    actor = actor_cls(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=cfg.distribution.get("type", "auto"),
        dense_units=cfg.algo.actor.dense_units,
        mlp_layers=cfg.algo.actor.mlp_layers,
        activation=cfg.algo.dense_act,
        layer_norm=cfg.algo.layer_norm,
        init_std=cfg.algo.actor.init_std,
        min_std=cfg.algo.actor.min_std,
        dtype=ctx.compute_dtype,
    )
    critic = CriticV2(
        dense_units=cfg.algo.critic.dense_units,
        mlp_layers=cfg.algo.critic.mlp_layers,
        activation=cfg.algo.dense_act,
        layer_norm=cfg.algo.layer_norm,
        dtype=ctx.compute_dtype,
    )

    dummy_obs = {}
    for k in cnn_keys:
        dummy_obs[k] = jnp.zeros((1, *cnn_shapes[k]), dtype=jnp.uint8)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((1, *mlp_shapes[k]), dtype=jnp.float32)
    act_dim_sum = int(sum(actions_dim))
    wm_params = world_model.init(ctx.rng(), dummy_obs, jnp.zeros((1, act_dim_sum)), ctx.rng())
    actor_params = actor.init(ctx.rng(), jnp.zeros((1, latent_size)), ctx.rng())
    critic_params = critic.init(ctx.rng(), jnp.zeros((1, latent_size)))

    wm_params = {"params": _xavier_normal_init(wm_params["params"], ctx.rng())}
    actor_params = {"params": _xavier_normal_init(actor_params["params"], ctx.rng())}
    critic_params = {"params": _xavier_normal_init(critic_params["params"], ctx.rng())}
    target_critic_params = jax.tree.map(lambda x: x, critic_params)

    params = {
        "world_model": ctx.replicate(wm_params),
        "actor": ctx.replicate(actor_params),
        "critic": ctx.replicate(critic_params),
        "target_critic": ctx.replicate(target_critic_params),
    }
    return world_model, actor, critic, params, latent_size


def make_player_step(world_model: WorldModelV2, actor: ActorV2, actions_dim: Sequence[int], is_continuous: bool):
    """Pure player step with zero-resets and optional exploration noise
    (reference ``PlayerDV2``, ``agent.py:735-…``)."""

    def player_step(params, state: PlayerState, obs, is_first, key, expl_amount=0.0, greedy: bool = False):
        k_repr, k_act, k_expl = jax.random.split(key, 3)
        wm, ap = params["world_model"], params["actor"]
        mask = {k: v for k, v in obs.items() if k.startswith("mask")} or None
        embed = world_model.apply(wm, obs, method=WorldModelV2.encode)
        recurrent = (1 - is_first) * state.recurrent_state
        stoch = (1 - is_first) * state.stochastic_state
        prev_actions = (1 - is_first) * state.actions
        recurrent = world_model.apply(
            wm,
            jnp.concatenate([stoch, prev_actions], -1),
            recurrent,
            method=lambda m, x, h: m.rssm.recurrent_model(x, h),
        )
        _, stoch_sample = world_model.apply(wm, recurrent, embed, k_repr, method=WorldModelV2.representation)
        stoch = stoch_sample.reshape(*stoch_sample.shape[:-2], -1)
        latent = jnp.concatenate([stoch, recurrent], -1)
        actions, _ = actor.apply(ap, latent, k_act, greedy, mask)
        if not greedy:
            actions = add_exploration_noise(actions, jnp.asarray(expl_amount), k_expl, is_continuous)
        stored = jnp.concatenate(actions, -1)
        return actions, stored, PlayerState(recurrent, stoch, stored)

    return player_step
