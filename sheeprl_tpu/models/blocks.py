"""Reusable flax.linen building blocks.

TPU-native re-design of ``/root/reference/sheeprl/models/models.py``:

* ``MLP`` (reference ``:16-119``) — dense stack with optional per-layer LayerNorm.
* ``CNN`` / ``DeCNN`` (``:122-287``) — conv stacks in **NHWC** (TPU-native layout; the
  reference is NCHW because torch).  Callers transpose channel-first observations once
  at the boundary.
* ``NatureCNN`` (``:288-330``) — the classic 3-conv Atari trunk + projection.
* ``LayerNormGRUCell`` (``:331-412``) — GRU with LayerNorm on the joint input/hidden
  projection and the Hafner ``update - 1`` bias trick.
* ``MultiEncoder`` / ``MultiDecoder`` (``:413-506``) — fuse dict observations: CNN keys
  concatenated channel-wise into one conv trunk, MLP keys concatenated into one dense
  trunk, outputs concatenated.

All modules take ``dtype`` (compute dtype, bf16 for TPU) and keep ``param_dtype``
float32 — the standard mixed-precision recipe for the MXU.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

Dtype = Any


def _activation(act: str | Callable | None) -> Optional[Callable]:
    if act is None or callable(act):
        return act
    table = {
        "relu": nn.relu,
        "tanh": jnp.tanh,
        "silu": nn.silu,
        "swish": nn.silu,
        "elu": nn.elu,
        "gelu": nn.gelu,
        "leaky_relu": nn.leaky_relu,
        "identity": None,
        "none": None,
    }
    return table[str(act).lower()]


class MLP(nn.Module):
    hidden_sizes: Sequence[int] = ()
    output_dim: Optional[int] = None
    activation: str | Callable = "tanh"
    layer_norm: bool = False
    norm_eps: float = 1e-5
    flatten_input: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = _activation(self.activation)
        if self.flatten_input:
            x = x.reshape(*x.shape[:-1], -1) if x.ndim > 1 else x
        x = x.astype(self.dtype)
        for size in self.hidden_sizes:
            x = nn.Dense(size, dtype=self.dtype)(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
            if act is not None:
                x = act(x)
        if self.output_dim is not None:
            x = nn.Dense(self.output_dim, dtype=self.dtype)(x)
        return x


class CNN(nn.Module):
    """Conv stack over NHWC input. ``channels[i]`` with ``kernels[i]``/``strides[i]``."""

    channels: Sequence[int]
    kernels: Sequence[int] = (4,)
    strides: Sequence[int] = (2,)
    paddings: Sequence[Any] = ("SAME",)
    activation: str | Callable = "relu"
    layer_norm: bool = False
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = _activation(self.activation)
        n = len(self.channels)
        kernels = list(self.kernels) * n if len(self.kernels) == 1 else self.kernels
        strides = list(self.strides) * n if len(self.strides) == 1 else self.strides
        paddings = list(self.paddings) * n if len(self.paddings) == 1 else self.paddings
        x = x.astype(self.dtype)
        for c, k, s, p in zip(self.channels, kernels, strides, paddings):
            pad = p if isinstance(p, str) else [(p, p), (p, p)]
            x = nn.Conv(c, (k, k), strides=(s, s), padding=pad, dtype=self.dtype)(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
            if act is not None:
                x = act(x)
        return x


class DeCNN(nn.Module):
    """Transposed-conv stack over NHWC input."""

    channels: Sequence[int]
    kernels: Sequence[int] = (4,)
    strides: Sequence[int] = (2,)
    paddings: Sequence[Any] = ("SAME",)
    activation: str | Callable = "relu"
    apply_act_last: bool = False
    layer_norm: bool = False
    norm_eps: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = _activation(self.activation)
        n = len(self.channels)
        kernels = list(self.kernels) * n if len(self.kernels) == 1 else self.kernels
        strides = list(self.strides) * n if len(self.strides) == 1 else self.strides
        paddings = list(self.paddings) * n if len(self.paddings) == 1 else self.paddings
        x = x.astype(self.dtype)
        for i, (c, k, s, p) in enumerate(zip(self.channels, kernels, strides, paddings)):
            last = i == n - 1
            pad = p if isinstance(p, str) else [(p, p), (p, p)]
            x = nn.ConvTranspose(c, (k, k), strides=(s, s), padding=pad, dtype=self.dtype)(x)
            if not last or self.apply_act_last:
                if self.layer_norm:
                    x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
                if act is not None:
                    x = act(x)
        return x


class NatureCNN(nn.Module):
    """DQN Nature trunk (reference ``models.py:288-330``): uint8 NHWC in, flat features out."""

    features_dim: int = 512
    activation: str | Callable = "relu"
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = _activation(self.activation)
        x = x.astype(self.dtype)
        x = act(nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID", dtype=self.dtype)(x))
        x = act(nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID", dtype=self.dtype)(x))
        x = act(nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID", dtype=self.dtype)(x))
        x = x.reshape(*x.shape[:-3], -1)
        x = act(nn.Dense(self.features_dim, dtype=self.dtype)(x))
        return x


class LayerNormGRUCell(nn.Module):
    """GRU cell with LayerNorm on the fused projection (reference ``models.py:331-412``).

    One matmul computes all three gates from ``[input, hidden]`` — a single large MXU op
    instead of six small ones.  The update gate gets a ``-1`` bias (Hafner) so the cell
    starts out remembering.

    The post-matmul chain (LayerNorm + gates + state blend) is
    ``sheeprl_tpu.ops.gru.reference_layernorm_gru``, plain ``jax.numpy``.
    """

    hidden_size: int
    layer_norm: bool = True
    norm_eps: float = 1e-3
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        from sheeprl_tpu.ops.gru import reference_layernorm_gru

        inp = jnp.concatenate([x, h], axis=-1).astype(self.dtype)
        fused = nn.Dense(3 * self.hidden_size, use_bias=not self.layer_norm, dtype=self.dtype)(inp)
        if self.layer_norm:
            # NOTE: ln_scale/ln_bias replaced the earlier nn.LayerNorm child module, so
            # the param tree changed (checkpoints from before this cell revision need a
            # LayerNorm_0/{scale,bias} -> ln_scale/ln_bias rename).
            gamma = self.param("ln_scale", nn.initializers.ones, (3 * self.hidden_size,), jnp.float32)
            beta = self.param("ln_bias", nn.initializers.zeros, (3 * self.hidden_size,), jnp.float32)
            h_new = reference_layernorm_gru(fused, h.astype(self.dtype), gamma, beta, self.norm_eps)
            return h_new, h_new
        reset, cand, update = jnp.split(fused, 3, axis=-1)
        reset = jax.nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = jax.nn.sigmoid(update - 1.0)
        h_new = update * cand + (1 - update) * h.astype(self.dtype)
        return h_new, h_new


def cnn_obs_to_nhwc(x: jax.Array, stacked: bool = False) -> jax.Array:
    """``[..., C, H, W]`` (or ``[..., S, C, H, W]`` when ``stacked``) uint8 →
    ``[..., H, W, C·S]`` float in [-0.5, 0.5].

    ``stacked`` must be passed explicitly (derived from the observation-space rank at
    build time): shape alone cannot distinguish a frame-stacked batch from a
    sequence batch ``[T, B, C, H, W]``."""
    if x.dtype == jnp.uint8:
        x = x.astype(jnp.float32) / 255.0 - 0.5
    if stacked:
        *lead, s, c, h, w = x.shape
        x = x.reshape(*lead, s * c, h, w)
    return jnp.moveaxis(x, -3, -1)


class MultiEncoder(nn.Module):
    """Fuse dict observations into one feature vector (reference ``models.py:413-477``).

    ``cnn_keys`` are concatenated channel-wise and passed through one conv trunk;
    ``mlp_keys`` are concatenated and passed through one dense trunk; outputs concat.
    """

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_stacked: bool = False  # True when the env pipeline frame-stacks ([S, C, H, W] obs)
    cnn_channels: Sequence[int] = (32, 64, 64)
    cnn_kernels: Sequence[int] = (8, 4, 3)
    cnn_strides: Sequence[int] = (4, 2, 1)
    cnn_features_dim: int = 512
    mlp_hidden_sizes: Sequence[int] = (256, 256)
    mlp_features_dim: Optional[int] = None
    activation: str | Callable = "relu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        feats = []
        act = _activation(self.activation)
        if self.cnn_keys:
            imgs = jnp.concatenate(
                [cnn_obs_to_nhwc(obs[k], stacked=self.cnn_stacked) for k in self.cnn_keys], axis=-1
            )
            lead = imgs.shape[:-3]
            imgs = imgs.reshape(-1, *imgs.shape[-3:])
            x = CNN(
                channels=self.cnn_channels,
                kernels=self.cnn_kernels,
                strides=self.cnn_strides,
                paddings=("VALID",),
                activation=self.activation,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )(imgs)
            x = x.reshape(*lead, -1)
            x = nn.Dense(self.cnn_features_dim, dtype=self.dtype)(x)
            if act is not None:
                x = act(x)
            feats.append(x)
        if self.mlp_keys:
            vec = jnp.concatenate([obs[k].astype(self.dtype) for k in self.mlp_keys], axis=-1)
            x = MLP(
                hidden_sizes=self.mlp_hidden_sizes,
                output_dim=self.mlp_features_dim,
                activation=self.activation,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )(vec)
            feats.append(x)
        return jnp.concatenate(feats, axis=-1)


class MultiDecoder(nn.Module):
    """Decode a latent into per-key observation reconstructions (reference ``:478-506``)."""

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_shapes: Dict[str, Tuple[int, ...]]  # per-key [C, H, W]
    mlp_shapes: Dict[str, Tuple[int, ...]]
    cnn_decoder_init: Tuple[int, int, int] = (4, 4, 128)  # H, W, C before deconvs
    cnn_channels: Sequence[int] = (64, 32, 3)
    cnn_kernels: Sequence[int] = (4, 4, 4)
    cnn_strides: Sequence[int] = (2, 2, 2)
    mlp_hidden_sizes: Sequence[int] = (256, 256)
    activation: str | Callable = "relu"
    layer_norm: bool = False
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, z: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_keys:
            total_c = sum(int(np.prod(self.cnn_shapes[k][:-2])) for k in self.cnn_keys)
            h0, w0, c0 = self.cnn_decoder_init
            x = nn.Dense(h0 * w0 * c0, dtype=self.dtype)(z.astype(self.dtype))
            lead = x.shape[:-1]
            x = x.reshape(-1, h0, w0, c0)
            channels = list(self.cnn_channels[:-1]) + [total_c]
            x = DeCNN(
                channels=channels,
                kernels=self.cnn_kernels,
                strides=self.cnn_strides,
                paddings=("SAME",),
                activation=self.activation,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )(x)
            x = jnp.moveaxis(x, -1, -3)  # back to channel-first for parity with obs
            x = x.reshape(*lead, *x.shape[-3:])
            offset = 0
            for k in self.cnn_keys:
                c = int(np.prod(self.cnn_shapes[k][:-2]))
                out[k] = x[..., offset : offset + c, :, :].reshape(*lead, *self.cnn_shapes[k])
                offset += c
        for k in self.mlp_keys:
            out[k] = MLP(
                hidden_sizes=self.mlp_hidden_sizes,
                output_dim=int(np.prod(self.mlp_shapes[k])),
                activation=self.activation,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
                name=f"mlp_decoder_{k}",
            )(z)
        return out
