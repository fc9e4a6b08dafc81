"""Recurrent PPO agent (reference: ``/root/reference/sheeprl/algos/ppo_recurrent/agent.py:83-…``).

Encoder → (pre-RNN MLP) → LSTM → (post-RNN MLP) → actor/critic heads.  The LSTM input is
the encoded observation concatenated with the previous action (reference ``:133-138``).

TPU-native deviation (documented): instead of the reference's padded per-episode
sequences with masks (``ppo_recurrent.py:39-118``), sequences are the fixed-shape
``[rollout_steps, num_envs]`` rollout with hidden-state resets at episode starts applied
*inside* the scan (``is_first`` masking, same trick as the RSSM).  The objective is the
same; shapes are static so the whole update stays one jit."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo.agent import parse_action_space
from sheeprl_tpu.algos.ppo.utils import chunked_log_prob_and_entropy, log_prob_and_entropy
from sheeprl_tpu.models import decoder
from sheeprl_tpu.models.blocks import MLP, MultiEncoder
from sheeprl_tpu.obs.perf import scope
from sheeprl_tpu.ops.ring_attention import reference_attention


#: tokens whose logits the decoder's update forms at a time: 512 x 37,984 float32 logits are
#: 78 MB where the whole minibatch's would be 0.62 GB (SmallThinker's rows held; LFM2's 16,384: 34 MB)
HEAD_CHUNK = 512


class RecurrentPPOAgent(nn.Module):
    """``sequence_model="lstm"`` (default, reference parity) or ``"attention"`` — a
    causal windowed self-attention sequence mixer in place of the LSTM.  The
    attention variant is the ``sequence`` mesh axis's training-path consumer: with
    ``attention_fn`` set (built from ``make_ring_attention``) the training-time
    attention runs sequence-parallel over the ring; env-side steps carry a rolling
    window of the last ``attn_window`` inputs (reset at episode starts), which
    matches the training masks exactly because the loop also resets the window at
    every rollout start."""

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    action_dims: Sequence[int]
    is_continuous: bool
    cnn_stacked: bool = False
    cnn_features_dim: int = 512
    mlp_features_dim: int = 64
    dense_units: int = 64
    mlp_layers: int = 1
    dense_act: str = "tanh"
    layer_norm: bool = False
    lstm_hidden_size: int = 64
    pre_rnn_mlp: bool = False
    post_rnn_mlp: bool = False
    sequence_model: str = "lstm"
    attn_heads: int = 4
    attn_window: int = 64
    attention_fn: Any = None  # static; sequence-parallel ring attention when set
    dtype: Any = jnp.float32

    def setup(self):
        self.feature_extractor = MultiEncoder(
            cnn_keys=self.cnn_keys,
            mlp_keys=self.mlp_keys,
            cnn_stacked=self.cnn_stacked,
            cnn_features_dim=self.cnn_features_dim,
            mlp_hidden_sizes=(self.dense_units,) * self.mlp_layers,
            mlp_features_dim=self.mlp_features_dim,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )
        if self.pre_rnn_mlp:
            self.pre_mlp = MLP(
                hidden_sizes=(self.dense_units,),
                activation=self.dense_act,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )
        if self.sequence_model == "attention":
            h = self.lstm_hidden_size  # model width shared with the lstm variant
            self.attn_in = nn.Dense(h, dtype=self.dtype)
            self.attn_q = nn.Dense(h, dtype=self.dtype)
            self.attn_k = nn.Dense(h, dtype=self.dtype)
            self.attn_v = nn.Dense(h, dtype=self.dtype)
            self.attn_out = nn.Dense(h, dtype=self.dtype)
            self.attn_ln = nn.LayerNorm(dtype=self.dtype)
        else:
            self.cell = nn.OptimizedLSTMCell(self.lstm_hidden_size, dtype=self.dtype)
        if self.post_rnn_mlp:
            self.post_mlp = MLP(
                hidden_sizes=(self.dense_units,),
                activation=self.dense_act,
                layer_norm=self.layer_norm,
                dtype=self.dtype,
            )
        self.actor_backbone = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )
        if self.is_continuous:
            self.actor_heads = [nn.Dense(2 * self.action_dims[0], dtype=self.dtype)]
        else:
            self.actor_heads = [nn.Dense(d, dtype=self.dtype) for d in self.action_dims]
        self.critic = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            output_dim=1,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )

    def _heads(self, hidden: jax.Array) -> Tuple[List[jax.Array], jax.Array]:
        feat = self.post_mlp(hidden) if self.post_rnn_mlp else hidden
        pre_actor = self.actor_backbone(feat)
        actor_out = [h(pre_actor).astype(jnp.float32) for h in self.actor_heads]
        value = self.critic(feat).astype(jnp.float32)
        return actor_out, value

    def _rnn_input(self, obs: Dict[str, jax.Array], prev_actions: jax.Array) -> jax.Array:
        feat = self.feature_extractor(obs)
        x = jnp.concatenate([feat, prev_actions.astype(feat.dtype)], -1)
        if self.pre_rnn_mlp:
            x = self.pre_mlp(x)
        return x

    def _split_heads(self, x: jax.Array) -> jax.Array:
        *lead, h = x.shape
        return x.reshape(*lead, self.attn_heads, h // self.attn_heads)

    def step(
        self,
        obs: Dict[str, jax.Array],  # [B, ...]
        prev_actions: jax.Array,  # [B, A]
        is_first: jax.Array,  # [B, 1]
        state: Tuple[jax.Array, jax.Array],
    ):
        """Single env-side step: returns (actor_out, value, new_state)."""
        x = self._rnn_input(obs, (1 - is_first) * prev_actions)
        if self.sequence_model == "attention":
            window, valid = state  # [B, W, H], [B, W]
            xp = self.attn_in(x)
            # Episode start: forget the previous episode's window.
            window = (1 - is_first[..., None]) * window
            valid = (1 - is_first) * valid
            window = jnp.concatenate([window[:, 1:], xp[:, None].astype(window.dtype)], 1)
            valid = jnp.concatenate([valid[:, 1:], jnp.ones_like(valid[:, :1])], 1)
            q = self._split_heads(self.attn_q(xp))[:, None]  # [B, 1, nh, hd]
            k = self._split_heads(self.attn_k(window.astype(xp.dtype)))
            v = self._split_heads(self.attn_v(window.astype(xp.dtype)))
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
            s = s / jnp.sqrt(jnp.asarray(k.shape[-1], jnp.float32))
            s = jnp.where(valid[:, None, None, :] > 0, s, jnp.finfo(jnp.float32).min)
            p = jax.nn.softmax(s, -1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
            o = o.reshape(xp.shape[0], -1).astype(xp.dtype)
            out = self.attn_ln(xp + self.attn_out(o))
            actor_out, value = self._heads(out.astype(jnp.float32))
            return actor_out, value, (window, valid)
        c, h = state
        c = (1 - is_first) * c
        h = (1 - is_first) * h
        (c, h), out = self.cell((c, h), x)
        actor_out, value = self._heads(out.astype(jnp.float32))
        return actor_out, value, (c, h)

    def __call__(
        self,
        obs: Dict[str, jax.Array],  # [T, B, ...]
        prev_actions: jax.Array,  # [T, B, A]
        is_first: jax.Array,  # [T, B, 1]
        initial_state: Tuple[jax.Array, jax.Array],  # ([B,H], [B,H])
    ):
        """Sequence forward with in-scan resets; returns (actor_out [T,B,...], values)."""
        xs = self._rnn_input(obs, prev_actions * (1 - is_first))

        if self.sequence_model == "attention":
            # Causal windowed attention over the rollout, masked at episode
            # boundaries (segments = running count of is_first).  ``initial_state``
            # is unused: the loop resets the acting window at every rollout start,
            # so training and acting see identical contexts.
            T, B = xs.shape[:2]
            xp = self.attn_in(xs)  # [T, B, H]
            xbt = jnp.swapaxes(xp, 0, 1)  # [B, T, H]
            q = self._split_heads(self.attn_q(xbt))
            k = self._split_heads(self.attn_k(xbt))
            v = self._split_heads(self.attn_v(xbt))
            segs = jnp.swapaxes(jnp.cumsum(is_first[..., 0], axis=0), 0, 1).astype(jnp.int32)
            if self.attention_fn is not None:  # sequence-parallel ring
                o = self.attention_fn(q, k, v, segs)
            else:
                o = reference_attention(
                    q, k, v, causal=True, segment_ids=segs, window=self.attn_window
                )
            o = jnp.swapaxes(o.reshape(B, T, -1), 0, 1).astype(xp.dtype)  # [T, B, H]
            outs = self.attn_ln(xp + self.attn_out(o))
            actor_out, values = self._heads(outs.astype(jnp.float32))
            return actor_out, values

        def scan_step(mdl, carry, t):
            # The body must touch submodules through the TRANSFORMED module
            # ``mdl`` nn.scan hands it — reaching through the closed-over
            # ``self`` mixes the outer module with the scan's inner trace, which
            # newer flax rejects with JaxTransformError.
            c, h = carry
            x, first = t
            c = (1 - first) * c
            h = (1 - first) * h
            (c, h), out = mdl.cell((c, h), x)
            return (c, h), out

        _, outs = nn.scan(
            scan_step,
            variable_broadcast="params",
            split_rngs={"params": False},
        )(self, initial_state, (xs, is_first))
        actor_out, values = self._heads(outs.astype(jnp.float32))
        return actor_out, values


class DecoderPPOAgent(decoder.DecoderPolicy):
    """``sequence_model="decoder"``: the sparse-expert decoder of ``models/decoder.py`` as
    the policy.  Observations and actions are token ids of one vocabulary (an embedding
    lookup in place of the encoder and of the one-hot previous action); the critic is a
    linear head on the final hidden state; the policy's head is a table of its own or
    the embedding's (``decoder.head_of``)."""

    is_continuous = False

    @property
    def action_dims(self) -> Tuple[int, ...]:
        return (self.cfg.vocab_held,)


def evaluate_sequences(agent, params, batch: Dict[str, jax.Array], obs_keys: Sequence[str], state0: Any):
    """New log-probabilities, entropies and values ``[T, B]`` of a minibatch of rollouts
    from their carry ``state0``, and the model's counters (a dict, empty for most)."""
    if isinstance(agent, DecoderPPOAgent):
        (key,) = obs_keys
        ids = lambda x: jnp.swapaxes(x[..., 0], 0, 1).astype(jnp.int32)  # noqa: E731  [T, B, 1] -> [B, T]
        hidden, values, _, _, aux = agent.apply(
            params, ids(batch[key]), ids(batch["prev_actions"]), jnp.swapaxes(batch["is_first"][..., 0], 0, 1), state0
        )
        B, T, D = hidden.shape
        with scope("policy/head"):
            logprob, entropy = chunked_log_prob_and_entropy(
                hidden.reshape(B * T, D),
                decoder.head_of(params["params"]),
                ids(batch["actions"]).reshape(B * T),
                min(HEAD_CHUNK, B * T),
                agent.dtype,
            )
        return logprob.reshape(B, T).T, entropy.reshape(B, T).T, values.T, aux
    actor_out, values = agent.apply(
        params, {k: batch[k] for k in obs_keys}, batch["prev_actions"], batch["is_first"], state0
    )
    logprob, entropy = log_prob_and_entropy(actor_out, batch["actions"], agent.is_continuous)
    return logprob, entropy, values[..., 0], {}


def make_zero_state(cfg, dtype: Any = jnp.float32):
    """Per-env zero carry matching ``algo.sequence_model``: LSTM ``(c, h)``, the
    attention variant's ``(window, valid)`` rolling context, or the decoder's tree of
    per-layer caches (``models/decoder.py``; ``dtype``: what the caches hold)."""
    h = cfg.algo.rnn.lstm.hidden_size
    if cfg.algo.get("sequence_model", "lstm") == "decoder":
        dcfg = decoder.DecoderConfig.from_cfg(cfg.algo.decoder)

        def zero_state(n: int):
            return decoder.zero_state(dcfg, n, dtype)

    elif cfg.algo.get("sequence_model", "lstm") == "attention":
        w = int(cfg.algo.attention.window)

        def zero_state(n: int):
            return (jnp.zeros((n, w, h)), jnp.zeros((n, w)))

    else:

        def zero_state(n: int):
            return (jnp.zeros((n, h)), jnp.zeros((n, h)))

    return zero_state


def build_agent(ctx, action_space, obs_space, cfg) -> Tuple[RecurrentPPOAgent, Any]:
    sequence_model = cfg.algo.get("sequence_model", "lstm")
    if sequence_model == "decoder":
        return build_decoder_agent(ctx, action_space, obs_space, cfg)
    is_continuous, dims = parse_action_space(action_space)
    attention_fn = None
    if sequence_model == "attention" and ctx.mesh.shape.get("sequence", 1) > 1:
        from sheeprl_tpu.ops.ring_attention import make_ring_attention

        attention_fn = make_ring_attention(
            ctx.mesh, causal=True, window=int(cfg.algo.attention.window)
        )
    agent = RecurrentPPOAgent(
        cnn_keys=list(cfg.algo.cnn_keys.encoder),
        mlp_keys=list(cfg.algo.mlp_keys.encoder),
        action_dims=dims,
        is_continuous=is_continuous,
        cnn_stacked=any(len(obs_space[k].shape) == 4 for k in cfg.algo.cnn_keys.encoder),
        cnn_features_dim=cfg.algo.encoder.cnn_features_dim,
        mlp_features_dim=cfg.algo.encoder.mlp_features_dim,
        dense_units=cfg.algo.dense_units,
        mlp_layers=cfg.algo.mlp_layers,
        dense_act=cfg.algo.dense_act,
        layer_norm=cfg.algo.layer_norm,
        lstm_hidden_size=cfg.algo.rnn.lstm.hidden_size,
        pre_rnn_mlp=cfg.algo.rnn.pre_rnn_mlp.apply,
        post_rnn_mlp=cfg.algo.rnn.post_rnn_mlp.apply,
        sequence_model=sequence_model,
        attn_heads=int(cfg.algo.get("attention", {}).get("num_heads", 4)),
        attn_window=int(cfg.algo.get("attention", {}).get("window", 64)),
        attention_fn=attention_fn,
        dtype=ctx.compute_dtype,
    )
    dummy_obs = {}
    for k in list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder):
        space = obs_space[k]
        dummy_obs[k] = jnp.zeros((1, *space.shape), dtype=space.dtype)
    act_sum = int(sum(dims))
    state0 = make_zero_state(cfg)(1)
    params = agent.init(
        ctx.rng(), dummy_obs, jnp.zeros((1, act_sum)), jnp.ones((1, 1)), state0, method=RecurrentPPOAgent.step
    )
    params = ctx.replicate(params)
    return agent, params


def build_decoder_agent(ctx, action_space, obs_space, cfg) -> Tuple[DecoderPPOAgent, Any]:
    dcfg = decoder.DecoderConfig.from_cfg(cfg.algo.decoder)
    keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    if len(keys) != 1 or not isinstance(action_space, gymnasium.spaces.Discrete):
        raise ValueError(f"sequence_model=decoder reads one key of token ids and writes one token; got keys {keys}, actions {action_space}")
    if int(action_space.n) != dcfg.vocab_held or int(obs_space[keys[0]].high.max()) >= dcfg.vocab_held:
        raise ValueError(f"the environment's ids ({obs_space[keys[0]]}, {action_space}) are not the {dcfg.vocab_held} rows held of the tables")
    # the update's attention kernel is a custom call, which the partitioner does not split: it is told the mesh
    agent = DecoderPPOAgent(dcfg, ctx.compute_dtype, ctx.mesh if ctx.mesh.size > 1 else None)
    ids = jnp.zeros((1,), jnp.int32)
    state0 = decoder.zero_state(dcfg, 1, ctx.compute_dtype)
    # under jit, one compile: run eagerly, each of a step's ~300 operations (the attention kernel's too) is compiled on its own
    params = jax.jit(lambda key: agent.init(key, ids, ids, jnp.ones((1, 1)), state0, method=DecoderPPOAgent.step))(ctx.rng())
    return agent, ctx.replicate(params)
