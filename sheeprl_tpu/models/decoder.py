"""A sparse-expert decoder as a sequence policy: RMSNorm, rotary embedding, grouped-query
attention of a chunk against a carried cache, and an expert layer that is told which
experts it holds.

The layer (SmallThinker's, ``howto/decoder_policy.md`` gives the equations): the router
reads the layer's input (before the attention's norm) and keeps the ``experts_per_token``
largest of a softmax over all ``num_experts``, renormalised; attention is per layer
either full without positional encoding or windowed with RoPE; the feed-forward is a
ReGLU expert mixture.  A chip holds ``heads_held`` query heads, ``kv_heads_held`` key
heads, ``experts_held`` experts (``expert_offset`` onward) and ``vocab_held`` rows of
the tables: it routes over all the experts and computes its own experts' part of the
result, and what the absent heads and experts would add is left out.  No exchange
between chips is written here, and nothing stands in for the absent ones.

The carry is a tree: ``{"pos": [B], "layers": ({"k", "v", "pos"}, ...)}``.  ``pos`` is
the row's next position inside its episode; a layer's cache holds keys (rotated
already) and values in ``capacity`` slots (full layers) or ``window`` slots (a ring),
each with the position it holds (``-1``: empty), written at ``position % slots``.  The
shapes are static, so a step's cost does not depend on the fill.  A chunk of ``T``
tokens attends to the cache and to itself by position (``ops.ring_attention.
grouped_attention``): acting is the chunk of one token, whose keys are then written;
training reads the cache as it stood when the rollout began and writes nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from sheeprl_tpu.obs.perf import scope
from sheeprl_tpu.ops.ring_attention import grouped_attention


@dataclass(frozen=True)
class DecoderConfig:
    hidden_size: int
    head_dim: int
    heads_held: int
    kv_heads_held: int
    num_experts: int
    experts_held: int
    experts_per_token: int
    expert_width: int
    vocab_held: int
    layers: int
    window: int
    window_layout: Tuple[int, ...]  # per layer: 1 = sliding window, 0 = full attention
    rope_layout: Tuple[int, ...]  # per layer: 1 = rotary embedding, 0 = no positional encoding
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    expert_offset: int = 0
    capacity: int = 8192  # slots of a full-attention layer's cache

    @classmethod
    def from_cfg(cls, d: Any) -> "DecoderConfig":
        layers = int(d["layers"])
        cyc = lambda xs: tuple(int(xs[i % len(xs)]) for i in range(layers))  # noqa: E731
        return cls(
            hidden_size=int(d["hidden_size"]),
            head_dim=int(d["head_dim"]),
            heads_held=int(d["heads_held"]),
            kv_heads_held=int(d["kv_heads_held"]),
            num_experts=int(d["moe_num_primary_experts"]),
            experts_held=int(d["experts_held"]),
            experts_per_token=int(d["moe_num_active_primary_experts"]),
            expert_width=int(d["moe_ffn_hidden_size"]),
            vocab_held=int(d["vocab_held"]),
            layers=layers,
            window=int(d["sliding_window_size"]),
            window_layout=cyc(d["sliding_window_layout"]),
            rope_layout=cyc(d["rope_layout"]),
            rope_theta=float(d["rope_theta"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            expert_offset=int(d.get("expert_offset", 0)),
            capacity=int(d["cache_capacity"]),
        )

    def slots(self, layer: int) -> int:
        return self.window if self.window_layout[layer] else self.capacity


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps) * scale


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over all of a head's dimensions, half-rotation layout:
    ``x``: ``[B, T, H, D]``, ``pos``: ``[B, T]``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[..., None, None] * inv_freq  # [B, T, 1, half]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def route(x: jax.Array, w_router: jax.Array, k: int, renormalise: bool) -> Tuple[jax.Array, jax.Array]:
    """``x``: ``[N, D]`` -> weights and ids ``[N, k]`` of the ``k`` most probable of all the
    experts, in float32 (a tie flipped by rounding sends a token elsewhere)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    if renormalise:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_p, top_i


def _grouped(rows: jax.Array, w: jax.Array, group_sizes: jax.Array, valid: jax.Array) -> jax.Array:
    """``rows[i] @ w[group of i]``; the rows after the last group belong to no expert held
    here, and what the product leaves there is replaced by zeros.  The precision is
    stated: the operands are in the compute dtype already, and under the process-wide
    ``jax_default_matmul_precision`` that ``cli.run`` sets (``high``) the chip's grouped
    product gave the expert branch a quarter of its gradient (PERF.md, PR 28)."""
    precision = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jnp.where(valid, jax.lax.ragged_dot(rows, w, group_sizes, precision=precision), 0)


def expert_layer(
    m: jax.Array, top_w: jax.Array, top_i: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, offset: int, dtype: Any
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The part of the mixture that the experts held here give: ``m``: ``[N, D]`` (normed),
    ``top_w`` / ``top_i``: ``[N, K]`` over all the experts, ``w_*``: the held experts'
    weights ``[E_held, ...]``, which are experts ``offset .. offset + E_held - 1``.

    The ``N * K`` assignments are sorted by expert, those of experts not held last; the
    held ones' rows go through grouped products (``jax.lax.ragged_dot``: static shapes,
    groups as long as the routing makes them, so no capacity and no dropped token) and
    are added back into their tokens with the renormalised weights."""
    N, K = top_i.shape
    held_n = w_gate.shape[0]
    local = top_i - offset
    held = (local >= 0) & (local < held_n)
    group = jnp.where(held, local, held_n).reshape(-1)  # [N * K]; held_n: not held here
    order = jnp.argsort(group, stable=True)
    token = order // K
    group_sizes = jnp.sum(group[:, None] == jnp.arange(held_n)[None], 0, dtype=jnp.int32)
    n_held = group_sizes.sum()
    valid = (jnp.arange(N * K) < n_held)[:, None]
    rows = jnp.where(valid, m.astype(dtype)[token], 0)
    w_gate, w_up, w_down = w_gate.astype(dtype), w_up.astype(dtype), w_down.astype(dtype)
    h = jax.nn.relu(_grouped(rows, w_gate, group_sizes, valid)) * _grouped(rows, w_up, group_sizes, valid)
    y = _grouped(h, w_down, group_sizes, valid).astype(jnp.float32)
    weight = jnp.where(held, top_w, 0.0).reshape(-1)[order]
    out = jnp.zeros((N, m.shape[-1]), jnp.float32).at[token].add(y * weight[:, None])
    counters = {
        "held": held.sum().astype(jnp.float32),
        "load_max": group_sizes.max().astype(jnp.float32),
        "dropped": (held.sum() - n_held).astype(jnp.float32),
    }
    return out, counters


def positions(is_first: jax.Array, pos0: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``is_first``: ``[B, T]`` (1 where an episode starts), ``pos0``: ``[B]`` the next
    position of the carried episode -> each token's position inside its episode and its
    segment (0: the carried episode, which alone may read the cache)."""
    idx = jnp.arange(is_first.shape[1])[None]
    first = is_first > 0
    last_reset = jax.lax.cummax(jnp.where(first, idx, -1), axis=1)
    pos = jnp.where(last_reset >= 0, idx - last_reset, pos0[:, None] + idx)
    return pos.astype(jnp.int32), jnp.cumsum(first, 1, dtype=jnp.int32)


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    layer: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache_k, cache_v, cache_pos, q_pos, q_seg):
        """``x``: ``[B, T, D]`` float32 -> the layer's output, the chunk's keys and values
        ``[B, T, Hkv, hd]`` (for the cache) and the expert layer's counters."""
        c, dt = self.cfg, self.dtype
        D, hd, Hq, Hkv = c.hidden_size, c.head_dim, c.heads_held, c.kv_heads_held
        init = nn.initializers.normal(0.02)
        w_router = self.param("router", init, (D, c.num_experts))
        attn_norm = self.param("attn_norm", nn.initializers.ones, (D,))
        wq = self.param("wq", init, (D, Hq * hd))
        wk = self.param("wk", init, (D, Hkv * hd))
        wv = self.param("wv", init, (D, Hkv * hd))
        wo = self.param("wo", init, (Hq * hd, D))
        ffn_norm = self.param("ffn_norm", nn.initializers.ones, (D,))
        w_gate = self.param("w_gate", init, (c.experts_held, D, c.expert_width))
        w_up = self.param("w_up", init, (c.experts_held, D, c.expert_width))
        w_down = self.param("w_down", init, (c.experts_held, c.expert_width, D))

        B, T, _ = x.shape
        windowed = bool(c.window_layout[self.layer])
        with scope("policy/router"):
            top_w, top_i = route(x.reshape(B * T, D), w_router, c.experts_per_token, c.norm_topk_prob)
        with scope("policy/attention_window" if windowed else "policy/attention_full"):
            a = rms_norm(x, attn_norm, c.rms_norm_eps).astype(dt)
            q = jnp.dot(a, wq.astype(dt)).reshape(B, T, Hq, hd)
            k = jnp.dot(a, wk.astype(dt)).reshape(B, T, Hkv, hd)
            v = jnp.dot(a, wv.astype(dt)).reshape(B, T, Hkv, hd)
            if c.rope_layout[self.layer]:
                q, k = rope(q, q_pos, c.rope_theta), rope(k, q_pos, c.rope_theta)
            cache_seg = jnp.where(cache_pos >= 0, 0, -1)
            blocks = [(cache_k.astype(dt), cache_v.astype(dt), cache_pos, cache_seg), (k, v, q_pos, q_seg)]
            o = grouped_attention(q, blocks, q_pos, q_seg, c.window if windowed else None)
            h = x + jnp.dot(o.reshape(B, T, Hq * hd), wo.astype(dt), preferred_element_type=jnp.float32)
        with scope("policy/experts"):
            m = rms_norm(h, ffn_norm, c.rms_norm_eps).reshape(B * T, D)
            y, counters = expert_layer(m, top_w, top_i, w_gate, w_up, w_down, c.expert_offset, dt)
        return h + y.reshape(B, T, D), k, v, counters


class DecoderPolicy(nn.Module):
    """Token ids in, the final normed hidden state and a value out; the head's logits
    are formed by the caller (whole for one acting step, in token chunks for the
    update: ``algos/ppo/utils.py::chunked_log_prob_and_entropy``)."""

    cfg: DecoderConfig
    dtype: Any = jnp.float32

    def setup(self):
        c = self.cfg
        init = nn.initializers.normal(0.02)
        self.embed = self.param("embed", init, (c.vocab_held, c.hidden_size))
        # each layer is recomputed in the backward pass: its scores over the cache are not kept
        self.blocks = [nn.remat(DecoderLayer)(c, i, self.dtype, name=f"layers_{i}") for i in range(c.layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (c.hidden_size,))
        self.head = self.param("head", init, (c.hidden_size, c.vocab_held))
        self.value_w = self.param("value_w", nn.initializers.zeros, (c.hidden_size, 1))
        self.value_b = self.param("value_b", nn.initializers.zeros, (1,))

    def __call__(self, tokens, prev_actions, is_first, state):
        """``tokens`` / ``prev_actions``: ``[B, T]`` ids, ``is_first``: ``[B, T]``, ``state``:
        the carry -> ``(hidden [B, T, D] float32, values [B, T], (k, v) a layer, q_pos,
        counters)``.  Writes nothing."""
        c = self.cfg
        q_pos, q_seg = positions(is_first, state["pos"])
        with scope("policy/embed"):
            emb = self.embed.astype(jnp.float32)
            keep = (1.0 - is_first.astype(jnp.float32))[..., None]
            x = emb[tokens] + keep * emb[prev_actions]
        written, totals = [], None
        for block, cache in zip(self.blocks, state["layers"]):
            x, k, v, counters = block(x, cache["k"], cache["v"], cache["pos"], q_pos, q_seg)
            written.append((k, v))
            totals = counters if totals is None else jax.tree.map(jnp.add, totals, counters)
        with scope("policy/head"):
            hidden = rms_norm(x, self.final_norm, c.rms_norm_eps)
            values = (jnp.dot(hidden, self.value_w.astype(jnp.float32)) + self.value_b)[..., 0]
        assigned = float(tokens.size * c.experts_per_token * c.layers)
        aux = {
            "MoE/held_share": totals["held"] / assigned,
            "MoE/load_max_over_mean": totals["load_max"] * c.experts_held / jnp.maximum(totals["held"], 1.0),
            "MoE/dropped": totals["dropped"],
        }
        return hidden, values, written, q_pos, aux

    def logits(self, hidden):
        with scope("policy/head"):
            return jnp.dot(hidden.astype(self.dtype), self.head.astype(self.dtype), preferred_element_type=jnp.float32)

    def step(self, tokens, prev_actions, is_first, state):
        """One acting step over all rows: ``tokens`` / ``prev_actions``: ``[B]`` ids,
        ``is_first``: ``[B, 1]`` -> ``([logits [B, V]], value [B, 1], new state)``."""
        first = is_first[:, 0] > 0
        # an episode that starts forgets the one before it: its slots are empty from here on
        state = {
            "pos": jnp.where(first, 0, state["pos"]),
            "layers": tuple({**cache, "pos": jnp.where(first[:, None], -1, cache["pos"])} for cache in state["layers"]),
        }
        hidden, values, written, q_pos, _ = self(tokens[:, None], prev_actions[:, None], is_first, state)
        rows, pos = jnp.arange(tokens.shape[0]), q_pos[:, 0]
        layers = []
        for cache, (k, v) in zip(state["layers"], written):
            slot = pos % cache["pos"].shape[1]
            layers.append(
                {
                    "k": cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype)),
                    "v": cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype)),
                    "pos": cache["pos"].at[rows, slot].set(pos),
                }
            )
        return [self.logits(hidden[:, 0])], values, {"pos": pos + 1, "layers": tuple(layers)}


def zero_state(sizes: DecoderConfig, n: int, dtype: Any) -> Dict[str, Any]:
    """The carry of ``n`` rows before their first token: every slot empty."""
    layers = []
    for i in range(sizes.layers):
        shape = (n, sizes.slots(i), sizes.kv_heads_held, sizes.head_dim)  # a buffer each: the acting step is given them to overwrite
        layers.append({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), "pos": jnp.full(shape[:2], -1, jnp.int32)})
    return {"pos": jnp.zeros((n,), jnp.int32), "layers": tuple(layers)}


MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "head")


def cast_matmul_weights(params: Any, dtype: Any) -> Any:
    """The weights that the policy multiplies in ``dtype``, cast once (the acting steps of
    a rollout then read half the bytes); the tables, norms, router and value head stay."""
    def cast(path: Sequence[Any], x: jax.Array) -> jax.Array:
        return x.astype(dtype) if getattr(path[-1], "key", None) in MATMUL_WEIGHTS else x

    return jax.tree_util.tree_map_with_path(cast, params)
