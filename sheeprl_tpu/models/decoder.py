"""A sparse-expert decoder as a sequence policy: RMSNorm, rotary embedding, grouped-query
attention of a chunk against a carried cache, latent attention over a compressed cache
that keys and values share, a gated short convolution with a carried tail, a Mamba-2
state-space mixer with a carried state, a dense or an expert feed-forward, and an expert
layer that is told which experts it holds.

``DecoderConfig`` describes layer kinds, not one model (``howto/decoder_policy.md`` gives
the equations of the four published models that run on it).  A layer has two parts, ``h = x +
Mixer(norm(x))``, ``out = h + FFN(norm(h))``, or (``hybrid_override_pattern``) one of them
alone: ``out = x + Mixer(norm(x))`` (``ffn_layout`` 0) or ``out = x + FFN(norm(x))`` (mixer
``none``).  Its *mixer* is per layer (``mixers``) full
attention, sliding-window attention, latent attention, a gated short convolution, or a
Mamba-2 mixer; attention may norm each head's queries and keys (``qk_norm``) and rotates them where
``rope_layout`` says.  Its *feed-forward* is dense in the ``dense_layers`` leading layers and
an expert mixture after them, gated by ``activation`` or (``relu2``) not gated, with (``shared_width``) a dense part
beside it that every token passes.  The *router* keeps the ``experts_per_token``
largest of a softmax over all ``num_experts``, or (``router="sigmoid"``) of sigmoid scores
plus a selection bias that the weights do not see, and scales the kept weights by
``routed_scale``; it reads the layer's input or the normed state the experts read
(``router_reads``).  The head is a table of its own or the
embedding's (``tie_embeddings``).  A chip holds ``heads_held`` query heads,
``kv_heads_held`` key heads, ``experts_held`` experts (``expert_offset`` onward) and
``vocab_held`` rows of the tables: it routes over all the experts and computes its own
experts' part of the result, and what the absent heads and experts would add is left
out.  No exchange between chips is written here, and nothing stands in for the absent
ones.

*Latent attention* (MLA) as published: ``q = a W_q`` by head ``[q_nope, q_pe]``; ``[c_raw,
k_pe] = a W_kv_a``; ``c = norm(c_raw)``; ``[k_nope_h, v_h] = split(c W_kv_b)``; the rotation on
every ``q_pe`` and on the one ``k_pe`` all heads share; scores of ``[q_nope_h, q_pe_h]``
against ``[k_nope_h, k_pe]`` over ``sqrt(qk_nope_head_dim + qk_rope_head_dim)``.  What runs
here, on both paths, is a regrouping of those sums that never forms a cached row's
per-head keys and values: with ``W_kv_b = [W_uk | W_uv]`` by head, ``q_nope . (W_uk c) =
(W_uk^T q_nope) . c`` and ``sum_s p_s (W_uv c_s) = W_uv (sum_s p_s c_s)``, so a head's query
is ``[W_uk^T q_nope, q_pe]``, the row ``[c, k_pe]`` is the one key head of all the query
heads, its first ``kv_lora_rank`` columns are the values, and ``W_uv`` meets the weighted
sum.  A row is kept as ``[c, k_pe, zeros]``, ``latent_width`` wide (whole lanes); the
queries are padded alike.  ``W_kv_b`` is a weight wherever it multiplies, so it takes
gradient through the cached rows a query sees, while the cache itself takes none.

The *Mamba-2 mixer* (NemotronH's): ``[z | xBC | dt] = a W_in`` (``mamba_heads x mamba_head_dim``,
that plus ``2 x ssm_groups x ssm_state``, ``mamba_heads`` wide); ``xBC = silu(taps(xBC) + bias)``
over ``conv_taps`` inputs (``causal_taps``); ``xBC = [x | B | C]``; per head ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the scan of ``ops/ssd_scan.py`` (chunked over
``ssm_chunk`` tokens for a chunk, the one-token recurrence for an acting step) plus ``D x``;
then ``y = GroupRMSNorm(y * silu(z))`` over ``ssm_groups`` groups and ``out = y W_out``.

The carry is a tree: ``{"pos": [B], "layers": (state of layer 0, ...)}`` with a layer's
state by its mixer's kind: ``{"k", "v", "pos"}``, ``{"latent": [B, capacity, latent_width],
"pos"}``, ``{"conv": [B, taps - 1, D]}`` or ``{"ssm": [B, heads, head_dim, state], "conv":
[B, taps - 1, width of xBC]}`` (the state in float32, the tail in the compute dtype); a layer
without a mixer carries ``{}``.
``pos`` is the row's next position inside its episode; an attention layer's cache holds
keys (rotated already) and values in ``capacity`` slots (full layers) or ``window`` slots
(a ring), each with the position it holds (``-1``: empty), written at ``position %
slots`` (``k`` and ``v`` are one array ``[B, slots, Hkv, hd]``, or, for heads narrower than
the chip's ``LANES``, a tuple of ``[B, slots, 1, LANES]``: ``lane_grouped_attention``); a
convolution's tail holds the last ``taps - 1`` inputs of its taps in the row's
episode, oldest first (zeros before its first token); a state-space layer's state is the
decayed sum of its episode so far.  The shapes are static, so a
step's cost does not depend on the fill.  A chunk of ``T`` tokens attends to the cache
and to itself by position (``ops.ring_attention.grouped_attention``), convolves over
the tail and itself by segment and scans from the carried state by segment: acting is the
chunk of one token, whose keys, taps' input and state are then written; training reads
the carry as it stood when the rollout began and writes nothing.  An episode that starts
empties its row of every layer's state.

Attention takes one of two programs by the shape of its call.  A chunk (the update) goes
blockwise through the cache, a key block at a time with an online softmax, the scores
never leaving the chip and a block that the row has not filled (or that lies out of a
window's reach) not visited at all (``ops/blockwise_attention.py``); the chunk's own keys
are merged in afterwards.  One token a row (acting, the bootstrap value) goes the same
way where its query rows a key head fill a whole bfloat16 tile (a latent layer's sixteen
heads on its one key head), and otherwise forms its float32 scores over every slot whole.
The update reports the share of key blocks it visited (``Attn/key_blocks_visited_share``);
the trace notes ``blockwise_attention``, the acting call's tile too (``act_layer_<n>``).
A chunk through a state-space layer reports the share of its scan's (row, chunk) pairs
that an episode's start cuts (``SSM/resets_in_chunk_share``); the trace notes ``ssd_scan``.

The expert layer, too, takes one of two programs by the shape of its call: a few tokens
(an acting step's one a row) go through every held expert in one batched product a weight,
which reads each expert's weights once; many (the update's chunk) go through grouped
products of the tokens each expert was chosen for (``EVERY_HELD_TOKENS``).  The trace notes
each layer's (``expert_path``: ``act_layer_<n>`` for one token a row, ``layer_<n>`` for a chunk).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from sheeprl_tpu.obs.perf import note, scope
from sheeprl_tpu.ops.ring_attention import grouped_attention
from sheeprl_tpu.ops.ssd_scan import chunks_of, resets_in_chunks, ssd_scan, ssd_step


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
    mixers: Tuple[str, ...]  # per layer: "full" | "window" (attention) | "latent" (attention over a compressed cache) | "conv" (gated short convolution) | "mamba" (Mamba-2) | "none"
    rope_layout: Tuple[int, ...]  # per attention layer: 1 = rotary embedding, 0 = no positional encoding
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    expert_offset: int = 0
    capacity: int = 8192  # slots of a full-attention layer's cache
    conv_taps: int = 3  # a convolution's kernel (a conv layer's, a Mamba-2 mixer's); it carries ``conv_taps - 1`` inputs
    qk_norm: bool = False  # RMSNorm of each head's queries and keys before the rotation
    dense_layers: int = 0  # leading layers whose feed-forward is dense, ``dense_width`` wide
    dense_width: int = 0
    router: str = "softmax"  # "sigmoid": chosen by score + ``expert_bias``, weighted by the score alone
    router_reads: str = "input"  # "input": the layer's input | "ffn_norm": the normed state the experts read
    activation: str = "relu"  # the gate of the feed-forward: "relu" (ReGLU) | "silu" (SwiGLU) | "relu2" (no gate: relu(m W_up)^2 W_down)
    tie_embeddings: bool = False  # the head is the embedding table
    # a "latent" layer: what it compresses keys and values to, a head's part without and with rotation, a head's values
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    shared_width: int = 0  # of the dense feed-forward that every token passes beside its routed experts (0: none)
    routed_scale: float = 1.0  # on the routed experts' weights after their renormalisation; the shared part is not scaled
    ffn_layout: Tuple[int, ...] = ()  # per layer: 1 = it has a feed-forward part, 0 = its mixer alone; () = every layer has one
    # a "mamba" layer: heads of the scan and their width, groups of B and C, the state's width a head, the update's scan chunk
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_chunk: int = 128

    @classmethod
    def from_cfg(cls, d: Any) -> "DecoderConfig":
        layers = int(d["layers"])
        cyc = lambda xs: tuple(xs[i % len(xs)] for i in range(layers))  # noqa: E731
        ffn_layout: Tuple[int, ...] = ()
        if d.get("hybrid_override_pattern"):  # one part a layer, by the published pattern's first ``layers`` blocks
            pattern = str(d["hybrid_override_pattern"])
            if len(pattern) < layers or set(pattern) - set(PATTERN):
                raise ValueError(f"hybrid_override_pattern {pattern!r} does not give {layers} blocks of {sorted(PATTERN)}")
            mixers, ffn_layout = (tuple(part) for part in zip(*(PATTERN[ch] for ch in pattern[:layers])))
        elif d.get("layer_types") is not None:
            mixers = tuple(LAYER_TYPES[t] for t in cyc(d["layer_types"]))
        else:
            mixers = tuple("window" if w else "full" for w in cyc(d["sliding_window_layout"]))
        shared = d.get("moe_shared_expert_intermediate_size")  # published where the shared expert is not a multiple of a routed one
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
            mixers=mixers,
            rope_layout=tuple(int(r) for r in cyc(d["rope_layout"])),
            rope_theta=float(d["rope_theta"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            expert_offset=int(d.get("expert_offset", 0)),
            capacity=int(d["cache_capacity"]),
            conv_taps=int(d["conv_L_cache"]),
            qk_norm=bool(d["qk_norm"]),
            dense_layers=int(d["num_dense_layers"]),
            dense_width=int(d["intermediate_size"]),
            router=str(d["router"]),
            router_reads=str(d["router_reads"]),
            activation=str(d["activation"]),
            tie_embeddings=bool(d["tie_embeddings"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            shared_width=int(shared) if shared else int(d["n_shared_experts"]) * int(d["moe_ffn_hidden_size"]),
            routed_scale=float(d["routed_scaling_factor"]),
            ffn_layout=ffn_layout,
            mamba_heads=int(d.get("mamba_num_heads", 0)),
            mamba_head_dim=int(d.get("mamba_head_dim", 0)),
            ssm_groups=int(d.get("n_groups", 0)),
            ssm_state=int(d.get("ssm_state_size", 0)),
            ssm_chunk=int(d.get("chunk_size", 128)),
        )

    def slots(self, layer: int) -> int:
        return self.window if self.mixers[layer] == "window" else self.capacity

    def feed_forward(self, layer: int) -> bool:
        """Whether the layer has a feed-forward part."""
        return not self.ffn_layout or bool(self.ffn_layout[layer])

    @property
    def expert_layers(self) -> int:
        return sum(self.feed_forward(layer) for layer in range(min(self.dense_layers, self.layers), self.layers))

    @property
    def ssm_conv_width(self) -> int:
        """What a Mamba-2 mixer's taps convolve: ``x``, then ``B`` and ``C`` of every group."""
        return self.mamba_heads * self.mamba_head_dim + 2 * self.ssm_groups * self.ssm_state

    @property
    def lane_groups(self) -> int:
        """How many arrays a cache's keys (and values) are kept in: one, or, where a head is
        narrower than ``LANES`` and the held key heads fill whole lanes, one a lane-full."""
        width = self.kv_heads_held * self.head_dim
        return width // LANES if self.head_dim < LANES and LANES % self.head_dim == 0 and width % LANES == 0 else 1

    @property
    def latent_width(self) -> int:
        """What a slot of a latent layer's cache holds: the normed latent, then the rotated
        key that the heads share, then zeros up to whole ``LANES``."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // LANES) * LANES


#: the published configs' names for a layer's mixer -> ``DecoderConfig.mixers``
LAYER_TYPES = {"conv": "conv", "full_attention": "full", "sliding_attention": "window", "latent_attention": "latent"}
#: a block of a published ``hybrid_override_pattern`` (NemotronH's) -> its mixer and whether it has a feed-forward part
PATTERN = {"M": ("mamba", 0), "*": ("full", 0), "E": ("none", 1)}
#: the feed-forward's activation.  "relu" and "silu" gate: ``(act(m W_gate) * (m W_up)) W_down``;
#: those of ``UNGATED`` have no gate: ``act(m W_up) W_down`` ("relu2": relu squared)
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu, "relu2": lambda v: jnp.square(jax.nn.relu(v))}
UNGATED = ("relu2",)
ROUTER_EPS = 1e-6  # in the denominator of the sigmoid router's renormalisation
#: the minor axis of the chip's memory tiles: a cache whose rows are narrower is given another
#: layout on the device, and the acting step's one-row write then copies the cache whole, twice
LANES = 128
#: the most tokens an expert layer's call puts through every held expert (``expert_path``).  A
#: product of N tokens does 2N flops for each weight it reads, N flops a byte of bfloat16;
#: under the chip's ridge (TPU v5e: 197e12 flop/s over 819e9 B/s, ~240 flops a byte) it waits
#: on the read, so every held expert over every token costs one read of the held weights,
#: where grouped products walk a row tile (up to 512 rows) an expert that few tokens leave
#: mostly empty.  128 leaves room under the ridge; an acting step's 32 or 64 tokens lie under
#: it, an update's thousands far over it, and those keep the grouped products.
EVERY_HELD_TOKENS = 128


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` of head ``h`` is ``-(h + 1)``: the family's initialisation."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of steps spread geometrically over the family's ``time_step_min``
    .. ``time_step_max`` (0.001 .. 0.1)."""
    step = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), shape[0])).astype(dtype)
    return step + jnp.log(-jnp.expm1(-step))


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


def route(
    x: jax.Array, w_router: jax.Array, k: int, renormalise: bool, expert_bias: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """``x``: ``[N, D]`` -> weights and ids ``[N, k]`` of the ``k`` chosen of all the
    experts, in float32 (a tie flipped by rounding sends a token elsewhere).  Without
    ``expert_bias`` the most probable of a softmax; with it (``[E]``, no trained weight)
    sigmoid scores, the ``k`` largest of score + bias chosen and weighted by the score
    alone (renormalised: over their sum + ``ROUTER_EPS``).  Third: ``[N]``, whether the bias changed a token's chosen set (``None``
    without a bias)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    if expert_bias is None:
        top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        if renormalise:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        return top_p, top_i, None
    score = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(score + jax.lax.stop_gradient(expert_bias.astype(jnp.float32)), k)
    top_p = jnp.take_along_axis(score, top_i, -1)
    if renormalise:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + ROUTER_EPS)
    _, unbiased = jax.lax.top_k(score, k)
    moved = jnp.any(jnp.sort(top_i, -1) != jnp.sort(unbiased, -1), -1)
    return top_p, top_i, moved


def _precision(x: jax.Array):
    """Stated where a product runs: the process-wide ``jax_default_matmul_precision`` that
    ``cli.run`` sets (``high``) is not left to decide (PERF.md, PR 28).  Operands already
    in a narrow compute dtype multiply as they are; float32 ones in full."""
    return jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _dot(a: jax.Array, w: jax.Array, **kwargs) -> jax.Array:
    return jnp.dot(a, w, precision=_precision(a), **kwargs)


def _grouped(rows: jax.Array, w: jax.Array, group_sizes: jax.Array, valid: jax.Array) -> jax.Array:
    """``rows[i] @ w[group of i]``; the rows after the last group belong to no expert held
    here, and what the product leaves there is replaced by zeros.  The precision is
    stated: the operands are in the compute dtype already, and under the process-wide
    ``jax_default_matmul_precision`` that ``cli.run`` sets (``high``) the chip's grouped
    product gave the expert branch a quarter of its gradient (PERF.md, PR 28)."""
    return jnp.where(valid, jax.lax.ragged_dot(rows, w, group_sizes, precision=_precision(rows)), 0)


def expert_path(tokens: int) -> str:
    """Which of ``expert_layer``'s two programs a call of ``tokens`` tokens takes."""
    return "every_held" if tokens <= EVERY_HELD_TOKENS else "grouped"


def _hidden(activation: Callable[[jax.Array], jax.Array], product: Callable[[jax.Array], jax.Array], w_gate: Optional[jax.Array], w_up: jax.Array) -> jax.Array:
    """A feed-forward's hidden activations from ``product(w)``, the input times ``w``: gated,
    ``activation(x W_gate) * (x W_up)``, or without a gate (``w_gate`` ``None``), ``activation(x W_up)``."""
    if w_gate is None:
        return activation(product(w_up))
    return activation(product(w_gate)) * product(w_up)


def _every_held(
    m: jax.Array, top_w: jax.Array, local: jax.Array, w_gate: Optional[jax.Array], w_up: jax.Array, w_down: jax.Array,
    activation: Callable[[jax.Array], jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:  # fmt: skip
    """``expert_layer`` for few tokens: every token through every held expert, one batched
    product a weight (the experts the batch dimension of both operands, so each expert's
    weights are read once), the down product in the compute dtype as the grouped one leaves
    it; then, in float32, weighted by ``top_w`` where the token chose the expert, by 0 where
    it did not, and summed over the experts.  ``m`` and ``w_*`` are in the compute dtype."""
    chosen = local[..., None] == jnp.arange(w_up.shape[0])  # [N, K, E_held]
    weight = jnp.where(chosen, top_w[..., None], 0.0).sum(1)  # [N, E_held]: one choice an expert at most
    rows = jnp.broadcast_to(m, (w_up.shape[0], *m.shape))
    product = lambda a, w: jnp.einsum("end,edf->enf", a, w, precision=_precision(a))  # noqa: E731
    y = product(_hidden(activation, lambda w: product(rows, w), w_gate, w_up), w_down).astype(jnp.float32)
    load = chosen.sum((0, 1))
    counters = {"held": load.sum().astype(jnp.float32), "load_max": load.max().astype(jnp.float32), "dropped": jnp.float32(0.0)}
    return jnp.sum(y * weight.T[..., None], 0), counters


def expert_layer(
    m: jax.Array, top_w: jax.Array, top_i: jax.Array, w_gate: Optional[jax.Array], w_up: jax.Array, w_down: jax.Array, offset: int, dtype: Any,
    activation: Callable[[jax.Array], jax.Array] = jax.nn.relu,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:  # fmt: skip
    """The part of the mixture that the experts held here give: ``m``: ``[N, D]`` (normed),
    ``top_w`` / ``top_i``: ``[N, K]`` over all the experts, ``w_*``: the held experts'
    weights ``[E_held, ...]``, which are experts ``offset .. offset + E_held - 1``; without
    ``w_gate`` (``None``) an expert is not gated: ``activation(m W_up) W_down``.

    The program follows from ``N`` (``expert_path``).  Over ``EVERY_HELD_TOKENS`` the ``N *
    K`` assignments are sorted by expert, those of experts not held last; the held ones'
    rows go through grouped products (``jax.lax.ragged_dot``: static shapes, groups as long
    as the routing makes them, so no capacity and no dropped token) and are added back
    into their tokens with the renormalised weights.  Up to it every token goes through
    every held expert (``_every_held``): the same products of each (token, chosen expert)
    in the same dtypes, and the others multiplied by 0."""
    N, K = top_i.shape
    held_n = w_up.shape[0]
    local = top_i - offset
    held = (local >= 0) & (local < held_n)
    cast = lambda w: None if w is None else w.astype(dtype)  # noqa: E731
    if expert_path(N) == "every_held":
        w_gate, w_up, w_down = cast(w_gate), w_up.astype(dtype), w_down.astype(dtype)
        return _every_held(m.astype(dtype), top_w, local, w_gate, w_up, w_down, activation)
    group = jnp.where(held, local, held_n).reshape(-1)  # [N * K]; held_n: not held here
    order = jnp.argsort(group, stable=True)
    token = order // K
    group_sizes = jnp.sum(group[:, None] == jnp.arange(held_n)[None], 0, dtype=jnp.int32)
    n_held = group_sizes.sum()
    valid = (jnp.arange(N * K) < n_held)[:, None]
    rows = jnp.where(valid, m.astype(dtype)[token], 0)
    w_gate, w_up, w_down = cast(w_gate), w_up.astype(dtype), w_down.astype(dtype)
    h = _hidden(activation, lambda w: _grouped(rows, w, group_sizes, valid), w_gate, w_up)
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
    segment (0: the carried episode, which alone may read the carried state)."""
    idx = jnp.arange(is_first.shape[1])[None]
    first = is_first > 0
    last_reset = jax.lax.cummax(jnp.where(first, idx, -1), axis=1)
    pos = jnp.where(last_reset >= 0, idx - last_reset, pos0[:, None] + idx)
    return pos.astype(jnp.int32), jnp.cumsum(first, 1, dtype=jnp.int32)


def causal_taps(z: jax.Array, tail: jax.Array, kernel: jax.Array, q_seg: jax.Array) -> jax.Array:
    """``c_t = sum_j kernel[j] * z_{t-j}`` per channel, in float32.  ``z``: ``[B, T, D]``,
    ``tail``: ``[B, J - 1, D]`` the carried episode's last gated inputs (oldest first),
    ``kernel``: ``[J, D]``, ``q_seg``: ``[B, T]``.  A tap reaches a token of its own
    segment only: no tap crosses an episode's start, and the tail is segment 0's."""
    T, back = z.shape[1], tail.shape[1]
    zs = jnp.concatenate([tail.astype(z.dtype), z], 1)
    segs = jnp.concatenate([jnp.zeros((z.shape[0], back), q_seg.dtype), q_seg], 1)
    out = jnp.zeros(z.shape, jnp.float32)
    for j in range(back + 1):
        same = (segs[:, back - j : back - j + T] == q_seg)[..., None]
        out = out + kernel[j].astype(jnp.float32) * jnp.where(same, zs[:, back - j : back - j + T], 0).astype(jnp.float32)
    return out


def lane_grouped_attention(q, k, v, cache, cache_seg, q_pos, q_seg, window, mesh=None):
    """``grouped_attention`` over a cache kept a lane-full of key heads an array
    (``cache["k"]``, ``cache["v"]``: tuples of ``[B, slots, 1, LANES]``, each holding
    ``LANES // hd`` heads side by side).  Each array is attended as one wide key head:
    a query head is padded with zeros to the lane's width, its own dimensions where its
    key head lies, so its scores are its own head's (the zeros add nothing) and of the
    weighted values it keeps its own head's part.  ``q``: ``[B, T, Hq, hd]``, ``k`` /
    ``v``: the chunk's own ``[B, T, Hkv, hd]`` -> ``[B, T, Hq, hd]`` and what a blockwise call
    visited (every array's is the same: the flags are the positions', the tile the shapes')."""
    B, T, Hq, hd = q.shape
    Hkv, P = k.shape[2], len(cache["k"])
    per, G = Hkv // P, Hq // Hkv  # key heads a lane-full, query heads a key head
    eye = jnp.eye(per, dtype=q.dtype)
    wide = jnp.einsum("btpagd,aj->btpagjd", q.reshape(B, T, P, per, G, hd), eye).reshape(B, T, P, per * G, per * hd)
    k, v = k.reshape(B, T, P, 1, per * hd), v.reshape(B, T, P, 1, per * hd)
    outs = []
    for j in range(P):
        held = (cache["k"][j].astype(q.dtype), cache["v"][j].astype(q.dtype), cache["pos"], cache_seg)
        o, visited = grouped_attention(wide[:, :, j], k[:, :, j], v[:, :, j], held, q_pos, q_seg, window, hd, mesh)
        outs.append(jnp.einsum("btagjd,aj->btagd", o.reshape(B, T, per, G, per, hd), eye))
    return jnp.stack(outs, 2).reshape(B, T, Hq, hd), visited


def _blocks_visited(visited, layer: int, tokens: int) -> Dict[str, jax.Array]:
    """The counters of a layer whose chunk went blockwise through its cache (none where
    ``grouped_attention`` says nothing of blocks, nor for one token a row: an acting step
    hands back nothing more), and the call's facts noted under the layer."""
    if visited is None:
        return {}
    flags, how = visited
    if tokens == 1:
        note("blockwise_attention", {f"act_layer_{layer}": how})
        return {}
    note("blockwise_attention", {f"layer_{layer}": how})
    return {"key_blocks": jnp.float32(flags.size), "key_blocks_visited": flags.sum().astype(jnp.float32)}


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    layer: int
    dtype: Any = jnp.float32
    mesh: Any = None  # the devices the rows are spread over, if several (``grouped_attention``)

    @nn.compact
    def __call__(self, x, state, q_pos, q_seg):
        """``x``: ``[B, T, D]`` float32, ``state``: the layer's carried state -> the layer's
        output, what the chunk made for that state (keys and values ``[B, T, Hkv, hd]``,
        the gated inputs ``[B, T, D]``, or a state-space mixer's state after the chunk and
        its taps' inputs) and the layer's counters: the expert layer's, and the key blocks
        of its cache that a chunk's attention had and visited."""
        c, dt = self.cfg, self.dtype
        D, hd, Hq, Hkv = c.hidden_size, c.head_dim, c.heads_held, c.kv_heads_held
        init = nn.initializers.normal(0.02)
        kind = c.mixers[self.layer]
        dense = self.layer < c.dense_layers
        act = ACTIVATIONS[c.activation]
        B, T, _ = x.shape
        counters = {}

        def routed(r):  # [B * T, D], as the router reads it
            w_router = self.param("router", init, (D, c.num_experts))
            bias = self.param("expert_bias", nn.initializers.zeros, (c.num_experts,)) if c.router == "sigmoid" else None
            with scope("policy/router"):
                top_w, top_i, moved = route(r, w_router, c.experts_per_token, c.norm_topk_prob, bias)
                return top_w * c.routed_scale, top_i, moved

        if not dense and c.router_reads == "input" and c.feed_forward(self.layer):
            top_w, top_i, moved = routed(x.reshape(B * T, D))
        if kind == "conv":
            conv_norm = self.param("conv_norm", nn.initializers.ones, (D,))
            conv_in = self.param("conv_in", init, (D, 3 * D))
            conv_kernel = self.param("conv_kernel", init, (c.conv_taps, D))
            conv_out = self.param("conv_out", init, (D, D))
            with scope("policy/conv"):
                a = rms_norm(x, conv_norm, c.rms_norm_eps).astype(dt)
                gate_in, gate_out, u = jnp.split(_dot(a, conv_in.astype(dt)), 3, -1)
                z = gate_in * u  # in the compute dtype: what the tail carries is what the chunk reads
                y = (gate_out.astype(jnp.float32) * causal_taps(z, state["conv"], conv_kernel, q_seg)).astype(dt)
                h = x + _dot(y, conv_out.astype(dt), preferred_element_type=jnp.float32)
            made = {"conv": z}
        elif kind == "latent":
            r, dn, dr, dv = c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
            attn_norm = self.param("attn_norm", nn.initializers.ones, (D,))
            wq = self.param("wq", init, (D, Hq * (dn + dr)))
            wkv_a = self.param("wkv_a", init, (D, r + dr))
            kv_norm = self.param("kv_norm", nn.initializers.ones, (r,))
            wkv_b = self.param("wkv_b", init, (r, Hq * (dn + dv)))
            wo = self.param("wo", init, (Hq * dv, D))
            with scope("policy/attention_latent"):
                a = rms_norm(x, attn_norm, c.rms_norm_eps).astype(dt)
                q_nope, q_pe = jnp.split(_dot(a, wq.astype(dt)).reshape(B, T, Hq, dn + dr), [dn], -1)
                down = _dot(a, wkv_a.astype(dt))
                latent = rms_norm(down[..., :r], kv_norm, c.rms_norm_eps).astype(dt)[:, :, None]  # [B, T, 1, r]
                q_pe, k_pe = rope(q_pe, q_pos, c.rope_theta), rope(down[:, :, None, r:], q_pos, c.rope_theta)
                # the up-projection is folded into the queries and the output: the cache is attended as it is kept
                w_uk, w_uv = jnp.split(wkv_b.astype(dt).reshape(r, Hq, dn + dv), [dn], -1)
                q_latent = jnp.einsum("bthd,rhd->bthr", q_nope, w_uk, precision=_precision(q_nope)).astype(dt)
                lanes = lambda *parts: jnp.concatenate([*parts, jnp.zeros((*parts[0].shape[:3], c.latent_width - r - dr), dt)], -1)  # noqa: E731
                own = lanes(latent, k_pe)  # [B, T, 1, latent_width]: one key head, whose values are its first r columns
                kept = state["latent"].astype(dt)[:, :, None]  # [B, slots, 1, latent_width]: the keys, whose first r columns are the values
                held = (kept, None, state["pos"], jnp.where(state["pos"] >= 0, 0, -1))
                o, visited = grouped_attention(lanes(q_latent, q_pe), own, latent, held, q_pos, q_seg, None, dn + dr, self.mesh)
                counters = _blocks_visited(visited, self.layer, T)
                o = jnp.einsum("bthr,rhd->bthd", o, w_uv, precision=_precision(o)).astype(dt)
                h = x + _dot(o.reshape(B, T, Hq * dv), wo.astype(dt), preferred_element_type=jnp.float32)
            made = {"latent": own[:, :, 0]}
        elif kind == "mamba":
            H, P, G, N = c.mamba_heads, c.mamba_head_dim, c.ssm_groups, c.ssm_state
            inner, width = H * P, c.ssm_conv_width
            mamba_norm = self.param("mamba_norm", nn.initializers.ones, (D,))
            mamba_in = self.param("mamba_in", init, (D, inner + width + H))
            mamba_conv = self.param("mamba_conv", init, (c.conv_taps, width))
            mamba_conv_bias = self.param("mamba_conv_bias", nn.initializers.zeros, (width,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (H,))
            a_log = self.param("A_log", _a_log_init, (H,))
            d_skip = self.param("D", nn.initializers.ones, (H,))
            gate_norm = self.param("mamba_gate_norm", nn.initializers.ones, (inner,))
            mamba_out = self.param("mamba_out", init, (inner, D))
            with scope("policy/mamba"):
                a = rms_norm(x, mamba_norm, c.rms_norm_eps).astype(dt)
                z, xbc, dt_in = jnp.split(_dot(a, mamba_in.astype(dt)), [inner, inner + width], -1)  # xbc in the compute dtype, as the tail carries it
                taps = jax.nn.silu(causal_taps(xbc, state["conv"], mamba_conv, q_seg) + mamba_conv_bias)
                xs, b_in, c_in = jnp.split(taps, [inner, inner + G * N], -1)
                xs, b_in, c_in = xs.reshape(B, T, H, P), b_in.reshape(B, T, G, N), c_in.reshape(B, T, G, N)
                step_dt = jax.nn.softplus(dt_in.astype(jnp.float32) + dt_bias)
                A = -jnp.exp(a_log.astype(jnp.float32))
            with scope("policy/ssd_scan"):
                if T == 1:  # an acting step: the recurrence on the carried state, empty where the episode starts
                    start = jnp.where((q_seg[:, 0] == 0)[:, None, None, None], state["ssm"], 0.0)
                    y, ssm = ssd_step(xs[:, 0], step_dt[:, 0], A, b_in[:, 0], c_in[:, 0], start, dt)
                    y = y[:, None]
                else:
                    y, ssm = ssd_scan(xs, step_dt, A, b_in, c_in, q_seg, state["ssm"], c.ssm_chunk, dt)
                    Q = min(c.ssm_chunk, T)
                    note("ssd_scan", {f"layer_{self.layer}": {"chunk": Q, "chunks": chunks_of(T, Q)}})
            with scope("policy/mamba"):
                y = (y + d_skip[:, None] * xs) * jax.nn.silu(z.astype(jnp.float32)).reshape(B, T, H, P)
                y = rms_norm(y.reshape(B, T, G, inner // G), gate_norm.reshape(G, inner // G), c.rms_norm_eps)
                h = x + _dot(y.reshape(B, T, inner).astype(dt), mamba_out.astype(dt), preferred_element_type=jnp.float32)
            made = {"ssm": ssm, "conv": xbc}
        elif kind == "none":
            h, made = x, {}
        else:
            attn_norm = self.param("attn_norm", nn.initializers.ones, (D,))
            wq = self.param("wq", init, (D, Hq * hd))
            wk = self.param("wk", init, (D, Hkv * hd))
            wv = self.param("wv", init, (D, Hkv * hd))
            wo = self.param("wo", init, (Hq * hd, D))
            if c.qk_norm:
                q_norm = self.param("q_norm", nn.initializers.ones, (hd,))
                k_norm = self.param("k_norm", nn.initializers.ones, (hd,))
            with scope("policy/attention_window" if kind == "window" else "policy/attention_full"):
                a = rms_norm(x, attn_norm, c.rms_norm_eps).astype(dt)
                q = jnp.dot(a, wq.astype(dt)).reshape(B, T, Hq, hd)
                k = jnp.dot(a, wk.astype(dt)).reshape(B, T, Hkv, hd)
                v = jnp.dot(a, wv.astype(dt)).reshape(B, T, Hkv, hd)
                if c.qk_norm:
                    q, k = rms_norm(q, q_norm, c.rms_norm_eps).astype(dt), rms_norm(k, k_norm, c.rms_norm_eps).astype(dt)
                if c.rope_layout[self.layer]:
                    q, k = rope(q, q_pos, c.rope_theta), rope(k, q_pos, c.rope_theta)
                cache_seg = jnp.where(state["pos"] >= 0, 0, -1)
                window = c.window if kind == "window" else None
                if c.lane_groups > 1:
                    o, visited = lane_grouped_attention(q, k, v, state, cache_seg, q_pos, q_seg, window, self.mesh)
                else:
                    held = (state["k"].astype(dt), state["v"].astype(dt), state["pos"], cache_seg)
                    o, visited = grouped_attention(q, k, v, held, q_pos, q_seg, window, mesh=self.mesh)
                counters = _blocks_visited(visited, self.layer, T)
                h = x + jnp.dot(o.reshape(B, T, Hq * hd), wo.astype(dt), preferred_element_type=jnp.float32)
            made = {"k": k, "v": v}
        if not c.feed_forward(self.layer):
            return h, made, counters
        gated = c.activation not in UNGATED
        ffn_norm = self.param("ffn_norm", nn.initializers.ones, (D,))
        if dense:
            F = c.dense_width
            dense_gate = self.param("dense_gate", init, (D, F))
            dense_up = self.param("dense_up", init, (D, F))
            dense_down = self.param("dense_down", init, (F, D))
            with scope("policy/dense_ffn"):
                m = rms_norm(h, ffn_norm, c.rms_norm_eps).astype(dt)
                g = act(_dot(m, dense_gate.astype(dt))) * _dot(m, dense_up.astype(dt))
                return h + _dot(g, dense_down.astype(dt), preferred_element_type=jnp.float32), made, counters
        w_gate = self.param("w_gate", init, (c.experts_held, D, c.expert_width)) if gated else None
        w_up = self.param("w_up", init, (c.experts_held, D, c.expert_width))
        w_down = self.param("w_down", init, (c.experts_held, c.expert_width, D))
        with scope("policy/experts"):
            m = rms_norm(h, ffn_norm, c.rms_norm_eps).reshape(B * T, D)
        if c.router_reads != "input":
            top_w, top_i, moved = routed(m)
        with scope("policy/experts"):
            y, routing = expert_layer(m, top_w, top_i, w_gate, w_up, w_down, c.expert_offset, dt, act)
        note("expert_path", {f"{'act_' if T == 1 else ''}layer_{self.layer}": expert_path(B * T)})
        counters = {**counters, **routing}
        if moved is not None:
            counters["bias_moved"] = moved.sum().astype(jnp.float32)
        if c.shared_width:  # every token's, whatever it was routed to; every chip of a group computes it alike
            shared_gate = self.param("shared_gate", init, (D, c.shared_width)) if gated else None
            shared_up = self.param("shared_up", init, (D, c.shared_width))
            shared_down = self.param("shared_down", init, (c.shared_width, D))
            with scope("policy/shared_expert"):
                ms = m.astype(dt)
                g = _hidden(act, lambda w: _dot(ms, w.astype(dt)), shared_gate, shared_up)
                y = y + _dot(g, shared_down.astype(dt), preferred_element_type=jnp.float32)
        return h + y.reshape(B, T, D), made, counters


class DecoderPolicy(nn.Module):
    """Token ids in, the final normed hidden state and a value out; the head's logits
    are formed by the caller (whole for one acting step, in token chunks for the
    update: ``algos/ppo/utils.py::chunked_log_prob_and_entropy`` over ``head_of``)."""

    cfg: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Any = None  # the devices the update's rows are spread over, if several

    def setup(self):
        c = self.cfg
        init = nn.initializers.normal(0.02)
        self.embed = self.param("embed", init, (c.vocab_held, c.hidden_size))
        # each layer is recomputed in the backward pass: what it computes between its input and output is not kept
        self.blocks = [nn.remat(DecoderLayer)(c, i, self.dtype, self.mesh, name=f"layers_{i}") for i in range(c.layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (c.hidden_size,))
        if not c.tie_embeddings:
            self.head = self.param("head", init, (c.hidden_size, c.vocab_held))
        self.value_w = self.param("value_w", nn.initializers.zeros, (c.hidden_size, 1))
        self.value_b = self.param("value_b", nn.initializers.zeros, (1,))

    def __call__(self, tokens, prev_actions, is_first, state):
        """``tokens`` / ``prev_actions``: ``[B, T]`` ids, ``is_first``: ``[B, T]``, ``state``:
        the carry -> ``(hidden [B, T, D] float32, values [B, T], what each layer made for
        its state, q_pos, counters)``.  Writes nothing."""
        c = self.cfg
        q_pos, q_seg = positions(is_first, state["pos"])
        with scope("policy/embed"):
            emb = self.embed.astype(jnp.float32)
            keep = (1.0 - is_first.astype(jnp.float32))[..., None]
            x = emb[tokens] + keep * emb[prev_actions]
        written, totals = [], {}
        for block, layer_state in zip(self.blocks, state["layers"]):
            x, made, counters = block(x, layer_state, q_pos, q_seg)
            written.append(made)
            totals = {**totals, **{name: totals.get(name, 0.0) + n for name, n in counters.items()}}
        with scope("policy/head"):
            hidden = rms_norm(x, self.final_norm, c.rms_norm_eps)
            values = (jnp.dot(hidden, self.value_w.astype(jnp.float32)) + self.value_b)[..., 0]
        aux = {}
        if "mamba" in c.mixers and tokens.shape[1] > 1:  # a chunk went through the scan
            aux["SSM/resets_in_chunk_share"] = resets_in_chunks(is_first, c.ssm_chunk)
        if "key_blocks" in totals:  # a chunk's attention went blockwise through the caches
            aux["Attn/key_blocks_visited_share"] = totals["key_blocks_visited"] / totals["key_blocks"]
        if "held" in totals:
            routed = float(tokens.size * c.expert_layers)  # token-layers that met a router
            aux = {
                **aux,
                "MoE/held_share": totals["held"] / (routed * c.experts_per_token),
                "MoE/load_max_over_mean": totals["load_max"] * c.experts_held / jnp.maximum(totals["held"], 1.0),
                "MoE/dropped": totals["dropped"],
            }
            if "bias_moved" in totals:
                aux["MoE/bias_moved_share"] = totals["bias_moved"] / routed
        return hidden, values, written, q_pos, aux

    def logits(self, hidden):
        with scope("policy/head"):
            if self.cfg.tie_embeddings:
                return _dot(hidden.astype(self.dtype), self.embed.astype(self.dtype).T, preferred_element_type=jnp.float32)
            return jnp.dot(hidden.astype(self.dtype), self.head.astype(self.dtype), preferred_element_type=jnp.float32)

    def step(self, tokens, prev_actions, is_first, state):
        """One acting step over all rows: ``tokens`` / ``prev_actions``: ``[B]`` ids,
        ``is_first``: ``[B, 1]`` -> ``([logits [B, V]], value [B, 1], new state)``."""
        first = is_first[:, 0] > 0
        state = {"pos": jnp.where(first, 0, state["pos"]), "layers": tuple(emptied(s, first) for s in state["layers"])}
        hidden, values, written, q_pos, _ = self(tokens[:, None], prev_actions[:, None], is_first, state)
        rows, pos = jnp.arange(tokens.shape[0]), q_pos[:, 0]
        layers = []
        for old, made in zip(state["layers"], written):
            if "ssm" in old:
                layers.append({"ssm": made["ssm"], "conv": jnp.concatenate([old["conv"][:, 1:], made["conv"].astype(old["conv"].dtype)], 1)})
                continue
            if not old:  # a layer without a mixer carries nothing
                layers.append(old)
                continue
            if "conv" in old:
                layers.append({"conv": jnp.concatenate([old["conv"][:, 1:], made["conv"].astype(old["conv"].dtype)], 1)})
                continue
            slot = pos % old["pos"].shape[1]
            kept = {name: _into_slot(old[name], new[:, 0], rows, slot) for name, new in made.items()}  # keys and values, or the latent
            layers.append({**kept, "pos": old["pos"].at[rows, slot].set(pos)})
        return [self.logits(hidden[:, 0])], values, {"pos": pos + 1, "layers": tuple(layers)}


def _into_slot(cache, new: jax.Array, rows: jax.Array, slot: jax.Array):
    """A step's keys (or values) ``[B, Hkv, hd]``, or its latents ``[B, width]``, written into
    their rows' slots of a cache kept as one array or a lane-full of heads an array."""
    if isinstance(cache, tuple):
        new = new.reshape(len(rows), len(cache), 1, -1)
        return tuple(c.at[rows, slot].set(new[:, j].astype(c.dtype)) for j, c in enumerate(cache))
    return cache.at[rows, slot].set(new.astype(cache.dtype))


def emptied(layer_state: Dict[str, jax.Array], first: jax.Array) -> Dict[str, jax.Array]:
    """A layer's carried state with the rows of ``first`` (``[B]``) empty: an episode that
    starts forgets the one before it, whichever kind of state the layer carries."""
    if "ssm" in layer_state:
        return {"ssm": jnp.where(first[:, None, None, None], 0.0, layer_state["ssm"]), "conv": jnp.where(first[:, None, None], 0, layer_state["conv"])}
    if not layer_state:
        return layer_state
    if "conv" in layer_state:
        return {"conv": jnp.where(first[:, None, None], 0, layer_state["conv"])}
    return {**layer_state, "pos": jnp.where(first[:, None], -1, layer_state["pos"])}


def head_of(params: Dict[str, Any]) -> jax.Array:
    """The head ``[D, V]`` of a policy's ``params["params"]``: its own table, or the
    embedding's rows (tied: that one leaf then takes the lookups' scatter-add and the
    head's dense gradient)."""
    return params["head"] if "head" in params else params["embed"].T


def zero_state(sizes: DecoderConfig, n: int, dtype: Any) -> Dict[str, Any]:
    """The carry of ``n`` rows before their first token: every slot and every tail empty."""
    layers = []
    for i in range(sizes.layers):
        if sizes.mixers[i] == "mamba":  # the state in float32 whatever the compute dtype: a decayed sum over the whole episode
            ssm = jnp.zeros((n, sizes.mamba_heads, sizes.mamba_head_dim, sizes.ssm_state), jnp.float32)
            layers.append({"ssm": ssm, "conv": jnp.zeros((n, sizes.conv_taps - 1, sizes.ssm_conv_width), dtype)})
            continue
        if sizes.mixers[i] == "none":
            layers.append({})
            continue
        if sizes.mixers[i] == "conv":
            layers.append({"conv": jnp.zeros((n, sizes.conv_taps - 1, sizes.hidden_size), dtype)})
            continue
        if sizes.mixers[i] == "latent":
            layers.append({"latent": jnp.zeros((n, sizes.capacity, sizes.latent_width), dtype), "pos": jnp.full((n, sizes.capacity), -1, jnp.int32)})
            continue
        shape = (n, sizes.slots(i), sizes.kv_heads_held, sizes.head_dim)  # a buffer each: the acting step is given them to overwrite
        if sizes.lane_groups > 1:
            kv = lambda: tuple(jnp.zeros((*shape[:2], 1, LANES), dtype) for _ in range(sizes.lane_groups))  # noqa: E731
        else:
            kv = lambda: jnp.zeros(shape, dtype)  # noqa: E731
        layers.append({"k": kv(), "v": kv(), "pos": jnp.full(shape[:2], -1, jnp.int32)})
    return {"pos": jnp.zeros((n,), jnp.int32), "layers": tuple(layers)}


MATMUL_WEIGHTS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "head", "conv_in", "conv_out", "dense_gate", "dense_up", "dense_down",
    "wkv_a", "wkv_b", "shared_gate", "shared_up", "shared_down", "mamba_in", "mamba_out",
)  # fmt: skip
#: leaves that are no trained weight: no gradient reaches them and the optimizer leaves them as they are
BUFFERS = ("expert_bias",)


def _named(path: Sequence[Any], names: Sequence[str]) -> bool:
    return getattr(path[-1], "key", None) in names


def cast_matmul_weights(params: Any, dtype: Any) -> Any:
    """The weights that the policy multiplies in ``dtype``, cast once (the acting steps of
    a rollout then read half the bytes); the tables, norms, router, taps and value head stay."""
    return jax.tree_util.tree_map_with_path(lambda path, x: x.astype(dtype) if _named(path, MATMUL_WEIGHTS) else x, params)


def hold_buffers(updates: Any) -> Any:
    """An optimizer's updates with those of ``BUFFERS`` set to zero, whatever the optimizer made of them."""
    return jax.tree_util.tree_map_with_path(lambda path, u: jnp.zeros_like(u) if _named(path, BUFFERS) else u, updates)
