"""Operations of one gradient step of recurrent PPO over a decoder policy whose layers
mix by a gated short convolution or by full attention and feed forward densely or
through experts (``lfm2_8b_a1b_1of4``), from the configuration's shapes.

Counted: the matrix multiplications of the forward pass over the step's tokens
(``num_envs x rollout_steps``; 2 x rows x in x out), twice that again for the backward
pass, and the optimizer's elementwise work.  A convolution mixer counts its in-projection
(``D -> 3 D``) and its out-projection (``D -> D``); an attention mixer its four projections
and its two products (scores and weighted values) over the chunk itself (half of it,
being causal) and ``mean_context`` keys of the carried cache, the configuration's estimate
of what a token sees on average under its traffic; the statically shaped path multiplies
every slot whatever the fill, and the empty ones are not counted.  The dense feed-forward
counts its three products; the expert layer the experts a token is expected to find here
(``experts_per_token x experts_held / num_experts``: 1 of 4 at 8 of 32), three products
each.  The tied head is counted once: the products of the logits; the lookups are no
product.  Not counted: the convolution's taps and gates (elementwise: 2 x taps x D a
token), normalisations, activations, softmaxes, the rotary embedding, the table lookups,
the health diagnostics, and what per-layer recomputation forms a second time.
"""

from __future__ import annotations

from typing import Any, Dict

ADAM_FLOPS_PER_PARAM = 18.0  # clip (3) + moments (7) + bias correction and update (8)


def _conv(S: Dict[str, Any], l: int) -> bool:
    return S["layer_types"][l] == "conv"


def parameters(S: Dict[str, Any]) -> float:
    D, hd = S["hidden_size"], S["head_dim"]
    total = S["vocab_held"] * D + 2 * D + 1  # the tied table, the final norm, the value head
    for l in range(S["layers"]):
        if _conv(S, l):
            total += D * 3 * D + S["conv_taps"] * D + D * D + D
        else:
            total += D * hd * (2 * S["heads_held"] + 2 * S["kv_heads_held"]) + 2 * hd + D
        total += D  # the feed-forward's norm
        if l < S["dense_layers"]:
            total += 3 * D * S["dense_width"]
        else:
            total += D * S["num_experts"] + S["num_experts"] + S["experts_held"] * 3 * D * S["expert_width"]
    return float(total)


def step_flops(S: Dict[str, Any]) -> Dict[str, float]:
    n = float(S["num_envs"] * S["rollout_steps"])
    D, hd, T = S["hidden_size"], S["head_dim"], S["rollout_steps"]
    qo, kv = S["heads_held"] * hd, S["kv_heads_held"] * hd
    held_per_token = S["experts_per_token"] * S["experts_held"] / S["num_experts"]
    forward = {"conv": 0.0, "attention_projections": 0.0, "attention_products": 0.0, "dense_ffn": 0.0, "router": 0.0, "experts": 0.0}
    for l in range(S["layers"]):
        if _conv(S, l):
            forward["conv"] += 2.0 * n * D * 4 * D
        else:
            keys = min(S["mean_context"], S["cache_capacity"]) + T / 2
            forward["attention_projections"] += 2.0 * n * D * (2 * qo + 2 * kv)
            forward["attention_products"] += 2.0 * 2.0 * n * qo * keys
        if l < S["dense_layers"]:
            forward["dense_ffn"] += 2.0 * n * 3 * D * S["dense_width"]
        else:
            forward["router"] += 2.0 * n * D * S["num_experts"]
            forward["experts"] += 2.0 * n * held_per_token * 3 * D * S["expert_width"]
    forward["head"] = 2.0 * n * D * (S["vocab_held"] + 1)
    parts = {k: 3.0 * v for k, v in forward.items()}
    parts["optimizer"] = ADAM_FLOPS_PER_PARAM * parameters(S)
    return {"total": sum(parts.values()), **parts}
