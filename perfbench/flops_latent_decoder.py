"""Operations of one gradient step of recurrent PPO over a decoder policy whose layers
attend through a compressed latent (MLA) and feed forward densely or through routed
experts beside a shared one (``moonlight16b_1of8``), from the configuration's shapes; and
the operations of one visited block of the update's blockwise attention over a latent
cache (``latent_block_flops``), for the kernels' share of the chip's peak.

Counted: the matrix multiplications of the forward pass over the step's tokens
(``num_envs x rollout_steps``; 2 x rows x in x out), twice that again for the backward
pass, and the optimizer's elementwise work.  An MLA layer counts its projections (``W_q``,
``W_kv_a``, ``W_kv_b`` once over the chunk's tokens, which is what the folded products of the
latent-space form cost as well, and ``W_o``) and its attention products over the chunk
itself (half of it, being causal) and ``mean_context`` keys of the carried cache, what a
token sees on average over the window the cell times under its traffic (the
configuration's ``assumed.mean_context`` says from what), in the latent's space, whatever implements them: a head's query against a key is ``kv_lora_rank +
qk_rope_head_dim`` multiply-adds and its weighted value ``kv_lora_rank`` forward; backward
the scores' recomputation is not counted, ``dP`` is ``kv_lora_rank`` and ``dQ`` ``kv_lora_rank +
qk_rope_head_dim``, and a cached key takes no ``dK`` / ``dV`` (the cache is a constant of the
update) where the chunk's own keys do.  (Decompressing every visible key to sixteen heads
of 192 + 128 would be 2 x 512 x 4096 a key more: no program does that over a cache.)  The
dense feed-forward and the shared expert count their three products; the routed experts
the experts a token is expected to find here (``experts_per_token x experts_held /
num_experts``: 0.75 of 6 at 8 of 64), three products each.  Not counted: normalisations,
activations, softmaxes, the rotary embedding, the table lookups, the health diagnostics,
what per-layer recomputation forms a second time, the zeros a slot is padded with, and
the slots that no query sees.
"""

from __future__ import annotations

from typing import Any, Dict

ADAM_FLOPS_PER_PARAM = 18.0  # clip (3) + moments (7) + bias correction and update (8)


def parameters(S: Dict[str, Any]) -> float:
    D, H, r = S["hidden_size"], S["heads_held"], S["kv_lora_rank"]
    dn, dr, dv = S["qk_nope_head_dim"], S["qk_rope_head_dim"], S["v_head_dim"]
    attention = D * H * (dn + dr) + D * (r + dr) + r + r * H * (dn + dv) + H * dv * D
    total = 2 * S["vocab_held"] * D + 2 * D + 1  # embedding and head, the final norm, the value head
    for l in range(S["layers"]):
        total += attention + 2 * D  # and the layer's two norms
        if l < S["dense_layers"]:
            total += 3 * D * S["dense_width"]
        else:
            total += D * S["num_experts"] + S["num_experts"] + 3 * D * (S["experts_held"] * S["expert_width"] + S["shared_width"])
    return float(total)


def latent_block_flops(rows: int, keys: int, S: Dict[str, Any]) -> Dict[str, float]:
    """One visited block of the blockwise attention over a latent cache: ``rows`` query rows
    (tokens x heads of the one key head) against ``keys`` slots.  Forward: scores and
    weighted values; backward: the scores again (a recomputation the backward pass needs:
    work that it does), ``dP`` and ``dQ``; no ``dK`` / ``dV``, the cache being a constant."""
    key, value = S["kv_lora_rank"] + S["qk_rope_head_dim"], S["kv_lora_rank"]
    return {"forward": 2.0 * rows * keys * (key + value), "backward": 2.0 * rows * keys * (2 * key + value)}


def step_flops(S: Dict[str, Any]) -> Dict[str, float]:
    n = float(S["num_envs"] * S["rollout_steps"])
    D, H, r, T = S["hidden_size"], S["heads_held"], S["kv_lora_rank"], S["rollout_steps"]
    dn, dr, dv = S["qk_nope_head_dim"], S["qk_rope_head_dim"], S["v_head_dim"]
    key, value = r + dr, r
    held_per_token = S["experts_per_token"] * S["experts_held"] / S["num_experts"]
    parts = {"attention_projections": 0.0, "latent_attention": 0.0, "dense_ffn": 0.0, "router": 0.0, "experts": 0.0, "shared_expert": 0.0}
    for l in range(S["layers"]):
        parts["attention_projections"] += 3.0 * 2.0 * n * (D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D)
        cached, own = min(S["mean_context"], S["cache_capacity"]), T / 2
        # forward key + value a pair; backward dP and dQ, and for the chunk's own keys dK and dV as well
        parts["latent_attention"] += 2.0 * n * H * (cached * (2 * (key + value)) + own * (3 * (key + value)))
        if l < S["dense_layers"]:
            parts["dense_ffn"] += 3.0 * 2.0 * n * 3 * D * S["dense_width"]
        else:
            parts["router"] += 3.0 * 2.0 * n * D * S["num_experts"]
            parts["experts"] += 3.0 * 2.0 * n * held_per_token * 3 * D * S["expert_width"]
            parts["shared_expert"] += 3.0 * 2.0 * n * 3 * D * S["shared_width"]
    parts["head"] = 3.0 * 2.0 * n * D * (S["vocab_held"] + 1)
    parts["optimizer"] = ADAM_FLOPS_PER_PARAM * parameters(S)
    return {"total": sum(parts.values()), **parts}
