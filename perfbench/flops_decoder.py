"""Operations of one gradient step of recurrent PPO over the decoder policy, from the
configuration's shapes.

Counted: the matrix multiplications of the forward pass over the step's tokens
(``num_envs x rollout_steps``; 2 x rows x in x out), twice that again for the backward
pass, and the optimizer's elementwise work.  The expert layer counts the experts a
token is expected to find here (``experts_per_token x experts_held / num_experts``: 1.5
of 6 at 16 of 64), three products each.  The attention products (scores and weighted
values) count the chunk itself (half of it, being causal) and ``mean_context`` keys of the
carried cache, the configuration's estimate of what a token sees on average under its
traffic; the statically shaped path multiplies every slot whatever the fill, and the
empty ones are not counted.  Not counted: normalisations,
activations, softmaxes, the rotary embedding, the table lookups, the health
diagnostics, and what per-layer recomputation forms a second time.
"""

from __future__ import annotations

from typing import Any, Dict

ADAM_FLOPS_PER_PARAM = 18.0  # clip (3) + moments (7) + bias correction and update (8)


def parameters(S: Dict[str, Any]) -> float:
    D, hd, F = S["hidden_size"], S["head_dim"], S["expert_width"]
    attention = D * hd * (2 * S["heads_held"] + 2 * S["kv_heads_held"])
    layer = attention + D * S["num_experts"] + S["experts_held"] * 3 * D * F + 2 * D
    return S["layers"] * layer + 2 * S["vocab_held"] * D + 2 * D + 1


def step_flops(S: Dict[str, Any]) -> Dict[str, float]:
    n = float(S["num_envs"] * S["rollout_steps"])
    D, hd, F, T = S["hidden_size"], S["head_dim"], S["expert_width"], S["rollout_steps"]
    qo, kv = S["heads_held"] * hd, S["kv_heads_held"] * hd
    held_per_token = S["experts_per_token"] * S["experts_held"] / S["num_experts"]
    forward = {"projections": 0.0, "attention_products": 0.0, "router": 0.0, "experts": 0.0}
    for layer in range(S["layers"]):
        keys = min(S["mean_context"], S["window"] if S["window_layout"][layer] else S["cache_capacity"]) + T / 2
        forward["projections"] += 2.0 * n * D * (2 * qo + 2 * kv)
        forward["attention_products"] += 2.0 * 2.0 * n * qo * keys
        forward["router"] += 2.0 * n * D * S["num_experts"]
        forward["experts"] += 2.0 * n * held_per_token * 3 * D * F
    forward["head"] = 2.0 * n * D * (S["vocab_held"] + 1)
    parts = {k: 3.0 * v for k, v in forward.items()}
    parts["optimizer"] = ADAM_FLOPS_PER_PARAM * parameters(S)
    return {"total": sum(parts.values()), **parts}
