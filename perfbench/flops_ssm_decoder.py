"""Operations of one gradient step of recurrent PPO over a decoder policy whose blocks are
each a Mamba-2 mixer, an attention mixer or the experts alone (``nemotron3nano30b_1of16``),
from the configuration's shapes; and the operations and bytes of the update's chunked scan
(``scan_costs``), for its share of the chip's roofline.

Counted: the matrix multiplications of the forward pass over the step's tokens
(``num_envs / num_batches x rollout_steps``; 2 x rows x in x out), twice that again for the
backward pass, and the optimizer's elementwise work.  A Mamba block counts its in- and
out-projections and its scan as the chunked form computes it (``scan_costs``: the whole
``chunk x chunk`` products inside a chunk, the masked half too, and the states in and out of
every chunk); an attention block its four projections and its products over the chunk
itself (half of it, being causal) and ``mean_context`` keys of the carried cache, what a
token sees on average over the window the cell times (the configuration's
``assumed.mean_context`` says from what); an expert block its router, the experts a token
is expected to find here (``experts_per_token x experts_held / num_experts``: 0.375 of 6 at 8
of 128), two products each, and the shared expert's two.  Not counted: normalisations,
activations, the convolution's taps, the decays, softmaxes, the table lookups, the health
diagnostics, and what per-layer recomputation forms a second time.
"""

from __future__ import annotations

from typing import Any, Dict

ADAM_FLOPS_PER_PARAM = 18.0  # clip (3) + moments (7) + bias correction and update (8)


def _kinds(S: Dict[str, Any]) -> str:
    return S["pattern"][: S["layers"]]


def parameters(S: Dict[str, Any]) -> float:
    D, H = S["hidden_size"], S["mamba_heads"]
    inner = H * S["mamba_head_dim"]
    width = inner + 2 * S["ssm_groups"] * S["ssm_state"]
    q, kv = S["heads_held"] * S["head_dim"], S["kv_heads_held"] * S["head_dim"]
    block = {
        "M": D + D * (inner + width + H) + S["conv_kernel"] * width + width + 3 * H + inner + inner * D,
        "*": D + D * (2 * q + 2 * kv),
        "E": D + D * S["num_experts"] + S["num_experts"] + 2 * D * (S["experts_held"] * S["expert_width"] + S["shared_width"]),
    }
    return float(sum(block[k] for k in _kinds(S)) + 2 * S["vocab_held"] * D + 2 * D + 1)


def scan_costs(rows: int, tokens: int, S: Dict[str, Any], state_bytes: int = 4, compute_bytes: int = 2) -> Dict[str, float]:
    """One Mamba block's chunked scan over ``rows`` rows of ``tokens`` tokens, forward:
    operations (``chunk x chunk`` products a group for ``C . B`` and a head for the weighted
    inputs, ``head_dim x state`` a head for the state a chunk passes on and the state it
    reads) and bytes (``x``, ``B`` and ``C`` read in the compute dtype, ``dt`` and ``y`` in
    float32, every chunk's state written and read once in float32, the carried state read).
    The padding of a last chunk is computed, so it is counted."""
    H, P, G, N = S["mamba_heads"], S["mamba_head_dim"], S["ssm_groups"], S["ssm_state"]
    Q = min(S["chunk_size"], tokens)
    chunks = -(-tokens // Q)
    flops = 2.0 * rows * chunks * (Q * Q * G * N + Q * Q * H * P + 2 * Q * H * P * N)
    per_token = H * P * compute_bytes + 2 * G * N * compute_bytes + H * 4 + H * P * 4
    states = rows * (2 * chunks + 1) * H * P * N * state_bytes
    return {"flops": flops, "bytes": float(rows * tokens * per_token + states)}


def step_flops(S: Dict[str, Any]) -> Dict[str, float]:
    rows, T = S["num_envs"] // S["num_batches"], S["rollout_steps"]
    n = float(rows * T)
    D, H = S["hidden_size"], S["mamba_heads"]
    inner = H * S["mamba_head_dim"]
    width = inner + 2 * S["ssm_groups"] * S["ssm_state"]
    q, kv = S["heads_held"] * S["head_dim"], S["kv_heads_held"] * S["head_dim"]
    held_per_token = S["experts_per_token"] * S["experts_held"] / S["num_experts"]
    forward = {"mamba_projections": 0.0, "ssd_scan": 0.0, "attention_projections": 0.0, "attention_products": 0.0, "router": 0.0, "experts": 0.0, "shared_expert": 0.0}
    for kind in _kinds(S):
        if kind == "M":
            forward["mamba_projections"] += 2.0 * n * D * (inner + width + H + inner)
            forward["ssd_scan"] += scan_costs(rows, T, S)["flops"]
        elif kind == "*":
            keys = min(S["mean_context"], S["cache_capacity"]) + T / 2
            forward["attention_projections"] += 2.0 * n * D * (2 * q + 2 * kv)
            forward["attention_products"] += 2.0 * 2.0 * n * q * keys
        else:
            forward["router"] += 2.0 * n * D * S["num_experts"]
            forward["experts"] += 2.0 * n * held_per_token * 2 * D * S["expert_width"]
            forward["shared_expert"] += 2.0 * n * 2 * D * S["shared_width"]
    forward["head"] = 2.0 * n * D * (S["vocab_held"] + 1)
    parts = {k: 3.0 * v for k, v in forward.items()}
    parts["optimizer"] = ADAM_FLOPS_PER_PARAM * parameters(S)
    return {"total": sum(parts.values()), **parts}
