"""LayerNorm-GRU gate math — the RSSM's hot loop after the matmul.

The RSSM's hot loop (SURVEY §3.1 hot loop 1: 64 sequential GRU steps per gradient
step) is ``h' = GRUGates(LayerNorm(concat(x, h) @ W), h)``.  The matmul is a Dense
layer; everything AFTER it — LayerNorm over the fused ``3H`` projection, the three
gate nonlinearities and the state blend — is :func:`reference_layernorm_gru`, plain
``jax.numpy`` with f32 statistics that XLA fuses on its own.  ``LayerNormGRUCell``
(``sheeprl_tpu/models/blocks.py``) calls it directly.

There is deliberately no hand-written kernel for this chain: on a TPU v5e one made
no measurable difference to the DreamerV3-S train step, and a Mosaic custom call
cannot be partitioned by GSPMD, so it stopped every multi-chip run at lowering
(PERF.md, PR 21).  ``_ln`` / ``_gates`` are shared with ``ops/rssm_step.py``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _ln(p: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    mean = jnp.mean(p, -1, keepdims=True)
    var = jnp.mean(jnp.square(p - mean), -1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    unit = (p - mean) * inv
    return unit * gamma + beta, unit, inv


def _gates(n: jax.Array, h: jax.Array, hidden: int):
    reset = jax.nn.sigmoid(n[..., :hidden])
    cand = jnp.tanh(reset * n[..., hidden : 2 * hidden])
    update = jax.nn.sigmoid(n[..., 2 * hidden :] - 1.0)
    out = update * cand + (1.0 - update) * h
    return out, reset, cand, update


def reference_layernorm_gru(
    proj: jax.Array, h: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-3
) -> jax.Array:
    """``h' = GRUGates(LN(proj) * gamma + beta, h)`` (f32 statistics, any batch
    rank).  ``proj``: [..., 3H] fused projection of ``concat(x, h)``; ``h``:
    [..., H]; ``gamma``/``beta``: [3H] LayerNorm parameters.  Returns [..., H] in
    ``h``'s dtype."""
    p = proj.astype(jnp.float32)
    n, _, _ = _ln(p, gamma.astype(jnp.float32), beta.astype(jnp.float32), eps)
    out, _, _, _ = _gates(n, h.astype(jnp.float32), h.shape[-1])
    return out.astype(h.dtype)
