"""Fully-fused RSSM GRU step (Pallas, TPU): matmul + LayerNorm + gates in ONE kernel.

Experimental, and called by nothing outside its tests and
``benchmarks/fused_step_bench.py`` (ROADMAP D4).  In plain XLA the ``[B, K] @ [K, 3H]``
projection runs as its own small GEMM with an HBM round trip for the ``[B, 3H]``
intermediate between it and the gate chain (``ops/gru.py``).
This kernel keeps the WHOLE step VMEM-resident: weights (``[K, 3H]`` bf16, ~3 MB at
size S), the concat input row block, the projection, and the gate chain never touch
HBM between the matmul and the new state.

The matmul still uses the MXU (``jnp.dot`` inside the kernel lowers to MXU ops); the
fusion removes per-step kernel boundaries and intermediate materialisation — the two
costs XLA cannot always eliminate across a ``lax.scan`` step boundary.

Hand-derived VJP (single kernel for the backward too): recomputes the projection and
LN/gate intermediates in VMEM from the saved ``(xh, h)`` residuals, then forms
``dW = xhᵀ @ dp`` and ``dxh = dp @ Wᵀ`` on the MXU in the same pass.

Single-tile kernel (whole batch in one block): the RSSM scan runs at B = 16–64 rows,
far under one (8, 128) tile budget in VMEM; ``fused_step_supported`` gates callers.
On a TPU v5e (PR 21) it compiles and matches the reference at the size-S shapes
(B=16, K=1024, H=512; bf16 and f32) and runs out of VMEM at size XL (the bf16
``[5120, 12288]`` weight alone is 120 MB), as that gate predicts.  Like every Mosaic
custom call it cannot be partitioned by GSPMD: inside a multi-device ``jit`` it must
be wrapped in ``shard_map``.
Reference hot loop: ``/root/reference/sheeprl/algos/dreamer_v3/dreamer_v3.py:134-145``
(the 64-step recurrent unroll this step implements one iteration of).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from sheeprl_tpu.ops.gru import _gates, _ln


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fwd_kernel(xh_ref, h_ref, w_ref, gamma_ref, beta_ref, out_ref, *, hidden: int, eps: float):
    xh = xh_ref[:]
    w = w_ref[:]
    # MXU matmul with f32 accumulation; everything downstream in f32 in VMEM.
    proj = jnp.dot(xh, w, preferred_element_type=jnp.float32)
    n, _, _ = _ln(proj, gamma_ref[:].astype(jnp.float32), beta_ref[:].astype(jnp.float32), eps)
    out, _, _, _ = _gates(n, h_ref[:].astype(jnp.float32), hidden)
    out_ref[:] = out.astype(out_ref.dtype)


def _bwd_kernel(
    xh_ref,
    h_ref,
    w_ref,
    gamma_ref,
    beta_ref,
    g_ref,
    dxh_ref,
    dh_ref,
    dw_ref,
    dgamma_ref,
    dbeta_ref,
    *,
    hidden: int,
    eps: float,
):
    xh = xh_ref[:]
    h = h_ref[:].astype(jnp.float32)
    w = w_ref[:]
    gamma = gamma_ref[:].astype(jnp.float32)
    beta = beta_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)

    # Recompute forward intermediates in VMEM (cheaper than storing them per step).
    proj = jnp.dot(xh, w, preferred_element_type=jnp.float32)
    n, unit, inv = _ln(proj, gamma, beta, eps)
    _, reset, cand, update = _gates(n, h, hidden)

    # Gate chain backward.
    dh = g * (1.0 - update)
    du = g * (cand - h)
    dn_u = du * update * (1.0 - update)
    dcand = g * update
    dtanh = dcand * (1.0 - jnp.square(cand))
    n_c = n[:, hidden : 2 * hidden]
    dreset = dtanh * n_c
    dn_c = dtanh * reset
    dn_r = dreset * reset * (1.0 - reset)
    dn = jnp.concatenate([dn_r, dn_c, dn_u], axis=-1)

    # LayerNorm backward.
    dg_hat = dn * gamma
    m1 = jnp.mean(dg_hat, -1, keepdims=True)
    m2 = jnp.mean(dg_hat * unit, -1, keepdims=True)
    dp = (dg_hat - m1 - unit * m2) * inv

    # Matmul backward on the MXU, still VMEM-resident.
    dxh_ref[:] = jnp.dot(dp.astype(xh.dtype), w.T, preferred_element_type=jnp.float32).astype(dxh_ref.dtype)
    dw_ref[:] = jnp.dot(xh.T, dp.astype(xh.dtype), preferred_element_type=jnp.float32).astype(dw_ref.dtype)
    dh_ref[:] = dh.astype(dh_ref.dtype)
    dgamma_ref[:] = jnp.sum(dn * unit, axis=0, keepdims=True).astype(dgamma_ref.dtype)
    dbeta_ref[:] = jnp.sum(dn, axis=0, keepdims=True).astype(dbeta_ref.dtype)


def fused_step_supported(batch: int, in_features: int, hidden: int, itemsize: int = 4) -> bool:
    """Single-tile budget: batch within one grid step and the working set
    (weights + activations + grads, f32-dominated in the backward) inside a
    conservative 12 MB VMEM envelope."""
    three_h = 3 * hidden
    working = (
        in_features * three_h * itemsize  # W (+ dW in bwd, covered by the margin)
        + batch * (in_features + three_h * 3 + hidden * 3) * 4
    )
    return batch <= 256 and working * 2 <= 12 * 1024 * 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_gru_step(
    xh: jax.Array, h: jax.Array, w: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-3
) -> jax.Array:
    """``h' = GRUGates(LN(xh @ w) * gamma + beta, h)`` — one VMEM-resident kernel.

    ``xh``: [B, K] concat(input, h); ``w``: [K, 3H]; ``h``: [B, H];
    ``gamma``/``beta``: [3H].  Returns [B, H].
    """
    return _fused_step_fwd(xh, h, w, gamma, beta, eps)[0]


def _specs(batch, k, hidden):
    three_h = 3 * hidden
    return [
        pl.BlockSpec((batch, k), lambda: (0, 0)),
        pl.BlockSpec((batch, hidden), lambda: (0, 0)),
        pl.BlockSpec((k, three_h), lambda: (0, 0)),
        pl.BlockSpec((three_h,), lambda: (0,)),
        pl.BlockSpec((three_h,), lambda: (0,)),
    ]


def _fused_step_fwd(xh, h, w, gamma, beta, eps=1e-3):
    batch, k = xh.shape
    hidden = h.shape[-1]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hidden=hidden, eps=eps),
        in_specs=_specs(batch, k, hidden),
        out_specs=pl.BlockSpec((batch, hidden), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, hidden), h.dtype),
        interpret=_interpret(),
    )(xh, h, w, gamma, beta)
    return out, (xh, h, w, gamma, beta)


def _fused_step_bwd(eps, residuals, g):
    xh, h, w, gamma, beta = residuals
    batch, k = xh.shape
    hidden = h.shape[-1]
    three_h = 3 * hidden
    dxh, dh, dw, dgamma, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, hidden=hidden, eps=eps),
        in_specs=_specs(batch, k, hidden) + [pl.BlockSpec((batch, hidden), lambda: (0, 0))],
        out_specs=[
            pl.BlockSpec((batch, k), lambda: (0, 0)),
            pl.BlockSpec((batch, hidden), lambda: (0, 0)),
            pl.BlockSpec((k, three_h), lambda: (0, 0)),
            pl.BlockSpec((1, three_h), lambda: (0, 0)),
            pl.BlockSpec((1, three_h), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, k), xh.dtype),
            jax.ShapeDtypeStruct((batch, hidden), h.dtype),
            jax.ShapeDtypeStruct((k, three_h), w.dtype),
            jax.ShapeDtypeStruct((1, three_h), jnp.float32),
            jax.ShapeDtypeStruct((1, three_h), jnp.float32),
        ],
        interpret=_interpret(),
    )(xh, h, w, gamma, beta, g)
    return dxh, dh, dw, dgamma[0].astype(gamma.dtype), dbeta[0].astype(beta.dtype)


fused_gru_step.defvjp(_fused_step_fwd, _fused_step_bwd)


def reference_gru_step(xh, h, w, gamma, beta, eps: float = 1e-3):
    """Plain-XLA same math: the parity target and the non-fused fallback."""
    proj = jnp.dot(xh, w, preferred_element_type=jnp.float32)
    n, _, _ = _ln(proj, gamma.astype(jnp.float32), beta.astype(jnp.float32), eps)
    out, _, _, _ = _gates(n, h.astype(jnp.float32), h.shape[-1])
    return out.astype(h.dtype)
