"""bf16 parity for the fused Pallas RSSM step (howto/precision.md satellite).

The kernel upcasts to f32 in VMEM and casts back to the state dtype on the way
out, so feeding bf16 operands must track the f32 XLA reference within bf16
rounding — forward AND the hand-derived VJP.  Off-TPU this runs the kernel in
interpreter mode: the exact code path the TPU executes, minus Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
from sheeprl_tpu.ops.rssm_step import fused_gru_step, reference_gru_step

# bf16 has an 8-bit mantissa (~0.4% relative); the chained gate nonlinearities
# keep everything O(1) so absolute tolerances are meaningful.
FWD_ATOL = 2e-2
GRAD_ATOL = 6e-2


def _step_operands(rng, batch=8, k=96, hidden=64, dtype=jnp.bfloat16):
    xh = jnp.asarray(rng.normal(size=(batch, k)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(batch, hidden)).astype(np.float32))
    w = jnp.asarray(rng.normal(scale=k**-0.5, size=(k, 3 * hidden)).astype(np.float32))
    gamma = jnp.asarray(rng.normal(1.0, 0.1, size=(3 * hidden,)).astype(np.float32))
    beta = jnp.asarray(rng.normal(0.0, 0.1, size=(3 * hidden,)).astype(np.float32))
    f32 = (xh, h, w, gamma, beta)
    return tuple(x.astype(dtype) for x in f32), f32


def test_fused_rssm_step_bf16_forward_tracks_f32_reference():
    bf16, f32 = _step_operands(np.random.default_rng(2))
    out = fused_gru_step(*bf16)
    assert out.dtype == jnp.bfloat16
    ref = reference_gru_step(*f32)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=FWD_ATOL
    )


def test_fused_rssm_step_bf16_vjp_tracks_f32_reference():
    bf16, f32 = _step_operands(np.random.default_rng(3))

    def loss(fn, args):
        return jnp.sum(fn(*args).astype(jnp.float32))

    grads = jax.grad(lambda *a: loss(fused_gru_step, a), argnums=(0, 1, 2, 3, 4))(*bf16)
    ref = jax.grad(lambda *a: loss(reference_gru_step, a), argnums=(0, 1, 2, 3, 4))(*f32)
    for g, r, name in zip(grads, ref, ["xh", "h", "w", "gamma", "beta"]):
        assert g.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32), atol=GRAD_ATOL, err_msg=name
        )
