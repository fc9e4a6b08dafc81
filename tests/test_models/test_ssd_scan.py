"""The chunked state-space scan (``sheeprl_tpu/ops/ssd_scan.py``) against the Mamba-2
recurrence written out token by token here, on the CPU in float32: a 20-token chunk cut
into three scan chunks of 8 (the last one padded), a nonzero carried state, and episode
starts inside chunks, forward and gradient; and the one-token step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops.ssd_scan import chunks_of, resets_in_chunks, ssd_scan, ssd_step

B, T, H, P, G, N, CHUNK = 3, 20, 4, 8, 2, 16, 8


def inputs(seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, T, H, P)), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((B, T, H)) - 2.0)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, T, G, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, T, G, N)), jnp.float32)
    state0 = jnp.asarray(rng.standard_normal((B, H, P, N)), jnp.float32)
    is_first = np.zeros((B, T), np.float32)
    is_first[0, [5, 13]] = 1  # two starts, each inside a chunk
    is_first[1, 16] = 1  # a start inside the last chunk; the carried episode runs through two chunks
    # row 2 carries its episode through the whole chunk
    return x, dt, b, c, state0, is_first


A = -jnp.asarray([0.05, 0.3, 1.0, 4.0], jnp.float32)  # a head that keeps a token across the chunk, and one that forgets it at once


def recurrence(x, dt, b, c, state0, is_first):
    """``state_t = exp(dt_t A) state_{t-1} + dt_t x_t (outer) B_t``, ``y_t = state_t C_t``, from
    an empty state at an episode's start; head ``h`` reads group ``h // (H // G)``."""
    bh, ch = jnp.repeat(b, H // G, 2), jnp.repeat(c, H // G, 2)
    state, ys = state0, []
    for t in range(T):
        state = jnp.where((is_first[:, t] > 0)[:, None, None, None], 0.0, state)
        state = jnp.exp(dt[:, t] * A)[..., None, None] * state + (dt[:, t, :, None] * x[:, t])[..., None] * bh[:, t, :, None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, ch[:, t], precision="highest"))
    return jnp.stack(ys, 1), state


def chunked(x, dt, b, c, state0, is_first):
    seg = jnp.cumsum(jnp.asarray(is_first) > 0, 1, dtype=jnp.int32)
    return ssd_scan(x, dt, A, b, c, seg, state0, CHUNK, jnp.float32)


def test_three_chunks_with_a_carried_state_and_starts_inside_them_give_the_recurrence():
    x, dt, b, c, state0, is_first = inputs()
    assert chunks_of(T, CHUNK) == 3
    want, want_state = recurrence(x, dt, b, c, state0, is_first)
    got, got_state = jax.jit(chunked)(x, dt, b, c, state0, is_first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), atol=2e-5, rtol=1e-5)
    # the carried state reaches row 2's every token and row 0's first five, no token after a start
    alone, _ = jax.jit(chunked)(x, dt, b, c, jnp.zeros_like(state0), is_first)
    moved = np.abs(np.asarray(got - alone)).max((2, 3))
    assert (moved[0, :5] > 1e-3).all() and (moved[0, 5:] == 0).all() and (moved[1, 16:] == 0).all() and (moved[2] > 0).all()


def test_the_gradient_of_the_chunked_form_is_the_recurrences():
    x, dt, b, c, state0, is_first = inputs(1)
    w = jnp.asarray(np.random.default_rng(2).standard_normal((B, T, H, P)), jnp.float32)
    grad = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a, is_first)[0] * w), argnums=(0, 1, 2, 3, 4)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = grad(recurrence)(x, dt, b, c, state0)
    got = grad(chunked)(x, dt, b, c, state0)
    for name, g, r in zip(("x", "dt", "B", "C", "state0"), got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5 * float(jnp.abs(r).max()), err_msg=name)


@pytest.mark.parametrize("start", [False, True])
def test_one_token_is_the_scan_of_one_token(start):
    x, dt, b, c, state0, _ = inputs(3)
    y, state = jax.jit(lambda *a: ssd_step(*a, jnp.float32))(x[:, 0], dt[:, 0], A, b[:, 0], c[:, 0], state0)
    seg = jnp.full((B, 1), int(start), jnp.int32)
    want_y, want_state = ssd_scan(x[:, :1], dt[:, :1], A, b[:, :1], c[:, :1], seg, state0, CHUNK, jnp.float32)
    if start:  # the step itself is handed an emptied state by its caller
        y, state = ssd_step(x[:, 0], dt[:, 0], A, b[:, 0], c[:, 0], jnp.zeros_like(state0), jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-6)


def test_the_share_of_chunks_an_episode_start_cuts():
    _, _, _, _, _, is_first = inputs()
    # row 0: starts in chunks 0 and 1; row 1: in chunk 2; row 2: none -> 3 of 9
    assert float(resets_in_chunks(jnp.asarray(is_first), CHUNK)) == pytest.approx(3 / 9)
    assert float(resets_in_chunks(jnp.zeros((2, 256)), 128)) == 0.0
