"""The selective state-space scan of a Mamba-2 layer, in its chunked (state-space duality,
SSD) form for a chunk of tokens and as the one-token recurrence for an acting step.

Per head ``h`` of group ``g`` (``H // G`` heads a group, head-major: head ``h`` reads group
``h // (H // G)``), with ``a_t = dt_t * A_h`` (``A_h < 0``):

    state_t = exp(a_t) state_{t-1} + dt_t * x_t (outer) B_{g,t}        [P, N]
    y_t     = state_t C_{g,t}                                          [P]

(the skip ``D_h x_t`` is the caller's).  An episode that starts at token ``t`` starts from an
empty state: ``seg`` (``models/decoder.py::positions``) numbers each row's episodes from 0,
the carried one, which alone reads the carried state ``state_0``.

The chunked form cuts the tokens into chunks of ``chunk`` and computes, with ``S_t`` the
cumulative sum of ``a`` inside a chunk:

* inside a chunk, ``y_t = sum_{s <= t, same segment} exp(S_t - S_s) (C_t . B_s) dt_s x_s``:
  two products a chunk, of ``[Q, N] x [N, Q]`` a group and ``[Q, Q] x [Q, P]`` a head;
* the state a chunk passes on, ``exp(S_last) state_in + sum_s exp(S_last - S_s) dt_s x_s
  (outer) B_s`` over the tokens of the chunk's last segment (the entering state is dropped
  where an episode starts inside the chunk);
* what the entering state gives its chunk's tokens of its own segment: ``exp(S_t) state_in
  C_t``.

The states between chunks are the one sequential part (``lax.scan`` over the chunks, so
its differentiation holds each chunk's entering state once).  Decays and cumulative sums
are in float32; every product takes its operands in the compute dtype and accumulates in
float32, at the precision ``_product`` states.  The state is carried in float32: it is a
decayed sum over the whole episode.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def _product(spec: str, a: jax.Array, b: jax.Array, dtype: Any) -> jax.Array:
    """``einsum`` of two operands cast to ``dtype``, accumulated in float32; float32 operands
    multiply in full, a narrow compute dtype as it is (``models/decoder.py::_precision``)."""
    precision = jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else jax.lax.Precision.DEFAULT
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), precision=precision, preferred_element_type=jnp.float32)


def chunks_of(tokens: int, chunk: int) -> int:
    """How many chunks the scan cuts ``tokens`` tokens into."""
    return -(-tokens // chunk)


def ssd_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, seg: jax.Array, state0: jax.Array, chunk: int, dtype: Any
) -> Tuple[jax.Array, jax.Array]:
    """``x``: ``[b, T, H, P]``, ``dt``: ``[b, T, H]`` (after its softplus), ``A``: ``[H]``,
    ``B`` / ``C``: ``[b, T, G, N]``, ``seg``: ``[b, T]``, ``state0``: ``[b, H, P, N]`` float32
    -> ``y`` ``[b, T, H, P]`` float32 and the state after the last token (of its segment)."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    K, Q = H // G, min(chunk, T)
    nc = chunks_of(T, Q)
    pad = nc * Q - T

    def cut(v, mode="constant"):  # [b, T, ...] -> [b, nc, Q, ...]; the padding adds no input and no decay
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2), mode=mode)
        return v.reshape(b, nc, Q, *v.shape[2:])

    xc, Bc, Cc = cut(x), cut(B), cut(C)
    dtc = cut(dt.astype(jnp.float32))
    segc = cut(seg, "edge")
    S = jnp.cumsum(dtc * A.astype(jnp.float32), axis=2)  # [b, nc, Q, H]
    seg_in = jnp.concatenate([jnp.zeros((b, 1), segc.dtype), segc[:, :-1, -1]], 1)  # the segment of the state entering each chunk

    # inside a chunk: decay from s to t within one segment, 0 across an episode's start and above the diagonal
    St = jnp.moveaxis(S, 3, 2)  # [b, nc, H, Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    same = (segc[:, :, :, None] == segc[:, :, None, :]) & causal  # [b, nc, t, s]
    decay = jnp.exp(jnp.where(same[:, :, None], St[..., :, None] - St[..., None, :], -jnp.inf))  # [b, nc, H, t, s]
    cb = _product("bctgn,bcsgn->bcgts", Cc, Bc, dtype)  # [b, nc, G, t, s]
    weights = decay.reshape(b, nc, G, K, Q, Q) * cb[:, :, :, None]
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).reshape(b, nc, Q, G, K, P)
    y = _product("bcgkts,bcsgkp->bctgkp", weights, xdt, dtype)

    # the state each chunk passes on, and the one that enters it
    last = S[:, :, -1]  # [b, nc, H]
    keep = jnp.exp(last[:, :, None] - S) * (segc == segc[:, :, -1:])[..., None]  # [b, nc, Q, H]
    made = _product("bcsgkp,bcsgn->bcgkpn", xdt * keep.reshape(b, nc, Q, G, K)[..., None], Bc, dtype)
    carried_on = (segc[:, :, -1] == seg_in)[..., None]  # no episode starts inside the chunk
    passed = (jnp.exp(last) * carried_on).reshape(b, nc, G, K)

    def across(state, chunk_in):
        dec, new = chunk_in
        return dec[..., None, None] * state + new, state

    final, entering = jax.lax.scan(across, state0.astype(jnp.float32).reshape(b, G, K, P, N), (jnp.moveaxis(passed, 1, 0), jnp.moveaxis(made, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)  # [b, nc, G, K, P, N]
    reads = (jnp.exp(S) * (segc == seg_in[:, :, None])[..., None]).reshape(b, nc, Q, G, K)
    y = y + _product("bctgn,bcgkpn->bctgkp", Cc, entering, dtype) * reads[..., None]
    return y.reshape(b, nc * Q, H, P)[:, :T], final.reshape(b, H, P, N)


def ssd_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, state0: jax.Array, dtype: Any) -> Tuple[jax.Array, jax.Array]:
    """One token a row: ``x``: ``[b, H, P]``, ``dt``: ``[b, H]``, ``B`` / ``C``: ``[b, G, N]``,
    ``state0``: ``[b, H, P, N]`` float32 -> ``y`` ``[b, H, P]`` float32 and the new state.  The
    state's update is elementwise in float32; its read by ``C`` a product as the scan's."""
    K = x.shape[1] // B.shape[1]
    dt = dt.astype(jnp.float32)
    Bh, Ch = jnp.repeat(B, K, axis=1), jnp.repeat(C, K, axis=1)  # [b, H, N]
    state = jnp.exp(dt * A.astype(jnp.float32))[..., None, None] * state0 + (dt[..., None] * x.astype(jnp.float32))[..., None] * Bh.astype(jnp.float32)[:, :, None]
    return _product("bhpn,bhn->bhp", state, Ch, dtype), state


def resets_in_chunks(is_first: jax.Array, chunk: int) -> jax.Array:
    """``is_first``: ``[b, T]`` -> the share of (row, chunk) pairs whose chunk holds an
    episode's start: the chunks in which the scan cuts a decay."""
    b, T = is_first.shape
    Q = min(chunk, T)
    nc = chunks_of(T, Q)
    starts = jnp.pad(is_first > 0, [(0, 0), (0, nc * Q - T)]).reshape(b, nc, Q).any(-1)
    return starts.mean(dtype=jnp.float32)
