"""Ring attention: sequence-parallel exact attention over the ``sequence`` mesh axis.

The reference framework has no attention module and no context parallelism at all
(SURVEY §2.4/§5: "no TP/PP/SP/EP/CP/ring-attention anywhere") — long-context support is
a capability this framework adds natively.  The ``sequence`` axis reserved by
``build_mesh`` becomes usable: queries stay put, key/value blocks rotate around the
ring (``lax.ppermute`` over ICI neighbours), and a flash-style online-softmax
accumulator keeps the result EXACT while each device only ever holds ``T/ring`` keys —
memory per device is O(T·d/ring + T²/ring²) instead of O(T²).

Shapes follow the usual convention: ``q, k, v: [B, T_local, H, D]`` sharded over the
time axis (``PartitionSpec(None, "sequence")``).  ``ring_attention`` is the per-device
function for use inside ``shard_map``; ``make_ring_attention`` wraps it with the
``shard_map`` plumbing for a given mesh.  Causal masking uses global positions, so the
semantics match full causal attention regardless of the ring size.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.ops import blockwise_attention

_log = logging.getLogger(__name__)


def _block_mask(q_pos, kv_pos, causal, q_seg=None, kv_seg=None, window=None):
    """[B?, Tq, Tk] boolean mask combining causality, segment equality (episode
    boundaries) and a sliding attention window; None when nothing masks.

    ``q_pos`` / ``kv_pos``: ``[Tq]`` / ``[Tk]`` positions shared by the batch, or
    ``[B, Tq]`` / ``[B, Tk]`` absolute positions of their own for every row (a
    decoder's chunk against its carried cache, ``grouped_attention``)."""
    mask = None
    if causal:
        mask = kv_pos[..., None, :] <= q_pos[..., :, None]  # [B?, Tq, Tk]
    if window is not None:
        # A window always excludes the future too ("the LAST `window` positions"),
        # so window-only attention is causal-windowed by construction.
        delta = q_pos[..., :, None] - kv_pos[..., None, :]
        w = (delta >= 0) & (delta < window)
        mask = w if mask is None else (mask & w)
    if mask is not None and mask.ndim == 2:
        mask = mask[None]  # broadcast over batch
    if q_seg is not None:
        seg = q_seg[:, :, None] == kv_seg[:, None, :]  # [B, Tq, Tk]
        mask = seg if mask is None else (mask & seg)
    return mask


def _attn_block(q, k_blk, v_blk, acc, m, l, scale, mask):
    """One flash-attention accumulation step against a single kv block.

    ``acc``: [B, H, Tq, D] un-normalised output; ``m``: [B, H, Tq] running max;
    ``l``: [B, H, Tq] running denominator; ``mask``: [B|1, Tq, Tk] or None."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale  # [B, H, Tq, Tk]
    if mask is not None:
        s = jnp.where(mask[:, None], s, jnp.finfo(s.dtype).min)
    m_new = jnp.maximum(m, s.max(-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        # re-mask: a fully-masked row has s == m_new == finfo.min everywhere, so the
        # exp above would contribute p = 1 per masked entry without this zeroing
        p = jnp.where(mask[:, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(-1)
    acc = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk)
    return acc, m_new, l


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array = None,
    axis_name: str = "sequence",
    causal: bool = False,
    window: int = None,
) -> jax.Array:
    """Per-device ring attention body (call inside ``shard_map``).

    ``q, k, v``: the LOCAL ``[B, T_local, H, D]`` blocks of a global ``[B, T, H, D]``
    sequence sharded over ``axis_name``; ``segment_ids``: optional local ``[B,
    T_local]`` int segments (attention never crosses a segment boundary — episode
    masking); ``window``: optional sliding-window size (a query attends to at most
    the last ``window`` positions).  Returns the local ``[B, T_local, H, D]`` output
    of exact attention over the full sequence under those masks.
    """
    ring = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, T_local, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))  # f32, matching the accumulators

    q_pos = my_idx * T_local + jnp.arange(T_local)
    acc = jnp.zeros((B, H, T_local, D), jnp.float32)
    m = jnp.full((B, H, T_local), jnp.finfo(jnp.float32).min)
    l = jnp.zeros((B, H, T_local))

    qf, kf, vf = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    perm = [(i, (i + 1) % ring) for i in range(ring)]
    k_blk, v_blk = kf, vf
    kv_seg = segment_ids
    for r in range(ring):
        src = (my_idx - r) % ring  # which device's kv block we currently hold
        kv_pos = src * T_local + jnp.arange(T_local)
        mask = _block_mask(q_pos, kv_pos, causal, segment_ids, kv_seg, window)
        acc, m, l = _attn_block(qf, k_blk, v_blk, acc, m, l, scale, mask)
        if r + 1 < ring:
            # rotate kv (and its segments) around the ring; overlaps with the next
            # block's compute
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            if kv_seg is not None:
                kv_seg = jax.lax.ppermute(kv_seg, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, H, Tq, D]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def make_ring_attention(
    mesh: Mesh, axis_name: str = "sequence", causal: bool = False, window: int = None
):
    """Wrap ``ring_attention`` in ``shard_map`` for ``[B, T, H, D]`` inputs sharded
    over ``axis_name`` on ``mesh`` (time axis 1); optional ``[B, T]``
    ``segment_ids``."""
    spec = P(None, axis_name)
    body = functools.partial(ring_attention, axis_name=axis_name, causal=causal, window=window)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    fn_seg = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec, spec), out_specs=spec)

    def apply(q, k, v, segment_ids=None):
        sharding = NamedSharding(mesh, spec)
        q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
        if segment_ids is None:
            return fn(q, k, v)
        return fn_seg(q, k, v, jax.device_put(segment_ids, sharding))

    return apply


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    segment_ids: jax.Array = None,
    window: int = None,
) -> jax.Array:
    """Plain full-materialisation attention (same masks as ``ring_attention``) —
    the single-device path and the parity oracle for the ring."""
    B, T, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    pos = jnp.arange(T)
    mask = _block_mask(pos, pos, causal, segment_ids, segment_ids, window)
    if mask is not None:
        s = jnp.where(mask[:, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, -1)
    if mask is not None:
        p = jnp.where(mask[:, None], p, 0.0)  # fully-masked rows attend to nothing
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def grouped_attention(q, k, v, cache, q_pos, q_seg, window=None, head_dim=None, mesh=None):
    """Grouped-query attention of a chunk's queries over a carried cache, then the chunk's
    own keys, masked by absolute position, one softmax over both.

    ``q``: ``[B, Tq, Hq, D]``; ``k``, ``v``: the chunk's own ``[B, Tq, Hkv, D]`` and ``[B, Tq,
    Hkv, Dv]`` with ``Hq`` a multiple of ``Hkv`` (query head ``h`` reads key head ``h // (Hq //
    Hkv)``) and ``Dv`` the keys' width or less; ``cache``: ``(k, v, kv_pos, kv_seg)`` with ``k``:
    ``[B, slots, Hkv, D]`` and ``v``: ``[B, slots, Hkv, Dv]``, or ``None`` where, with one key
    head, the values are the first ``Dv`` columns of ``k`` (a latent that is key and value at
    once, stored once and read once); ``q_pos`` / ``kv_pos``: ``[B, Tq]`` / ``[B, slots]``
    positions inside the episode; ``q_seg`` / ``kv_seg``: int segments (a key of another
    segment, e.g. an empty cache slot given ``-1``, is never visible).  A key is visible iff
    it is of the query's segment, not after it, and, with ``window``, fewer than ``window``
    positions before it.  The cache is read where it lies; scores and softmax are float32,
    the scores over ``sqrt(head_dim)`` (``D`` unless given: queries padded with zeros to a
    wider ``D`` keep their own).  Returns ``[B, Tq, Hq, Dv]`` in ``q.dtype`` (a query that sees
    no key returns zeros) and what a blockwise call visited.  The cache is an input, the
    carry as it stood: it takes no gradient, whichever way the call goes (``q`` and the
    chunk's own ``k``, ``v`` do).

    By the shape of the call: a chunk of queries goes blockwise through the cache with the
    scores kept on the chip and the key blocks that the row has not filled skipped
    (``ops/blockwise_attention.py``; its ``Visited``: the ``[B, blocks]`` flags and the tile
    taken), where that kernel takes the shapes; so does one query a row (an acting step,
    whose time is the cache's read) where its query rows a key head fill whole bfloat16
    tiles (``blockwise_attention.ONE_QUERY_ROWS``).  Other one-query calls form the scores
    whole, ``_grouped_attention``, and nothing is said of blocks (``None``): the kernel would
    take a grid step a row and a key block for a few products.  A chunk whose shapes the
    kernel does not take forms its scores whole too, ``B * Hq * Tq * slots`` of them in
    float32 at once, and on the chip says so in the log.  ``mesh``: the devices the rows are
    spread over, if several."""
    B, Tq, Hq, D = q.shape
    ck, cv, kv_pos, kv_seg = cache
    if cv is None and k.shape[2] != 1:
        raise ValueError(f"values that are the keys' first columns need one key head: keys {ck.shape}, values {v.shape}")
    rows = Tq * Hq // k.shape[2]
    tile = blockwise_attention.tiles(rows, kv_pos.shape[1], D, v.shape[-1]) if Tq > 1 or rows % blockwise_attention.ONE_QUERY_ROWS == 0 else None
    if tile is None:
        if Tq > 1 and jax.default_backend() == "tpu":
            _log.warning("grouped_attention: a chunk %s forms its float32 scores whole: the blockwise kernel does not take %d slots of width %d", q.shape[:3], kv_pos.shape[1], D)  # fmt: skip
        held = (jax.lax.stop_gradient(ck), jax.lax.stop_gradient(ck if cv is None else cv), kv_pos, kv_seg)
        return _grouped_attention(q, [held, (k, v, q_pos, q_seg)], q_pos, q_seg, window, head_dim), None
    flags = blockwise_attention.key_block_flags(q_pos, q_seg, kv_pos, kv_seg, window, tile[1])
    scale = float((head_dim or D) ** -0.5)
    out = blockwise_attention.cache_and_own_attention(q, k, v, ck, cv, flags, q_pos, q_seg, kv_pos, kv_seg, scale, window, tile, mesh)
    return out, blockwise_attention.Visited(flags, {"query_tile": tile[0], "key_block": tile[1], "own_keys": "merged outside the kernel"})


def _grouped_attention(q, blocks, q_pos, q_seg, window, head_dim) -> jax.Array:
    """``grouped_attention`` with the scores of every block of keys (``(k, v, kv_pos, kv_seg)``
    each) formed whole in float32 and one softmax over them all: the acting path, and
    the oracle of the blockwise one.  The values are as wide as the narrowest block's; a
    block handed a wider array (the keys' own) gives its first columns, taken after the
    product: the array is read as it lies, and a step's output is small."""
    B, Tq, Hq, D = q.shape
    Hkv = blocks[0][0].shape[2]
    qg = q.reshape(B, Tq, Hkv, Hq // Hkv, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim or D, jnp.float32))
    scores, masks = [], []
    for k, _, kv_pos, kv_seg in blocks:
        scores.append(jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32) * scale)
        masks.append(_block_mask(q_pos, kv_pos, True, q_seg, kv_seg, window)[:, None, None])
    mask = jnp.concatenate(masks, -1)
    s = jnp.where(mask, jnp.concatenate(scores, -1), jnp.finfo(jnp.float32).min)
    p = jnp.where(mask, jax.nn.softmax(s, -1), 0.0)
    Dv = min(v.shape[-1] for _, v, _, _ in blocks)  # a block whose values are the first columns of a wider array is multiplied whole
    out, at = 0.0, 0
    for _, v, kv_pos, _ in blocks:
        n = kv_pos.shape[1]
        part = jnp.einsum("bhgqk,bkhd->bqhgd", p[..., at : at + n].astype(v.dtype), v, preferred_element_type=jnp.float32)
        out = out + (part if v.shape[-1] == Dv else part[..., :Dv])
        at += n
    return out.reshape(B, Tq, Hq, Dv).astype(q.dtype)
