"""Blockwise attention of a chunk's queries (or of one query a row whose query rows a key
head fill a bfloat16 tile) over a carried cache (Pallas, TPU): the scores stay on the
chip, and a key block that a row has not filled is not visited.

``ops/ring_attention.py::_grouped_attention`` writes the float32 scores ``[B, Hkv, G, Tq,
slots]`` to HBM and walks them once each for the mask, the softmax and the cast, and
several times more in the backward pass: for a 64 x 64-token chunk of 32 query heads over
8,192 slots that is 4.3 GB a pass, and the time goes with those passes, not with the
products (PERF.md, PR 32).  Here a grid step holds one row's query tile ``[Tq * G, D]``
and one key block ``[KEY_BLOCK, D]`` in VMEM, forms their scores, masks them by position
and segment as ``_block_mask`` does, and folds them into a running maximum, denominator
and accumulator (online softmax); nothing of the scores' size leaves the chip.  The
backward kernel recomputes a block's probabilities from the saved row statistics and
accumulates ``dq``; the cache is an input of the update and takes no gradient, so no
``dk`` / ``dv`` product runs over it and nothing of its size is written.

Which blocks a row visits is decided before the kernel, row by row: ``key_block_flags``
marks the blocks that hold a key some query of the row sees (a slot never written, a
ring slot out of the window's reach and a cache whose episode ended are seen by none).
The flags and, for every step, the block to have in VMEM ride in as scalar prefetch: a
skipped step's ``index_map`` names the block that is there already, so it copies nothing,
and its body does not run.

The chunk's own keys (``Tq`` of them) are not the kernel's: ``cache_and_own_attention``
attends to them in plain XLA and merges the two parts by their log-sum-exp.  One
``custom_vjp`` spans both parts, so the kernel's backward pass reads the output's
cotangent as it arrives and the whole softmax's statistics.

Precision: operands enter the products in their own dtype with float32 accumulation,
stated (``HIGHEST`` for float32 operands, ``DEFAULT`` for narrower ones, whatever the
process-wide default); scores, statistics and accumulators are float32; probabilities
are cast to the values' dtype before the second product, as ``_grouped_attention`` casts
them; ``dS`` enters ``dq``'s product in float32.

Like every Mosaic custom call it is not partitioned by GSPMD: given a mesh of several
devices the kernels run under ``shard_map`` over the ``data`` axis (rows are independent).
Off the TPU the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

#: keys a grid step attends to; a cache of fewer slots, or of a number it does not divide, is one block
KEY_BLOCK = 512
#: the most query rows (tokens x query heads of one key head) a grid step holds
QUERY_ROWS = 1024
#: the query rows a key head that one query a row needs to go blockwise: a whole bfloat16 (16, 128) tile.
#: Fewer pay a grid step a row and a key block (~0.35 us skipped) for a few products, more than the
#: whole scores cost them (PERF.md section 7, PR 36)
ONE_QUERY_ROWS = 16
#: the VMEM a kernel is granted without asking, less some room, and the most it asks for (a v5e has 128 MiB)
VMEM_GRANTED, VMEM_MOST = 14 << 20, 96 << 20
#: a masked score: finite, so that a row that has seen no key yet subtracts it from itself
MASKED = float(jnp.finfo(jnp.float32).min)
_CONTRACT_LAST = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]
_CONTRACT_FIRST = (((1,), (0,)), ((), ()))  # [m, n] x [n, d] -> [m, d]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _precision(dtype: Any):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_precision(a.dtype), preferred_element_type=jnp.float32)


class Visited(NamedTuple):
    """What ``ring_attention.grouped_attention`` says of a call that went blockwise."""

    flags: jax.Array  # ``key_block_flags``: [B, blocks] int32, 1: visited
    how: dict  # the call's static facts, for the program's note: query tile, key block, where the chunk's own keys join


def tiles(rows: int, slots: int, head_dim: int, value_dim: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """``(query tile, key block)`` for ``rows`` query rows a key head over a cache of
    ``slots`` keys ``head_dim`` wide with values ``value_dim`` wide (the keys' width unless
    given), or ``None`` where the kernel does not take the shape: on the chip a block's
    minor dimensions are whole (8, 128) tiles."""
    key_block = KEY_BLOCK if slots % KEY_BLOCK == 0 else slots
    query_tile = next((t for t in range(min(rows, QUERY_ROWS), 0, -1) if rows % t == 0 and (t == rows or t % 16 == 0)), None)
    ok = key_block <= 2 * KEY_BLOCK and query_tile is not None
    if not _interpret():
        ok = ok and head_dim % 128 == 0 and (value_dim or head_dim) % 128 == 0 and key_block % 128 == 0 and query_tile % 8 == 0
    return (query_tile, key_block) if ok else None


def visible(q_pos, q_seg, kv_pos, kv_seg, window: Optional[int]):
    """``[.., Tq, 1]`` query ids against ``[.., 1, Tk]`` key ids -> ``[.., Tq, Tk]``: a key is
    visible iff it is of the query's segment, not after it and, with ``window``, fewer
    than ``window`` positions before it (``ring_attention._block_mask``, causal)."""
    mask = (kv_seg == q_seg) & (kv_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - kv_pos < window)
    return mask


def key_block_flags(q_pos, q_seg, kv_pos, kv_seg, window: Optional[int], key_block: int) -> jax.Array:
    """``[B, slots // key_block]`` int32: 1 where the block holds a key that some query
    of the row sees.  ``q_pos`` / ``q_seg``: ``[B, Tq]``, ``kv_pos`` / ``kv_seg``: ``[B, slots]``."""
    seen = visible(q_pos[:, :, None], q_seg[:, :, None], kv_pos[:, None, :], kv_seg[:, None, :], window).any(1)
    return seen.reshape(seen.shape[0], -1, key_block).any(-1).astype(jnp.int32)


def _block_to_hold(flags: jax.Array) -> jax.Array:
    """For every step the key block to have in VMEM: its own where flagged, else the last
    flagged one before it (it is there already: no copy), else the first one after."""
    idx = jnp.arange(flags.shape[1], dtype=jnp.int32)[None]
    before = jax.lax.cummax(jnp.where(flags > 0, idx, -1), axis=1)
    return jnp.where(before >= 0, before, jnp.argmax(flags > 0, axis=1).astype(jnp.int32)[:, None])


def _scores(q_ref, k_ref, qpos_ref, qseg_ref, kpos_ref, kseg_ref, scale, window):
    s = _dot(q_ref[...], k_ref[...], _CONTRACT_LAST) * scale  # [rows, keys] float32
    mask = visible(qpos_ref[...], qseg_ref[...], kpos_ref[...], kseg_ref[...], window)
    return jnp.where(mask, s, MASKED), mask


def _fwd_kernel(flags_ref, hold_ref, q_ref, k_ref, v_ref, qpos_ref, qseg_ref, kpos_ref, kseg_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, window):  # fmt: skip
    del hold_ref  # the index maps' own
    b, j, n = pl.program_id(0), pl.program_id(3), pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(flags_ref[b * n + j] > 0)
    def _():
        s, mask = _scores(q_ref, k_ref, qpos_ref, qseg_ref, kpos_ref, kseg_ref, scale, window)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # a row all masked has s == m_new: exp gives 1 there
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + _dot(p.astype(v_ref.dtype), v_ref[...], _CONTRACT_FIRST)
        m_scr[...] = m_new

    @pl.when(j == n - 1)
    def _():
        l = l_scr[...]
        o_ref[...] = acc_scr[...] / jnp.where(l > 0, l, 1.0)
        lse_ref[...] = jnp.where(l > 0, m_scr[...] + jnp.log(jnp.where(l > 0, l, 1.0)), MASKED)


def _bwd_kernel(flags_ref, hold_ref, q_ref, k_ref, v_ref, qpos_ref, qseg_ref, kpos_ref, kseg_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scr, *, scale, window):  # fmt: skip
    del hold_ref
    b, j, n = pl.program_id(0), pl.program_id(3), pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(flags_ref[b * n + j] > 0)
    def _():
        s, mask = _scores(q_ref, k_ref, qpos_ref, qseg_ref, kpos_ref, kseg_ref, scale, window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[...]), 0.0)
        dp = _dot(do_ref[...], v_ref[...], _CONTRACT_LAST)
        ds = p * (dp - delta_ref[...])
        acc_scr[...] += _dot(ds, k_ref[...].astype(jnp.float32), _CONTRACT_FIRST)

    @pl.when(j == n - 1)
    def _():
        dq_ref[...] = acc_scr[...] * scale


def _vmem_limit(query_tile: int, key_block: int, D: int, Dv: int, itemsize: int, values_in_keys: bool = False) -> Optional[int]:
    """The VMEM to ask the compiler for, or ``None`` where a grid step fits what it grants
    unasked (``VMEM_GRANTED``): the step's blocks twice (they are double-buffered; one
    cache block where the values are in the keys'), its float32 output and accumulator,
    and some five score-sized float32 temporaries."""
    wide = max(D, Dv)
    blocks = itemsize * (query_tile * (D + Dv) + key_block * (D + (0 if values_in_keys else Dv)))
    step = 2 * blocks + 3 * 4 * query_tile * wide + 5 * 4 * query_tile * key_block
    return None if step <= VMEM_GRANTED else min(2 * step, VMEM_MOST)


def _call(kernel, flags, args, Dv, out_widths, scratch_widths, tile, scale, window, mesh):
    """One of the two kernels over the grid (row, key head, query tile, key block).
    ``args``: ``q [B, H, R, D]``, the cache's ``k`` ``[B, slots, H * D]`` and ``v`` ``[B, slots, H
    * Dv]`` (a key head's values are column block ``h``), or ``None``: the values are the
    first ``Dv`` columns of the keys' own block (one key head), which a visited step then
    copies into VMEM once for both products; ``q_pos``, ``q_seg`` ``[B, R, 1]``, ``kv_pos``,
    ``kv_seg`` ``[B, 1, slots]``, then any more arrays shaped by the queries (``[B, H, R,
    width]``).  Outputs (``[B, H, R, width]``) and scratch (``[query tile, width]``) are
    float32, one of each width given."""
    B, H, R, D = args[0].shape
    n_blocks = flags.shape[1]
    query_tile, key_block = tile
    kernel, values = functools.partial(kernel, scale=scale, window=window), [Dv]
    if args[2] is None:
        kernel, values, args = _values_in_keys(kernel, Dv), [], (*args[:2], *args[3:])

    def rows(width):  # a block of an array shaped by the queries
        return pl.BlockSpec((None, None, query_tile, width), lambda b, h, i, j, *_: (b, h, i, 0))

    def cache(width):  # a key head's block of the cache: keys, or values
        return pl.BlockSpec((None, key_block, width), lambda b, h, i, j, flags, hold: (b, hold[b * n_blocks + j], h))

    q_id = pl.BlockSpec((None, query_tile, 1), lambda b, h, i, j, *_: (b, i, 0))
    kv_id = pl.BlockSpec((None, 1, key_block), lambda b, h, i, j, flags, hold: (b, 0, hold[b * n_blocks + j]))
    limit = _vmem_limit(query_tile, key_block, D, Dv, args[0].dtype.itemsize, values_in_keys=not values)

    def run(flags, *args):
        B = args[0].shape[0]  # a shard's rows under ``shard_map``
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, H, R // query_tile, n_blocks),
                in_specs=[rows(D), cache(D), *map(cache, values), q_id, q_id, kv_id, kv_id] + [rows(a.shape[-1]) for a in args[6 + len(values) :]],
                out_specs=[rows(w) for w in out_widths],
                scratch_shapes=[pltpu.VMEM((query_tile, w), jnp.float32) for w in scratch_widths],
            ),
            out_shape=[jax.ShapeDtypeStruct((B, H, R, w), jnp.float32) for w in out_widths],
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"), **({"vmem_limit_bytes": limit} if limit else {})),
            interpret=_interpret(),
        )(flags.reshape(-1), _block_to_hold(flags).reshape(-1), *args)

    if mesh is not None and mesh.size > 1:  # rows are independent: each device its own, or all of them where they do not divide
        spec = P("data") if B % mesh.shape["data"] == 0 else P()
        run = jax.shard_map(run, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    return run(flags, *args)


def _values_in_keys(kernel, Dv):
    """``kernel`` with its values' block the first ``Dv`` columns of the keys' block: a view
    of what is in VMEM already, not a second copy of the cache's block."""

    def shared(flags_ref, hold_ref, q_ref, k_ref, *refs):
        return kernel(flags_ref, hold_ref, q_ref, k_ref, k_ref.at[:, :Dv], *refs)

    return shared


def _by_key_head(x, Hkv):
    """``[B, Tq, Hq, D]`` -> ``[B, Hkv, Tq * G, D]``: a key head's query rows, token-major."""
    B, Tq, Hq, D = x.shape
    return x.reshape(B, Tq, Hkv, Hq // Hkv, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, Tq * Hq // Hkv, D)


def _by_token(x, Tq):
    """``_by_key_head``'s inverse: ``[B, Hkv, Tq * G, D]`` -> ``[B, Tq, Hkv * G, D]``."""
    B, Hkv, R, D = x.shape
    return x.reshape(B, Hkv, Tq, R // Tq, D).transpose(0, 2, 1, 3, 4).reshape(B, Tq, Hkv * R // Tq, D)


def _cache_and_ids(cache_k, cache_v, q_pos, q_seg, kv_pos, kv_seg, G):
    """The kernels' view of the cache and the ids.  A key head is a lane-aligned column
    block of ``[B, slots, Hkv * D]``, which is the cache as it lies for one key head (the
    chip keeps ``[B, slots, 1, D]`` in that order; any other view of it is a copy of it).
    ``cache_v`` ``None`` (values that are the keys' first columns) stays ``None``."""
    heads_side_by_side = lambda x: None if x is None else x.reshape(*x.shape[:2], -1)  # noqa: E731
    per_query_row = lambda x: jnp.repeat(x, G, axis=1)[:, :, None]  # noqa: E731  [B, Tq] -> [B, Tq * G, 1]
    return heads_side_by_side(cache_k), heads_side_by_side(cache_v), per_query_row(q_pos), per_query_row(q_seg), kv_pos[:, None, :], kv_seg[:, None, :]


def _own_part(q, k, q_pos, q_seg, scale, window):
    """The chunk's own keys in plain XLA: masked float32 scores ``[B, Hkv, G, Tq, Tk]``,
    their mask, and the grouped queries.  (With the scores token-major, ``[B, Hkv, Tq, G,
    Tk]``, the kernels' row statistics would need no transposition, and the update step
    took 3.2 ms longer on the chip: PERF.md, PR 33.)"""
    B, Tq, Hq, D = q.shape
    qg = q.reshape(B, Tq, k.shape[2], -1, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=_precision(q.dtype), preferred_element_type=jnp.float32) * scale
    mask = visible(q_pos[:, :, None], q_seg[:, :, None], q_pos[:, None, :], q_seg[:, None, :], window)[:, None, None]
    return jnp.where(mask, s, MASKED), mask, qg


def _rows_of(x, Tq):
    """``[B, Hkv, Tq * G, 1]`` (the kernel's row statistics) -> ``[B, Hkv, G, Tq, 1]`` (the own part's)."""
    B, Hkv, R, _ = x.shape
    return x.reshape(B, Hkv, Tq, R // Tq, 1).swapaxes(2, 3)


def _to_rows(x):
    """``_rows_of``'s inverse."""
    B, Hkv, G, Tq, _ = x.shape
    return x.swapaxes(2, 3).reshape(B, Hkv, Tq * G, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13))
def cache_and_own_attention(q, k, v, cache_k, cache_v, flags, q_pos, q_seg, kv_pos, kv_seg, scale, window, tile, mesh):
    """Attention of ``q`` ``[B, Tq, Hq, D]`` over a cache (``cache_k``: ``[B, slots, Hkv, D]``,
    ``cache_v``: ``[B, slots, Hkv, Dv]`` with ``kv_pos``, ``kv_seg`` ``[B, slots]``) and the
    chunk's own ``k`` ``[B, Tq, Hkv, D]`` and ``v`` ``[B, Tq, Hkv, Dv]`` (at ``q_pos``,
    ``q_seg``), one softmax over both -> ``[B, Tq, Hq, Dv]`` in ``q.dtype``.  The values may
    be narrower than the keys; ``cache_v`` ``None``: they are the first ``Dv`` columns of
    ``cache_k`` (one key head: a latent that is key and value at once, stored once and read
    once).  ``flags``: ``key_block_flags``; ``tile``: ``tiles``'s pair.
    Differentiable in ``q``, ``k`` and ``v``; the cache takes no gradient."""
    return _attention_fwd(q, k, v, cache_k, cache_v, flags, q_pos, q_seg, kv_pos, kv_seg, scale, window, tile, mesh)[0]


def _attention_fwd(q, k, v, cache_k, cache_v, flags, q_pos, q_seg, kv_pos, kv_seg, scale, window, tile, mesh):
    Tq, Hkv = q.shape[1], k.shape[2]
    held = _cache_and_ids(cache_k, cache_v, q_pos, q_seg, kv_pos, kv_seg, q.shape[2] // Hkv)
    Dv = v.shape[3]
    o_c, lse_c = _call(_fwd_kernel, flags, (_by_key_head(q, Hkv), *held), Dv, (Dv, 1), (1, 1, Dv), tile, scale, window, mesh)
    s, mask, _ = _own_part(q, k, q_pos, q_seg, scale, window)
    lse_c = _rows_of(lse_c, Tq)
    lse = jnp.logaddexp(lse_c, jax.nn.logsumexp(s, -1, keepdims=True))  # [B, Hkv, G, Tq, 1]; MASKED (about) where no key is seen
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    own = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v, precision=_precision(v.dtype), preferred_element_type=jnp.float32)
    share_c = _to_rows(jnp.exp(lse_c - lse))  # the cache's share of the softmax (its part is zeros where it is seen by none)
    out = _by_token(_by_key_head(own.reshape(*q.shape[:3], Dv), Hkv) + share_c * o_c, Tq)  # float32
    return out.astype(q.dtype), (q, k, v, cache_k, cache_v, flags, q_pos, q_seg, kv_pos, kv_seg, out, lse)


def _attention_bwd(scale, window, tile, mesh, residuals, do):
    q, k, v, cache_k, cache_v, flags, q_pos, q_seg, kv_pos, kv_seg, out, lse = residuals
    Tq, Hkv = q.shape[1], k.shape[2]
    held = _cache_and_ids(cache_k, cache_v, q_pos, q_seg, kv_pos, kv_seg, q.shape[2] // Hkv)
    delta = _by_key_head((do.astype(jnp.float32) * out).sum(-1, keepdims=True), Hkv)  # [B, Hkv, R, 1]
    per_row = (_by_key_head(do, Hkv), _to_rows(lse), delta)
    (dq_c,) = _call(_bwd_kernel, flags, (_by_key_head(q, Hkv), *held, *per_row), v.shape[3], (q.shape[3],), (q.shape[3],), tile, scale, window, mesh)
    s, mask, qg = _own_part(q, k, q_pos, q_seg, scale, window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dog = do.reshape(*qg.shape[:4], v.shape[3])
    full = jax.lax.Precision.HIGHEST  # a float32 operand (dS) enters these products whole
    dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, v, precision=_precision(v.dtype), preferred_element_type=jnp.float32)
    ds = p * (dp - _rows_of(delta, Tq)) * scale
    dq = jnp.einsum("bhgqk,bkhd->bqhgd", ds, k.astype(jnp.float32), precision=full).reshape(q.shape) + _by_token(dq_c, Tq)
    dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg.astype(jnp.float32), precision=full)
    dv = jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(v.dtype), dog, precision=_precision(v.dtype), preferred_element_type=jnp.float32)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)) + (None,) * 7


cache_and_own_attention.defvjp(_attention_fwd, _attention_bwd)
