"""Blockwise attention of a chunk over a carried cache (``ops/blockwise_attention.py``, in
interpret mode here): outputs, the gradients of the queries and of the chunk's own keys
and values, and the key-block flags against ``ring_attention._grouped_attention``, which
forms the scores whole; and the kernels compiled at the benchmark's widths for a
described chip, with the acting call's expert layer beside them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder
from sheeprl_tpu.ops import blockwise_attention, ring_attention

KEY_BLOCK = 8  # of the tests' caches, so that a cache is several blocks


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    monkeypatch.setattr(blockwise_attention, "KEY_BLOCK", KEY_BLOCK)


def filled(fills, slots, ring=False):
    """``[B, slots]`` positions of caches that hold ``fills[b]`` tokens: slot ``p`` holds
    position ``p``, or (a ring) the newest position of ``p`` modulo ``slots``."""
    pos = np.full((len(fills), slots), -1, np.int32)
    for b, n in enumerate(fills):
        for p in range(max(n - slots, 0) if ring else 0, n):
            pos[b, p % slots] = p
    return jnp.asarray(pos)


def inputs(rng, fills, firsts, T, Hq, Hkv, D, slots, dtype, ring=False):
    """A chunk of ``T`` tokens a row after ``fills[b]`` carried ones, an episode starting at
    the tokens of ``firsts`` (``(row, token)`` pairs)."""
    B = len(fills)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    is_first = np.zeros((B, T), np.float32)
    for b, t in firsts:
        is_first[b, t] = 1
    q_pos, q_seg = decoder.positions(jnp.asarray(is_first), jnp.asarray(fills, jnp.int32))
    kv_pos = filled(fills, slots, ring)
    cache = (normal(B, slots, Hkv, D), normal(B, slots, Hkv, D), kv_pos, jnp.where(kv_pos >= 0, 0, -1))
    return (normal(B, T, Hq, D), normal(B, T, Hkv, D), normal(B, T, Hkv, D)), cache, q_pos, q_seg


def whole(qkv, cache, q_pos, q_seg, window, head_dim=None):
    q, k, v = qkv
    return ring_attention._grouped_attention(q, [cache, (k, v, q_pos, q_seg)], q_pos, q_seg, window, head_dim)


def out_and_grads(attend, qkv, weights):
    loss = lambda *qkv: (attend(qkv).astype(jnp.float32) * weights).sum()  # noqa: E731
    return jax.jit(lambda qkv: (attend(qkv), jax.grad(loss, argnums=(0, 1, 2))(*qkv)))(qkv)


# what a case is: (fills of the rows' caches, episode starts, T, Hq, Hkv, D, slots, window, ring)
CASES = {
    "seven_heads_on_one_wide_key_head": ((0, 5, 20, 32), (), 8, 7, 1, 128, 32, None, False),
    "a_window_and_a_ring_that_has_wrapped": ((3, 12, 40, 100), (), 8, 7, 1, 128, 16, 16, True),
    "a_window_shorter_than_the_ring": ((3, 12, 40, 100), (), 8, 4, 2, 16, 32, 10, True),
    "an_episode_that_starts_inside_the_chunk": ((9, 9, 20), ((0, 3), (1, 0), (2, 7)), 8, 4, 2, 16, 32, None, False),
    "an_empty_cache_and_full_ones": ((0, 0, 32), ((1, 0),), 6, 4, 1, 16, 32, None, False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_outputs_and_gradients_are_the_whole_scores_ones(case, dtype):
    fills, firsts, T, Hq, Hkv, D, slots, window, ring = CASES[case]
    rng = np.random.default_rng(0)
    qkv, cache, q_pos, q_seg = inputs(rng, fills, firsts, T, Hq, Hkv, D, slots, dtype, ring)
    weights = jnp.asarray(rng.standard_normal(qkv[0].shape), jnp.float32)
    blockwise = lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, window)  # noqa: E731
    assert blockwise(qkv)[1] is not None  # the chunk took the kernel
    got = out_and_grads(lambda qkv: blockwise(qkv)[0], qkv, weights)
    want = out_and_grads(lambda qkv: whole(qkv, cache, q_pos, q_seg, window), qkv, weights)
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=3e-2, atol=3e-2)
    for name, a, b in zip(("out", "dq", "dk", "dv"), jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.isfinite(np.asarray(a, np.float32)).all(), name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name, **tol)


def test_more_query_rows_than_a_grid_step_holds_go_in_tiles(monkeypatch):
    """32 query rows a key head under a limit of 16: two query tiles, each with its own
    statistics over the key blocks."""
    monkeypatch.setattr(blockwise_attention, "QUERY_ROWS", 16)
    assert blockwise_attention.tiles(8 * 4, 32, 16) == (16, KEY_BLOCK)
    rng = np.random.default_rng(7)
    qkv, cache, q_pos, q_seg = inputs(rng, (5, 20, 32), ((0, 6),), 8, 8, 2, 16, 32, jnp.float32)
    weights = jnp.asarray(rng.standard_normal(qkv[0].shape), jnp.float32)
    got = out_and_grads(lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg)[0], qkv, weights)
    want = out_and_grads(lambda qkv: whole(qkv, cache, q_pos, q_seg, None), qkv, weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_two_narrow_heads_a_lane_array_are_attended_as_one_wide_head(dtype, monkeypatch):
    """LFM2's call: the cache a tuple of ``[B, slots, 1, LANES]`` arrays with two heads side
    by side, the queries padded to the lane's width, the scores over ``sqrt(head_dim)``."""
    monkeypatch.setattr(decoder, "LANES", 16)
    rng = np.random.default_rng(1)
    B, T, Hq, Hkv, hd, slots = 3, 8, 8, 4, 8, 32
    (q, k, v), _, q_pos, q_seg = inputs(rng, (4, 17, 30), ((1, 5),), T, Hq, Hkv, hd, slots, dtype)
    kv_pos = filled((4, 17, 30), slots)
    lanes = lambda: tuple(jnp.asarray(rng.standard_normal((B, slots, 1, 16)), dtype) for _ in range(2))  # noqa: E731
    state = {"k": lanes(), "v": lanes(), "pos": kv_pos}
    cache_seg = jnp.where(kv_pos >= 0, 0, -1)
    unlaned = lambda xs: jnp.concatenate(xs, -1).reshape(B, slots, Hkv, hd)  # noqa: E731
    cache = (unlaned(state["k"]), unlaned(state["v"]), kv_pos, cache_seg)
    weights = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    laned = lambda qkv: decoder.lane_grouped_attention(*qkv, state, cache_seg, q_pos, q_seg, None)  # noqa: E731
    flags = laned((q, k, v))[1].flags
    np.testing.assert_array_equal(np.asarray(flags), [[1, 0, 0, 0], [1, 1, 1, 0], [1, 1, 1, 1]])
    got = out_and_grads(lambda qkv: laned(qkv)[0], (q, k, v), weights)
    want = out_and_grads(lambda qkv: whole(qkv, cache, q_pos, q_seg, None), (q, k, v), weights)
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=3e-2, atol=3e-2)
    for name, a, b in zip(("out", "dq", "dk", "dv"), jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name, **tol)


def test_a_query_that_sees_no_key_returns_zeros_and_takes_no_gradient():
    """Under a window of no position at all no query sees a key, its own included (no
    caller asks for that; every other mask leaves a query its own key): zeros out, zero
    gradients and no NaN, with a cache that is empty, one part full and one full."""
    rng = np.random.default_rng(2)
    qkv, cache, q_pos, q_seg = inputs(rng, (0, 12, 16), ((1, 2),), 4, 4, 2, 16, 16, jnp.float32)
    weights = jnp.asarray(rng.standard_normal(qkv[0].shape), jnp.float32)
    out, visited = ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, window=0)
    assert visited is not None and not np.asarray(visited.flags).any()
    for attend in (lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, window=0)[0], lambda qkv: whole(qkv, cache, q_pos, q_seg, 0)):
        for x in jax.tree.leaves(out_and_grads(attend, qkv, weights)):
            assert not np.asarray(x).any()  # NaN is not zero either


@pytest.mark.parametrize("T,Hq,firsts", [(8, 4, ((2, 4),)), (1, 16, ())], ids=["chunk", "one_query_a_row"])
@pytest.mark.parametrize("window,ring", [(None, False), (16, True)], ids=["full", "ring"])
def test_a_block_that_is_not_flagged_is_not_read(window, ring, T, Hq, firsts):
    """Every unflagged block of the cache's keys and values filled with NaN: outputs and
    gradients are what they were, and the share is the one counted by hand; for a chunk,
    and for one query a row whose sixteen query rows on the key head take the kernel too."""
    rng = np.random.default_rng(3)
    fills = (0, 5, 20, 100) if ring else (0, 5, 20, 32)
    slots = 32
    qkv, cache, q_pos, q_seg = inputs(rng, fills, firsts, T, Hq, 1, 16, slots, jnp.float32, ring)
    weights = jnp.asarray(rng.standard_normal(qkv[0].shape), jnp.float32)
    attend = lambda cache: lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, window)  # noqa: E731
    flags = np.asarray(attend(cache)(qkv)[1].flags)
    by_hand = np.zeros((len(fills), slots // KEY_BLOCK), np.int32)
    kv_pos, first_q = np.asarray(cache[2]), np.asarray(q_pos)[:, 0]
    for b in range(len(fills)):
        for j in range(slots // KEY_BLOCK):
            held = kv_pos[b, j * KEY_BLOCK : (j + 1) * KEY_BLOCK]
            in_reach = held >= 0 if window is None else (held >= 0) & (held > first_q[b] - window)
            by_hand[b, j] = in_reach.any()
    np.testing.assert_array_equal(flags, by_hand)
    assert 0 < flags.sum() < flags.size
    poison = jnp.asarray(np.repeat(flags == 0, KEY_BLOCK, axis=1))[:, :, None, None]
    poisoned = (jnp.where(poison, jnp.nan, cache[0]), jnp.where(poison, jnp.nan, cache[1]), *cache[2:])
    clean = out_and_grads(lambda qkv: attend(cache)(qkv)[0], qkv, weights)
    dirty = out_and_grads(lambda qkv: attend(poisoned)(qkv)[0], qkv, weights)
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(dirty)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rows", [8, 3], ids=["rows_divide_over_the_devices", "rows_do_not"])
def test_on_a_mesh_the_kernels_run_under_shard_map_over_the_data_axis(rows):
    """A Mosaic call is not partitioned by GSPMD: given a mesh of several devices each
    takes its rows (or, where they do not divide, all of them); same numbers either way."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
    rng = np.random.default_rng(6)
    qkv, cache, q_pos, q_seg = inputs(rng, tuple(range(0, 4 * rows, 4)), ((1, 2),), 4, 4, 2, 16, 32, jnp.float32)
    weights = jnp.asarray(rng.standard_normal(qkv[0].shape), jnp.float32)
    spread = lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, 12, mesh=mesh)[0]  # noqa: E731
    assert "shard_map" in str(jax.make_jaxpr(spread)(qkv))
    want = out_and_grads(lambda qkv: whole(qkv, cache, q_pos, q_seg, 12), qkv, weights)
    for a, b in zip(jax.tree.leaves(out_and_grads(spread, qkv, weights)), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_the_policy_reports_the_share_of_key_blocks_visited():
    """``Attn/key_blocks_visited_share`` of a chunk through the policy: flags set over flags,
    over its attention layers; an acting step reports none."""
    cfg = decoder.DecoderConfig(
        hidden_size=16, head_dim=8, heads_held=2, kv_heads_held=1, num_experts=4, experts_held=4, experts_per_token=2, expert_width=8,
        vocab_held=16, layers=2, window=KEY_BLOCK, mixers=("full", "window"), rope_layout=(0, 1), capacity=4 * KEY_BLOCK,
    )  # fmt: skip
    policy = decoder.DecoderPolicy(cfg)
    B, T = 3, 4
    state = decoder.zero_state(cfg, B, jnp.float32)
    ids = jnp.zeros((B, 1), jnp.int32)
    params = policy.init(jax.random.PRNGKey(0), ids[:, 0], ids[:, 0], jnp.ones((B, 1)), state, method=decoder.DecoderPolicy.step)
    assert "Attn/key_blocks_visited_share" not in policy.apply(params, ids, ids, jnp.ones((B, 1)), state)[-1]
    fills = (0, 3, 20)  # rows' carried tokens: of the full layer's 4 blocks 0, 1 and 3 hold some; the ring's one block 0, 1, 1
    pos = {8 * 4: filled(fills, 4 * KEY_BLOCK), 8: filled(fills, KEY_BLOCK, ring=True)}
    layers = tuple({**s, "pos": pos[s["pos"].shape[1]]} for s in state["layers"])
    chunk = jnp.zeros((B, T), jnp.int32)
    aux = policy.apply(params, chunk, chunk, jnp.zeros((B, T)), {"pos": jnp.asarray(fills, jnp.int32), "layers": layers})[-1]
    assert float(aux["Attn/key_blocks_visited_share"]) == pytest.approx((0 + 1 + 3 + 0 + 1 + 1) / (3 * 4 + 3 * 1))


def test_the_kernels_mask_is_the_whole_scores_mask():
    rng = np.random.default_rng(4)
    q_pos, kv_pos = jnp.asarray(rng.integers(0, 12, (3, 5))), jnp.asarray(rng.integers(-1, 12, (3, 9)))
    q_seg, kv_seg = jnp.asarray(rng.integers(0, 2, (3, 5))), jnp.asarray(rng.integers(-1, 2, (3, 9)))
    for window in (None, 4):
        want = ring_attention._block_mask(q_pos, kv_pos, True, q_seg, kv_seg, window)
        got = blockwise_attention.visible(q_pos[:, :, None], q_seg[:, :, None], kv_pos[:, None, :], kv_seg[:, None, :], window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# one query a row: (query heads, key heads, head width, lanes of a lane-grouped cache or None) -> takes the kernel
ONE_QUERY = {
    "lfm2_lane_grouped_eight_rows": ((16, 4, 8, 16), False),
    "smallthinker_seven_rows": ((7, 1, 16, None), False),
    "two_rows": ((4, 2, 16, None), False),
    "moonlight_sixteen_rows": ((16, 1, 16, None), True),
    "thirty_two_rows": ((32, 1, 16, None), True),
}


@pytest.mark.parametrize("case", list(ONE_QUERY))
def test_one_query_a_row_takes_the_kernel_only_where_its_rows_a_key_head_fill_a_bf16_tile(case, monkeypatch):
    """The acting step's call by its shape: query rows a key head a multiple of 16 go blockwise;
    LFM2's lane-grouped calls (two key heads of a lane-full, four query heads each: 8 rows)
    and SmallThinker's 7 rows form the scores whole, and their output is the oracle's own."""
    (Hq, Hkv, hd, lanes), blockwise = ONE_QUERY[case]
    rng = np.random.default_rng(10)
    fills = (0, 5, 20, 32)
    qkv, cache, q_pos, q_seg = inputs(rng, fills, (), 1, Hq, Hkv, hd, 32, jnp.float32)
    if lanes:
        monkeypatch.setattr(decoder, "LANES", lanes)
        arrays = lambda x: tuple(x.reshape(len(fills), 32, -1, lanes).transpose(2, 0, 1, 3)[:, :, :, None])  # noqa: E731
        state = {"k": arrays(cache[0]), "v": arrays(cache[1]), "pos": cache[2]}
        out, visited = decoder.lane_grouped_attention(*qkv, state, cache[3], q_pos, q_seg, None)
    else:
        out, visited = ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg)
    assert (visited is not None) == blockwise
    want = whole(qkv, cache, q_pos, q_seg, None)
    if blockwise:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)
    elif lanes is None:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_shapes_the_kernel_does_not_take_go_the_whole_scores_way(monkeypatch):
    """One query a row of fewer rows a key head than a bf16 tile never takes it; nor, on the
    chip, a head narrower than its lanes."""
    rng = np.random.default_rng(5)
    qkv, cache, q_pos, q_seg = inputs(rng, (3, 9), (), 1, 4, 2, 16, 16, jnp.float32)
    out, visited = ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg)
    assert visited is None
    np.testing.assert_array_equal(np.asarray(out), np.asarray(whole(qkv, cache, q_pos, q_seg, None)))
    assert blockwise_attention.tiles(64 * 7, 8192, 128) == (448, 8) and blockwise_attention.tiles(4096, 8192, 128) == (1024, 8)
    monkeypatch.setattr(blockwise_attention, "KEY_BLOCK", 512)
    monkeypatch.setattr(blockwise_attention, "_interpret", lambda: False)
    assert blockwise_attention.tiles(64 * 7, 8192, 128) == (448, 512) and blockwise_attention.tiles(64 * 8, 4096, 128) == (512, 512)
    assert blockwise_attention.tiles(64 * 4, 8192, 64) is None and blockwise_attention.tiles(64, 200, 128) is None


def float32_of(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, tree)


def first_visited_block_unflagged(monkeypatch, cache):
    """A planted fault: the first block a row would visit is skipped."""
    flags_of = blockwise_attention.key_block_flags

    def faulty(*args):
        flags = flags_of(*args)
        return flags * (jnp.arange(flags.shape[1])[None] != flags.argmax(1)[:, None])

    monkeypatch.setattr(blockwise_attention, "key_block_flags", faulty)
    return cache


def cache_one_position_late(monkeypatch, cache):
    """A planted fault: the cache's keys are taken for one position later than they are, so a
    window's oldest key drops out (a layer without a window sees the same keys)."""
    return (*cache[:2], jnp.where(cache[2] >= 0, cache[2] + 1, cache[2]), cache[3])


#: how far the blockwise program in bfloat16 may lie from the float32 one, in units of how far the
#: whole-scores program in bfloat16 lies from it (mean absolute difference, each of out, dq, dk, dv).
#: Read over seeds 0-2 of every case: 0.73-1.07 as the program is, 5.5-380 with the first visited block
#: unflagged, 13-78 with the cache a position late under a window (PERF.md section 6, PR 33).
AS_NEAR = 1.25
FAULTS = [
    *[(case, first_visited_block_unflagged) for case in CASES],
    *[(case, cache_one_position_late) for case in CASES if CASES[case][7] is not None],
]


@pytest.mark.parametrize("case,fault", [*[(case, None) for case in CASES], *FAULTS], ids=lambda x: x if isinstance(x, str) else getattr(x, "__name__", "as_it_is"))
def test_in_bfloat16_the_blockwise_program_is_as_near_the_float32_one_as_the_whole_scores_program(case, fault, monkeypatch):
    """The acting step forms the scores whole and rounds normalised probabilities to bfloat16;
    the update's kernel rounds them before the division by the denominator.  Neither is the
    other to better than that dtype's rounding (3e-2 above), so what holds the blockwise
    program in bfloat16 is its distance from the float32 program on the same numbers: no
    more than ``AS_NEAR`` times the whole-scores program's, which a planted fault passes by
    at least 3 times over."""
    fills, firsts, T, Hq, Hkv, D, slots, window, ring = CASES[case]
    rng = np.random.default_rng(0)
    qkv, cache, q_pos, q_seg = inputs(rng, fills, firsts, T, Hq, Hkv, D, slots, jnp.bfloat16, ring)
    weights = jnp.asarray(rng.standard_normal(qkv[0].shape), jnp.float32)
    exact = out_and_grads(lambda qkv: whole(qkv, float32_of(cache), q_pos, q_seg, window), float32_of(qkv), weights)
    rounded = out_and_grads(lambda qkv: whole(qkv, cache, q_pos, q_seg, window), qkv, weights)
    seen = fault(monkeypatch, cache) if fault else cache
    blockwise = out_and_grads(lambda qkv: ring_attention.grouped_attention(*qkv, seen, q_pos, q_seg, window)[0], qkv, weights)
    far = lambda got: np.array([np.abs(np.asarray(a, np.float32) - np.asarray(b)).mean() for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(exact))])  # noqa: E731
    ratio = far(blockwise) / far(rounded)
    if fault is None:
        assert (ratio <= AS_NEAR).all(), ratio
    else:
        assert ratio.max() >= 3 * AS_NEAR, ratio


# ---- a key wider than its value: a latent cache that is key and value at once, stored once ----


def latent_inputs(rng, fills, firsts, T, Hq, Hkv, D, Dv, slots, dtype, shared):
    """As ``inputs``, with values ``Dv`` wide under keys ``D`` wide.  ``shared``: the cache is
    one array (one key head) whose first ``Dv`` columns are the values, which are ``None``."""
    (q, k, _), (ck, _, kv_pos, kv_seg), q_pos, q_seg = inputs(rng, fills, firsts, T, Hq, Hkv, D, slots, dtype)
    v = jnp.asarray(rng.standard_normal((len(fills), T, Hkv, Dv)), dtype)
    cv = None if shared else jnp.asarray(rng.standard_normal((len(fills), slots, Hkv, Dv)), dtype)
    return (q, k, v), (ck, cv, kv_pos, kv_seg), q_pos, q_seg


def sliced(cache, Dv):
    """The cache with its values cut out as an array of their own: what the whole-scores oracle is given."""
    return (cache[0], (cache[0] if cache[1] is None else cache[1])[..., :Dv], *cache[2:])


# (fills, episode starts, T, Hq, Hkv, D, Dv, slots, the values are the keys' first columns, the head's width that the scores are over the root of)
LATENT_CASES = {
    "sixteen_heads_on_a_latent_five_lanes_wide": ((0, 5, 20, 32), (), 4, 16, 1, 640, 512, 32, True, 576),
    "an_episode_that_starts_inside_the_chunk": ((9, 9, 20), ((0, 3), (1, 0), (2, 7)), 8, 4, 1, 24, 16, 32, True, 12),
    "values_that_do_not_divide_the_keys_width": ((3, 17, 32), ((1, 2),), 6, 4, 1, 128, 96, 32, True, 100),
    "narrower_values_in_an_array_of_their_own": ((0, 12, 30), ((2, 5),), 8, 4, 2, 16, 8, 32, False, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(LATENT_CASES))
def test_a_key_wider_than_its_value_gives_the_whole_scores_outputs_and_gradients(case, dtype):
    """The kernel pair with ``D != Dv``, the cache's values a column block of its keys: outputs
    ``[.., Dv]`` and the gradients of the queries and of the chunk's own keys and values
    against the whole-scores program over the values cut out as an array of their own."""
    fills, firsts, T, Hq, Hkv, D, Dv, slots, shared, hd = LATENT_CASES[case]
    rng = np.random.default_rng(0)
    qkv, cache, q_pos, q_seg = latent_inputs(rng, fills, firsts, T, Hq, Hkv, D, Dv, slots, dtype, shared)
    weights = jnp.asarray(rng.standard_normal((*qkv[0].shape[:3], Dv)), jnp.float32)
    blockwise = lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, None, hd)  # noqa: E731
    out, visited = blockwise(qkv)
    assert visited is not None and out.shape == (len(fills), T, Hq, Dv) and (cache[1] is None) == shared
    got = out_and_grads(lambda qkv: blockwise(qkv)[0], qkv, weights)
    want = out_and_grads(lambda qkv: whole(qkv, sliced(cache, Dv), q_pos, q_seg, None, hd), qkv, weights)
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(rtol=3e-2, atol=3e-2)
    for name, a, b in zip(("out", "dq", "dk", "dv"), jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape and np.isfinite(np.asarray(a, np.float32)).all(), name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name, **tol)


@pytest.mark.parametrize("fault", [None, first_visited_block_unflagged], ids=["as_it_is", "first_visited_block_unflagged"])
@pytest.mark.parametrize("case", [c for c in LATENT_CASES if LATENT_CASES[c][8]])
def test_in_bfloat16_the_latent_call_is_as_near_the_float32_program_as_the_whole_scores_one(case, fault, monkeypatch):
    """The file's own measure (``AS_NEAR``) for the call with shared storage: as near as the
    whole-scores program as it is, three times past it with a visited block unflagged."""
    fills, firsts, T, Hq, Hkv, D, Dv, slots, _, hd = LATENT_CASES[case]
    rng = np.random.default_rng(0)
    qkv, cache, q_pos, q_seg = latent_inputs(rng, fills, firsts, T, Hq, Hkv, D, Dv, slots, jnp.bfloat16, True)
    weights = jnp.asarray(rng.standard_normal((*qkv[0].shape[:3], Dv)), jnp.float32)
    exact = out_and_grads(lambda qkv: whole(qkv, float32_of(sliced(cache, Dv)), q_pos, q_seg, None, hd), float32_of(qkv), weights)
    rounded = out_and_grads(lambda qkv: whole(qkv, sliced(cache, Dv), q_pos, q_seg, None, hd), qkv, weights)
    if fault:
        fault(monkeypatch, cache)
    blockwise = out_and_grads(lambda qkv: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, None, hd)[0], qkv, weights)
    far = lambda got: np.array([np.abs(np.asarray(a, np.float32) - np.asarray(b)).mean() for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(exact))])  # noqa: E731
    ratio = far(blockwise) / far(rounded)
    assert (ratio <= AS_NEAR).all() if fault is None else ratio.max() >= 3 * AS_NEAR, ratio


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_one_query_a_row_reads_a_shared_latent_as_it_lies_and_keeps_its_first_columns(dtype):
    """The acting step's call of a latent layer: sixteen query heads on the one key head fill a
    bf16 tile, so it goes blockwise, the cache's array read once as keys and as values, whose
    first ``Dv`` columns it keeps.  Rows whose caches hold nothing (an episode that starts),
    a part of one block, three blocks and every block; under a window of no position no row
    sees a key, its own neither, and gets zeros.  In float32 the output is the whole-scores
    program's; in bfloat16 it lies no farther from the float32 program than that program in
    bfloat16 does (``AS_NEAR``).  The cache takes no gradient; several key heads cannot share."""
    rng = np.random.default_rng(9)
    fills = (0, 5, 3 * KEY_BLOCK - 2, 4 * KEY_BLOCK)
    qkv, cache, q_pos, q_seg = latent_inputs(rng, fills, ((0, 0),), 1, 16, 1, 24, 16, 4 * KEY_BLOCK, dtype, True)
    attend = lambda qkv, cache=cache, window=None: ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg, window, 12)  # noqa: E731
    out, visited = attend(qkv)
    assert out.shape == (4, 1, 16, 16) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(visited.flags), [[0, 0, 0, 0], [1, 0, 0, 0], [1, 1, 1, 0], [1, 1, 1, 1]])
    weights = jnp.asarray(rng.standard_normal((*qkv[0].shape[:3], 16)), jnp.float32)
    exact = out_and_grads(lambda qkv: whole(qkv, float32_of(sliced(cache, 16)), q_pos, q_seg, None, 12), float32_of(qkv), weights)
    got = out_and_grads(lambda qkv: attend(qkv)[0], qkv, weights)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(exact[0]), rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(exact[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    else:
        rounded = out_and_grads(lambda qkv: whole(qkv, sliced(cache, 16), q_pos, q_seg, None, 12), qkv, weights)
        far = lambda got: np.array([np.abs(np.asarray(a, np.float32) - np.asarray(b)).mean() for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(exact))])  # noqa: E731
        assert (far(got) / far(rounded) <= AS_NEAR).all(), far(got) / far(rounded)
    none_seen, visited = attend(qkv, window=0)
    assert not np.asarray(visited.flags).any() and not np.asarray(none_seen, np.float32).any()
    d_cache = jax.grad(lambda c: attend(qkv, (c, None, *cache[2:]))[0].astype(jnp.float32).sum())(cache[0])
    assert not np.asarray(d_cache, np.float32).any()
    two, held, q_pos2, q_seg2 = latent_inputs(rng, (4, 9), (), 4, 4, 2, 16, 8, 32, jnp.float32, True)
    with pytest.raises(ValueError, match="one key head"):
        ring_attention.grouped_attention(*two, held, q_pos2, q_seg2)


@pytest.mark.parametrize("slots", [32, 20], ids=["blockwise", "scores_whole"])
def test_the_cache_takes_no_gradient_whichever_way_a_chunk_goes(slots):
    """The cache is the carry as it stood, an input of the update: the kernel's backward pass
    hands it nothing, and a chunk whose shapes the kernel does not take (here: slots that
    are neither a block's multiple nor few enough for one) stops the gradient too."""
    rng = np.random.default_rng(8)
    qkv, cache, q_pos, q_seg = inputs(rng, (5, 20), (), 4, 4, 2, 16, slots, jnp.float32)
    assert (ring_attention.grouped_attention(*qkv, cache, q_pos, q_seg)[1] is None) == (slots == 20)
    loss = lambda ck, cv, qkv: ring_attention.grouped_attention(*qkv, (ck, cv, *cache[2:]), q_pos, q_seg)[0].sum()  # noqa: E731
    d_ck, d_cv, d_qkv = jax.grad(loss, argnums=(0, 1, 2))(cache[0], cache[1], qkv)
    assert not np.asarray(d_ck).any() and not np.asarray(d_cv).any()
    assert all(np.asarray(x).any() for x in d_qkv)


# ---- compiled for a described chip: what interpret mode cannot refuse (tile alignment, VMEM) ----


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


# the benchmark's two decoders: (query heads a key head, slots, window, head_dim of the scale, chips)
@pytest.mark.parametrize(
    "G,slots,window,head_dim,chips",
    [(8, 8192, None, 64, 1), (7, 8192, None, None, 1), (7, 4096, 4096, None, 1), (7, 4096, 4096, None, 4)],
    ids=["lfm2", "smallthinker_full", "smallthinker_window", "smallthinker_window_on_four_chips"],
)
def test_the_kernels_compile_for_the_chip_at_the_benchmarks_widths(topology, monkeypatch, G, slots, window, head_dim, chips):
    """Forward and backward for a TPU v5e, 64 rows x 64 tokens in bfloat16; no copy of the
    cache is made on the way in (its view is the bytes as they lie).  On four chips the
    rows are spread over the ``data`` axis and each chip's kernels take its own."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    monkeypatch.setattr(blockwise_attention, "KEY_BLOCK", 512)
    monkeypatch.setattr(blockwise_attention, "_interpret", lambda: False)
    B, T, D = 64, 64, 128
    mesh = Mesh(np.array(topology.devices).reshape(4, 1), ("data", "model")) if chips > 1 else None
    placed = NamedSharding(mesh, PartitionSpec("data")) if mesh else SingleDeviceSharding(topology.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=placed)  # noqa: E731

    def step(q, k, v, ck, cv, kv_pos, q_pos, q_seg):
        cache = (ck, cv, kv_pos, jnp.where(kv_pos >= 0, 0, -1))
        loss = lambda q, k, v: ring_attention.grouped_attention(q, k, v, cache, q_pos, q_seg, window, head_dim, mesh)[0].astype(jnp.float32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    own, held, ids = shaped((B, T, 1, D)), shaped((B, slots, 1, D)), shaped((B, T), jnp.int32)
    text = jax.jit(step).lower(shaped((B, T, G, D)), own, own, held, held, shaped((B, slots), jnp.int32), ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    cache_sized = [line for line in text.splitlines() if " copy(" in line and f"bf16[{B // chips},{slots}," in line.split(" copy(")[0]]
    assert not cache_sized, cache_sized


def test_the_kernels_compile_for_the_chip_over_a_latent_cache_at_moonlights_widths(topology, monkeypatch):
    """Forward and backward for a TPU v5e at ``moonlight16b_1of8.rl_gen32``'s shapes: 32 rows x
    64 tokens x 16 heads = 1,024 query rows on one key head 640 wide (the 512-wide latent,
    the 64-wide rotated key, zeros to five lane-fulls) whose values are its first 512
    columns, 8,192 slots in blocks of 512, scores over ``sqrt(192)``, bfloat16.  The
    1,024-row tile fits once the kernels ask for their VMEM; the narrower shapes of the
    other two decoders ask for none, as before; no copy of the cache is made on the way in."""
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(blockwise_attention, "KEY_BLOCK", 512)
    monkeypatch.setattr(blockwise_attention, "_interpret", lambda: False)
    B, T, H, D, Dv, slots = 32, 64, 16, 640, 512, 8192
    assert blockwise_attention.tiles(T * H, slots, D, Dv) == (1024, 512) and blockwise_attention.tiles(T * H, slots, D, 576) is None
    shared = blockwise_attention._vmem_limit(1024, 512, D, Dv, 2, values_in_keys=True)  # one cache block where the values are in the keys'
    assert blockwise_attention._vmem_limit(1024, 512, D, Dv, 2) > shared > blockwise_attention.VMEM_GRANTED
    assert blockwise_attention._vmem_limit(512, 512, 128, 128, 2) is None and blockwise_attention._vmem_limit(1024, 512, 128, 128, 2) is None
    placed = SingleDeviceSharding(topology.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=placed)  # noqa: E731

    def step(q, k, v, latent, kv_pos, q_pos, q_seg):
        kept = latent[:, :, None]  # the carry keeps [B, slots, 640]
        cache = (kept, None, kv_pos, jnp.where(kv_pos >= 0, 0, -1))
        loss = lambda q, k, v: ring_attention.grouped_attention(q, k, v, cache, q_pos, q_seg, None, 192)[0].astype(jnp.float32).sum()  # noqa: E731
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    ids = shaped((B, T), jnp.int32)
    text = jax.jit(step).lower(shaped((B, T, H, D)), shaped((B, T, 1, D)), shaped((B, T, 1, Dv)), shaped((B, slots, D)), shaped((B, slots), jnp.int32), ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    cache_sized = [line for line in text.splitlines() if " copy(" in line and f"bf16[{B},{slots}," in line.split(" copy(")[0]]
    assert not cache_sized, cache_sized


# one acting step's attention for a described v5e: (query heads, key heads, head width, slots, window, lane-grouped, kernels)
ACTING = {
    "moonlight": ((16, 1, 640, 8192, None, False), 1),
    "lfm2": ((32, 8, 64, 8192, None, True), 0),
    "smallthinker_full": ((7, 1, 128, 8192, None, False), 0),
    "smallthinker_window": ((7, 1, 128, 4096, 4096, False), 0),
}


@pytest.mark.parametrize("case", list(ACTING))
def test_an_acting_step_compiles_for_the_chip_with_the_kernel_where_its_rows_fill_a_tile(case, topology, monkeypatch):
    """One token a row, as ``jit_act`` calls it at the benchmark's widths, then the row's slot
    written into the donated cache.  Moonlight's 32 rows x 16 heads on one latent key head 640
    wide (values its first 512 columns) over ``[32, 8192, 1, 640]`` take the kernel, once a
    layer, and neither the call nor the write copies the cache.  LFM2's four lane-fulls of
    8 query rows over ``[64, 8192, 1, 128]`` and SmallThinker's 7 rows take none: their
    scores are formed whole as before."""
    from jax.sharding import SingleDeviceSharding

    (Hq, Hkv, hd, slots, window, laned), kernels = ACTING[case]
    monkeypatch.setattr(blockwise_attention, "KEY_BLOCK", 512)
    monkeypatch.setattr(blockwise_attention, "_interpret", lambda: False)
    placed = SingleDeviceSharding(topology.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=placed)  # noqa: E731
    latent = hd > decoder.LANES
    B, Dv, lanes = (32, 512, hd) if latent else (64, hd, decoder.LANES)
    rows = jnp.arange(B)

    def step(q, k, v, cache, kv_pos, q_pos):
        q_seg, kv_seg = jnp.zeros_like(q_pos), jnp.where(kv_pos >= 0, 0, -1)
        slot = q_pos[:, 0] % slots
        if latent:
            kept = cache[:, :, None]
            out, _ = ring_attention.grouped_attention(q, k, v, (kept, None, kv_pos, kv_seg), q_pos, q_seg, None, 192)
            return out, cache.at[rows, slot].set(k[:, 0, 0])
        if laned:
            out, _ = decoder.lane_grouped_attention(q, k, v, {"k": cache, "v": cache, "pos": kv_pos}, kv_seg, q_pos, q_seg, window)
            return out, tuple(c.at[rows, slot].set(k[:, 0].reshape(B, len(cache), 1, -1)[:, j]) for j, c in enumerate(cache))
        out, _ = ring_attention.grouped_attention(q, k, v, (cache, cache, kv_pos, kv_seg), q_pos, q_seg, window)
        return out, cache.at[rows, slot].set(k[:, 0])

    if latent:
        cache = shaped((B, slots, hd))
    elif laned:
        cache = tuple(shaped((B, slots, 1, lanes)) for _ in range(Hkv * hd // lanes))
    else:
        cache = shaped((B, slots, Hkv, hd))
    args = (shaped((B, 1, Hq, hd)), shaped((B, 1, Hkv, hd)), shaped((B, 1, Hkv, Dv)), cache, shaped((B, slots), jnp.int32), shaped((B, 1), jnp.int32))
    text = jax.jit(step, donate_argnums=3).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == kernels
    cache_sized = [line for line in text.splitlines() if " copy(" in line and f"bf16[{B},{slots}," in line.split(" copy(")[0]]
    assert not cache_sized, cache_sized


# one acting call's expert layer for a described v5e: (tokens, experts held, experts a token, width, expert width)
ACTING_EXPERTS = {"moonlight": (32, 8, 6, 2048, 1408), "lfm2": (64, 8, 4, 2048, 1792), "smallthinker": (64, 16, 6, 2560, 768)}


@pytest.mark.parametrize("cell", list(ACTING_EXPERTS))
def test_an_acting_calls_expert_layer_compiles_for_the_chip_with_no_grouped_product_and_no_copy_of_a_weight(cell, topology):
    """At the cell's widths with bfloat16 weights: every token goes through every held expert
    (``decoder.expert_layer`` at an acting call's token count), no grouped product is left, and
    no expert weight is copied or transposed on its way into the batched products."""
    from jax.sharding import SingleDeviceSharding

    n, held, k, width, expert_width = ACTING_EXPERTS[cell]
    assert decoder.expert_path(n) == "every_held"
    placed = SingleDeviceSharding(topology.devices[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=placed)  # noqa: E731
    weights = [(held, width, expert_width), (held, width, expert_width), (held, expert_width, width)]
    layer = jax.jit(lambda m, top_w, top_i, *w: decoder.expert_layer(m, top_w, top_i, *w, 0, jnp.bfloat16, jax.nn.silu))
    text = layer.lower(shaped((n, width), jnp.float32), shaped((n, k), jnp.float32), shaped((n, k), jnp.int32), *map(shaped, weights)).compile().as_text()
    assert "ragged" not in text
    results = [line.split(" = ", 1)[1] for line in text.splitlines() if " copy(" in line or " transpose(" in line]
    assert not [r for r in results if any(r.startswith("bf16[%s]" % ",".join(map(str, s))) for s in weights)]
