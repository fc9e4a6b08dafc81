"""The sparse-expert decoder policy (``sheeprl_tpu/models/decoder.py``) against the
benchmark's plain reference (``perfbench/configs/smallthinker21b_1of4_reference.py``,
which shares no code with it) on seeded weights, at a small size on the CPU: window 8,
8 experts, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.configs import smallthinker21b_1of4_reference as ref
from sheeprl_tpu.algos.ppo.utils import chunked_log_prob_and_entropy, log_prob_and_entropy
from sheeprl_tpu.models import decoder
from sheeprl_tpu.ops.ring_attention import _block_mask

SIZES = {
    "hidden_size": 32, "head_dim": 8, "heads_held": 4, "kv_heads_held": 2, "num_experts": 8, "experts_held": 8, "expert_offset": 0,
    "experts_per_token": 3, "expert_width": 16, "vocab_held": 48, "layers": 4, "window": 8, "window_layout": [0, 1, 0, 1],
    "rope_layout": [0, 1, 0, 1], "rope_theta": 1500000.0, "rms_norm_eps": 1e-6, "cache_capacity": 32, "norm_topk_prob": True,
    "router_scale": 4.0, "branch_scale": 0.25,
}  # fmt: skip


def config_of(S) -> decoder.DecoderConfig:
    return decoder.DecoderConfig(
        hidden_size=S["hidden_size"], head_dim=S["head_dim"], heads_held=S["heads_held"], kv_heads_held=S["kv_heads_held"],
        num_experts=S["num_experts"], experts_held=S["experts_held"], experts_per_token=S["experts_per_token"],
        expert_width=S["expert_width"], vocab_held=S["vocab_held"], layers=S["layers"], window=S["window"],
        window_layout=tuple(S["window_layout"]), rope_layout=tuple(S["rope_layout"]), rope_theta=S["rope_theta"],
        expert_offset=S["expert_offset"], capacity=S["cache_capacity"],
    )  # fmt: skip


def sequences(rng, n, t, vocab, firsts):
    tokens = rng.integers(0, vocab, (n, t)).astype(np.int32)
    actions = rng.integers(0, vocab, (n, t)).astype(np.int32)
    is_first = np.zeros((n, t), np.float32)
    for row, steps in enumerate(firsts):
        is_first[row, list(steps)] = 1.0
    prev = np.concatenate([np.zeros((n, 1), np.int32), actions[:, :-1]], 1)
    return tokens, prev, is_first


def reference_forward(S, weights, n, tokens, prev, is_first, pos, ep):
    """One pass of the plain reference over whole sequences, nothing carried."""
    run = jax.jit(lambda w, *arrays: ref.forward(S, w, ref.empty_context(S, n, 0), *arrays))
    return run(weights, tokens, prev, jnp.asarray(is_first), jnp.asarray(pos), jnp.asarray(ep))


@pytest.mark.parametrize("kind", ["full", "window"])
def test_one_layer_of_each_kind_matches_the_reference(kind):
    S = {**SIZES, "layers": 1, "window_layout": [int(kind == "window")], "rope_layout": [int(kind == "window")]}
    weights = ref.make_weights(S, 7)
    tokens, prev, is_first = sequences(np.random.default_rng(0), 3, 20, S["vocab_held"], [(0,), (0, 11), (0, 5, 6)])
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(3, np.int32), np.zeros(3, np.int32))
    want, want_v, _, _ = reference_forward(S, weights, 3, tokens, prev, is_first, pos, ep)
    cfg = config_of(S)
    got, got_v, _, q_pos, aux = jax.jit(decoder.DecoderPolicy(cfg).apply)(weights, tokens, prev, is_first, decoder.zero_state(cfg, 3, jnp.float32))
    np.testing.assert_array_equal(np.asarray(q_pos), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), atol=2e-5)
    assert float(aux["MoE/dropped"]) == 0.0 and float(aux["MoE/held_share"]) == 1.0


def test_acting_through_the_caches_matches_the_full_forward_pass():
    """40 steps a row, one token at a time through the caches (the 8-slot rings wrap four
    times over; episodes end inside), against one pass over each whole sequence."""
    S = SIZES
    weights = ref.make_weights(S, 11)
    n, t = 3, 40
    tokens, prev, is_first = sequences(np.random.default_rng(1), n, t, S["vocab_held"], [(0, 25), (0, 13, 30), (0, 9, 10, 31)])
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    hidden, want_v, _, _ = reference_forward(S, weights, n, tokens, prev, is_first, pos, ep)
    want = np.asarray(hidden @ weights["params"]["head"])
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg)
    step = jax.jit(lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step))
    state = decoder.zero_state(cfg, n, jnp.float32)
    for i in range(t):
        (logits,), value, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
        np.testing.assert_allclose(np.asarray(logits), want[:, i], atol=5e-5, err_msg=f"step {i}")
        np.testing.assert_allclose(np.asarray(value)[:, 0], np.asarray(want_v[:, i]), atol=5e-5)
    assert np.asarray(state["pos"]).tolist() == (pos[:, -1] + 1).tolist()


def test_a_chunk_after_carried_steps_matches_the_full_forward_pass():
    """The update's view: 12 steps through the caches, then a chunk of 10 tokens in one piece
    that reads the cache as a constant, with an episode start inside the chunk."""
    S = SIZES
    weights = ref.make_weights(S, 13)
    n, carried, t = 2, 12, 22
    tokens, prev, is_first = sequences(np.random.default_rng(2), n, t, S["vocab_held"], [(0, 17), (0, 4)])
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    want, _, _, _ = reference_forward(S, weights, n, tokens, prev, is_first, pos, ep)
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg)
    step = jax.jit(lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step))
    state = decoder.zero_state(cfg, n, jnp.float32)
    for i in range(carried):
        _, _, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
    got, _, _, _, _ = jax.jit(policy.apply)(weights, tokens[:, carried:], prev[:, carried:], is_first[:, carried:], state)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, carried:]), atol=5e-5)


@pytest.mark.parametrize("what", ["heads", "experts"])
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference(what):
    """Four chips share a layer: each holds a quarter of the query heads with their key
    head, or a quarter of the experts, and computes its part; the parts add up to what
    the plain reference gives for the whole layer (the other branch switched off, since
    a share's partial result feeds its own next sub-layer)."""
    S = {**SIZES, "layers": 1, "window_layout": [1], "rope_layout": [1], "heads_held": 8, "kv_heads_held": 4}
    L = dict(ref.make_weights(S, 3)["params"]["layers_0"])
    off = "w_down" if what == "heads" else "wo"
    L[off] = jnp.zeros_like(L[off])
    rng = np.random.default_rng(4)
    n, t = 2, 12
    x = jnp.asarray(rng.standard_normal((n, t, S["hidden_size"])), jnp.float32)
    is_first = np.zeros((n, t), np.float32)
    is_first[:, 0] = 1
    is_first[1, 7] = 1
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    whole, _, _ = jax.jit(lambda L, x: ref.layer(S, 0, L, x, ref.empty_context(S, n, 0), jnp.asarray(pos), jnp.asarray(ep)))(L, x)

    hd, D = S["head_dim"], S["hidden_size"]
    total = jnp.zeros_like(x)
    for share in range(4):
        if what == "heads":
            cut = {**S, "heads_held": 2, "kv_heads_held": 1}
            q, kv = slice(share * 2 * hd, (share + 1) * 2 * hd), slice(share * hd, (share + 1) * hd)
            part = {**L, "wq": L["wq"][:, q], "wk": L["wk"][:, kv], "wv": L["wv"][:, kv], "wo": L["wo"][q]}
        else:
            cut = {**S, "experts_held": 2, "expert_offset": 2 * share}
            e = slice(2 * share, 2 * share + 2)
            part = {**L, "w_gate": L["w_gate"][e], "w_up": L["w_up"][e], "w_down": L["w_down"][e]}
        cfg = config_of(cut)
        cache = decoder.zero_state(cfg, n, jnp.float32)["layers"][0]
        q_pos, q_seg = decoder.positions(jnp.asarray(is_first), jnp.zeros(n, jnp.int32))
        out, _, _, counters = jax.jit(decoder.DecoderLayer(cfg, 0).apply)({"params": part}, x, cache["k"], cache["v"], cache["pos"], q_pos, q_seg)
        total = total + (out - x)
        assert float(counters["dropped"]) == 0.0
    assert float(jnp.abs(whole - x).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole - x), atol=2e-5)
    assert x.shape[-1] == D


def test_no_assignment_is_dropped_when_every_token_picks_the_same_expert():
    """No capacity: the grouped products take groups as long as the routing makes them."""
    rng = np.random.default_rng(5)
    N, D, F, E = 24, 16, 8, 4
    m = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((E, F, D)), jnp.float32)
    top_i = jnp.tile(jnp.asarray([[1, 6]], jnp.int32), (N, 1))  # expert 1 is held (of 0..3), expert 6 is not
    top_w = jnp.tile(jnp.asarray([[0.7, 0.3]], jnp.float32), (N, 1))
    out, counters = decoder.expert_layer(m, top_w, top_i, w_gate, w_up, w_down, 0, jnp.float32)
    want = 0.7 * (jax.nn.relu(m @ w_gate[1]) * (m @ w_up[1])) @ w_down[1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert float(counters["held"]) == N and float(counters["load_max"]) == N and float(counters["dropped"]) == 0.0


def test_the_chunked_head_loss_is_the_whole_one():
    rng = np.random.default_rng(6)
    hidden = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((16, 40)), jnp.float32)
    actions = jnp.asarray(rng.integers(0, 40, (32,)), jnp.int32)

    def whole(h, w):
        lp, ent = log_prob_and_entropy([h @ w], actions[:, None], False)
        return (lp * 0.3 + ent).sum(), (lp, ent)

    def chunked(h, w):
        lp, ent = chunked_log_prob_and_entropy(h, w, actions, 8, jnp.float32)
        return (lp * 0.3 + ent).sum(), (lp, ent)

    (_, a), ga = jax.value_and_grad(whole, argnums=(0, 1), has_aux=True)(hidden, head)
    (_, b), gb = jax.value_and_grad(chunked, argnums=(0, 1), has_aux=True)(hidden, head)
    for x, y in zip(jax.tree.leaves((a, ga)), jax.tree.leaves((b, gb))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="do not divide"):
        chunked_log_prob_and_entropy(hidden, head, actions, 5, jnp.float32)


def test_block_mask_takes_a_position_range_of_its_own_for_every_row():
    q_pos = jnp.asarray([[4, 5, 6], [0, 1, 2]])
    kv_pos = jnp.asarray([[2, 3, 4, 5], [9, 0, 1, -1]])
    q_seg, kv_seg = jnp.zeros((2, 3), jnp.int32), jnp.asarray([[0, 0, 0, 0], [-1, 0, 0, -1]])
    got = np.asarray(_block_mask(q_pos, kv_pos, True, q_seg, kv_seg, window=3))
    for b in range(2):
        row = np.asarray(_block_mask(q_pos[b], kv_pos[b], True, q_seg[b : b + 1], kv_seg[b : b + 1], window=3))[0]
        np.testing.assert_array_equal(got[b], row)
    assert got[0].tolist() == [[True, True, True, False], [False, True, True, True], [False, False, True, True]]
    assert got[1].tolist() == [[False, True, False, False], [False, True, True, False], [False, True, True, False]]


def test_matmul_weights_are_cast_once_and_the_rest_stay():
    S = {**SIZES, "layers": 1}
    cast = decoder.cast_matmul_weights(ref.make_weights(S, 1), jnp.bfloat16)["params"]
    assert {k for k, v in cast["layers_0"].items() if v.dtype == jnp.bfloat16} == {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert cast["head"].dtype == jnp.bfloat16 and cast["embed"].dtype == jnp.float32 and cast["layers_0"]["router"].dtype == jnp.float32
