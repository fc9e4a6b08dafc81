"""The sparse-expert decoder policy (``sheeprl_tpu/models/decoder.py``) against the
benchmark's plain references (``perfbench/configs/smallthinker21b_1of4_reference.py``,
``perfbench/configs/lfm2_8b_a1b_1of4_reference.py`` and
``perfbench/configs/moonlight16b_1of8_reference.py``, which share no code with it or with
each other) on seeded weights, at a small size on the CPU: window 8, 8 experts, float32."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.configs import lfm2_8b_a1b_1of4_reference as lfm2
from perfbench.configs import moonlight16b_1of8_reference as moon
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


#: LFM2's five layers as the cell holds them (conv + dense, attention + experts, three conv + experts), small
LFM2 = {
    "hidden_size": 32, "head_dim": 8, "heads_held": 4, "kv_heads_held": 2, "num_experts": 8, "experts_held": 8, "expert_offset": 0,
    "experts_per_token": 2, "expert_width": 16, "dense_width": 48, "dense_layers": 1, "vocab_held": 48, "layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"], "conv_taps": 3, "rope_theta": 1000000.0, "norm_eps": 1e-5,
    "router_eps": 1e-6, "norm_topk_prob": True, "cache_capacity": 48, "router_scale": 2.0, "branch_scale": 0.25, "bias_scale": 0.05,
}  # fmt: skip


#: Moonlight's layers (latent attention + dense, latent attention + routed and shared experts), small
MOON = {
    "hidden_size": 32, "heads_held": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "num_experts": 8, "experts_held": 8, "expert_offset": 0, "experts_per_token": 2, "expert_width": 16, "shared_width": 32,
    "routed_scale": 2.446, "dense_width": 48, "dense_layers": 1, "vocab_held": 48, "layers": 3, "rope_theta": 50000.0, "norm_eps": 1e-5,
    "router_eps": 1e-20, "norm_topk_prob": True, "cache_capacity": 48, "router_scale": 2.0, "branch_scale": 0.25, "bias_scale": 0.05,
}  # fmt: skip


def carry_kinds(state):
    """How many layers of a carry hold a cache of keys and values, how many a convolution
    tail and how many a latent cache, with their bytes."""
    out = {"cache": {"layers": 0, "bytes": 0}, "conv": {"layers": 0, "bytes": 0}, "latent": {"layers": 0, "bytes": 0}}
    for layer_state in state["layers"]:
        kind = out[next((name for name in ("conv", "latent") if name in layer_state), "cache")]
        kind["layers"] += 1
        kind["bytes"] += sum(x.nbytes for x in jax.tree.leaves(layer_state))
    return out


def config_of(S) -> decoder.DecoderConfig:
    if "kv_lora_rank" in S:  # Moonlight's layer kinds
        return decoder.DecoderConfig(
            hidden_size=S["hidden_size"], head_dim=S["qk_nope_head_dim"] + S["qk_rope_head_dim"], heads_held=S["heads_held"], kv_heads_held=S["heads_held"],
            num_experts=S["num_experts"], experts_held=S["experts_held"], experts_per_token=S["experts_per_token"], expert_width=S["expert_width"],
            vocab_held=S["vocab_held"], layers=S["layers"], window=0, mixers=("latent",) * S["layers"], rope_layout=(1,) * S["layers"],
            rope_theta=S["rope_theta"], rms_norm_eps=S["norm_eps"], expert_offset=S["expert_offset"], capacity=S["cache_capacity"],
            dense_layers=S["dense_layers"], dense_width=S["dense_width"], router="sigmoid", router_reads="ffn_norm", activation="silu",
            kv_lora_rank=S["kv_lora_rank"], qk_nope_head_dim=S["qk_nope_head_dim"], qk_rope_head_dim=S["qk_rope_head_dim"], v_head_dim=S["v_head_dim"],
            shared_width=S["shared_width"], routed_scale=S["routed_scale"],
        )  # fmt: skip
    common = dict(
        hidden_size=S["hidden_size"], head_dim=S["head_dim"], heads_held=S["heads_held"], kv_heads_held=S["kv_heads_held"],
        num_experts=S["num_experts"], experts_held=S["experts_held"], experts_per_token=S["experts_per_token"],
        expert_width=S["expert_width"], vocab_held=S["vocab_held"], layers=S["layers"], rope_theta=S["rope_theta"],
        expert_offset=S["expert_offset"], capacity=S["cache_capacity"],
    )  # fmt: skip
    if "layer_types" in S:  # LFM2's layer kinds
        return decoder.DecoderConfig(
            **common, window=0, mixers=tuple(decoder.LAYER_TYPES[t] for t in S["layer_types"][: S["layers"]]), rope_layout=(1,) * S["layers"],
            rms_norm_eps=S["norm_eps"], conv_taps=S["conv_taps"], qk_norm=True, dense_layers=S["dense_layers"], dense_width=S["dense_width"],
            router="sigmoid", router_reads="ffn_norm", activation="silu", tie_embeddings=True,
        )  # fmt: skip
    mixers = tuple("window" if w else "full" for w in S["window_layout"])
    return decoder.DecoderConfig(**common, window=S["window"], mixers=mixers, rope_layout=tuple(S["rope_layout"]))


def model_of(name, monkeypatch=None):
    """``(reference module, sizes)`` of one of the three published models at the small size;
    ``lfm2_lanes``: with four key heads of 8 on a chip whose lanes are 16 wide, so that the
    cache is kept two heads an array (``decoder.lane_grouped_attention``)."""
    if name == "lfm2_lanes":
        monkeypatch.setattr(decoder, "LANES", 16)
        return lfm2, {**LFM2, "heads_held": 8, "kv_heads_held": 4}
    if name == "moonlight":
        return moon, MOON
    if name == "moonlight_sixteen_heads":  # as many query rows on the latent's key head as the cell's: the acting step goes blockwise
        return moon, {**MOON, "heads_held": 16}
    return (lfm2, LFM2) if name == "lfm2" else (ref, SIZES)


def sequences(rng, n, t, vocab, firsts):
    tokens = rng.integers(0, vocab, (n, t)).astype(np.int32)
    actions = rng.integers(0, vocab, (n, t)).astype(np.int32)
    is_first = np.zeros((n, t), np.float32)
    for row, steps in enumerate(firsts):
        is_first[row, list(steps)] = 1.0
    prev = np.concatenate([np.zeros((n, 1), np.int32), actions[:, :-1]], 1)
    return tokens, prev, is_first


def reference_forward(S, weights, n, tokens, prev, is_first, pos, ep, ref=ref):
    """One pass of the plain reference over whole sequences, nothing carried."""
    run = jax.jit(lambda w, *arrays: ref.forward(S, w, ref.empty_context(S, n, 0), *arrays))
    return run(weights, tokens, prev, jnp.asarray(is_first), jnp.asarray(pos), jnp.asarray(ep))


#: one layer of each kind: SmallThinker's two, LFM2's three and Moonlight's two (mixer + feed-forward)
ONE_LAYER = {
    "full": (ref, {**SIZES, "layers": 1, "window_layout": [0], "rope_layout": [0]}),
    "window": (ref, {**SIZES, "layers": 1, "window_layout": [1], "rope_layout": [1]}),
    "conv_dense": (lfm2, {**LFM2, "layers": 1, "layer_types": ["conv"], "dense_layers": 1}),
    "conv_experts": (lfm2, {**LFM2, "layers": 1, "layer_types": ["conv"], "dense_layers": 0}),
    "attention_qk_norm_experts": (lfm2, {**LFM2, "layers": 1, "layer_types": ["full_attention"], "dense_layers": 0}),
    "latent_dense": (moon, {**MOON, "layers": 1, "dense_layers": 1}),
    "latent_experts_shared": (moon, {**MOON, "layers": 1, "dense_layers": 0}),
}


@pytest.mark.parametrize("kind", list(ONE_LAYER))
def test_one_layer_of_each_kind_matches_the_reference(kind):
    ref, S = ONE_LAYER[kind]
    weights = ref.make_weights(S, 7)
    tokens, prev, is_first = sequences(np.random.default_rng(0), 3, 20, S["vocab_held"], [(0,), (0, 11), (0, 5, 6)])
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(3, np.int32), np.zeros(3, np.int32))
    want, want_v, _, _ = reference_forward(S, weights, 3, tokens, prev, is_first, pos, ep, ref)
    cfg = config_of(S)
    got, got_v, _, q_pos, aux = jax.jit(decoder.DecoderPolicy(cfg).apply)(weights, tokens, prev, is_first, decoder.zero_state(cfg, 3, jnp.float32))
    np.testing.assert_array_equal(np.asarray(q_pos), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), atol=2e-5)
    if kind.endswith("_dense"):
        assert not [name for name in aux if name.startswith("MoE/")]  # no router, none of its counters
    else:
        assert float(aux["MoE/dropped"]) == 0.0 and float(aux["MoE/held_share"]) == 1.0
        assert ("MoE/bias_moved_share" in aux) == (ref is not ONE_LAYER["full"][0])


@pytest.mark.parametrize("model", ["smallthinker", "lfm2", "lfm2_lanes", "moonlight", "moonlight_sixteen_heads"])
def test_acting_through_the_caches_matches_the_full_forward_pass(model, monkeypatch):
    """40 steps a row, one token at a time through the carry (the 8-slot rings wrap four
    times over; episodes end inside, so caches and convolution tails are both emptied),
    against one pass over each whole sequence."""
    ref, S = model_of(model, monkeypatch)
    weights = ref.make_weights(S, 11)
    n, t = 3, 40
    tokens, prev, is_first = sequences(np.random.default_rng(1), n, t, S["vocab_held"], [(0, 25), (0, 13, 30), (0, 9, 10, 31)])
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    hidden, want_v, _, _ = reference_forward(S, weights, n, tokens, prev, is_first, pos, ep, ref)
    want = np.asarray(hidden @ decoder.head_of(weights["params"]))
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg)
    step = jax.jit(lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step))
    state = decoder.zero_state(cfg, n, jnp.float32)
    for i in range(t):
        (logits,), value, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
        np.testing.assert_allclose(np.asarray(logits), want[:, i], atol=5e-5, err_msg=f"step {i}")
        np.testing.assert_allclose(np.asarray(value)[:, 0], np.asarray(want_v[:, i]), atol=5e-5)
    assert np.asarray(state["pos"]).tolist() == (pos[:, -1] + 1).tolist()
    if model == "lfm2_lanes":  # two arrays of two key heads each, a lane-full wide
        assert [x.shape for x in state["layers"][1]["k"]] == [(n, S["cache_capacity"], 1, 16)] * 2 and cfg.lane_groups == 2
    if model.startswith("moonlight"):  # one array a layer: the latent, the rotated key, zeros to a lane-full; nothing by head
        assert [sorted(layer) for layer in state["layers"]] == [["latent", "pos"]] * 3 and cfg.latent_width == decoder.LANES
        assert state["layers"][0]["latent"].shape == (n, S["cache_capacity"], decoder.LANES)
        assert not np.asarray(state["layers"][0]["latent"][..., S["kv_lora_rank"] + S["qk_rope_head_dim"] :]).any()


def test_a_latent_acting_step_compiles_once_as_its_caches_fill_and_notes_its_tile(monkeypatch):
    """Moonlight's layer kinds with sixteen query heads on each latent layer's one key head: the
    acting step goes through the blockwise kernel, and its flags and blocks to hold are data, so
    one jitted step serves caches that hold nothing, one block, three blocks and every block,
    compiled once.  Its logits and values are those of the whole-scores step over the same
    carry, and its trace notes the tile that each latent layer's call took."""
    from sheeprl_tpu.obs import perf as obs_perf
    from sheeprl_tpu.ops import blockwise_attention

    monkeypatch.setattr(blockwise_attention, "KEY_BLOCK", 16)
    S = {**MOON, "heads_held": 16, "cache_capacity": 64}
    cfg = config_of(S)
    weights = moon.make_weights(S, 17)
    policy = decoder.DecoderPolicy(cfg)
    act = lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step)  # noqa: E731
    blockwise, whole = jax.jit(act), jax.jit(act)
    n, rng = 3, np.random.default_rng(5)
    r, dr = S["kv_lora_rank"], S["qk_rope_head_dim"]
    obs_perf.reset()
    try:
        for i, fill in enumerate((0, 16, 48, 64)):
            pos = np.where(np.arange(64)[None] < fill, np.arange(64)[None], -1).repeat(n, 0).astype(np.int32)
            latent = rng.standard_normal((n, 64, cfg.latent_width)).astype(np.float32)
            latent[..., r + dr :] = 0.0
            state = {"pos": jnp.full((n,), fill, jnp.int32), "layers": tuple({"latent": jnp.asarray(latent), "pos": jnp.asarray(pos)} for _ in range(S["layers"]))}
            tok, prv = (jnp.asarray(rng.integers(0, S["vocab_held"], n), jnp.int32) for _ in range(2))
            first = jnp.full((n, 1), float(fill == 0))
            (logits,), value, _ = blockwise(state, tok, prv, first)
            if i == 0:
                notes = dict(obs_perf._notes.get("blockwise_attention", {}))
                monkeypatch.setattr(blockwise_attention, "ONE_QUERY_ROWS", 10**9)  # the whole-scores step traces with no shape taking the kernel
            (want,), want_v, _ = whole(state, tok, prv, first)
            np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=2e-5, err_msg=f"fill {fill}")
            np.testing.assert_allclose(np.asarray(value), np.asarray(want_v), atol=2e-5)
    finally:
        obs_perf.reset()
    assert blockwise._cache_size() == 1 and whole._cache_size() == 1
    tile = {"query_tile": 16, "key_block": 16, "own_keys": "merged outside the kernel"}
    assert notes == {f"act_layer_{i}": tile for i in range(S["layers"])}


@pytest.mark.parametrize("model", ["smallthinker", "lfm2", "lfm2_lanes", "moonlight"])
def test_a_chunk_after_carried_steps_matches_the_full_forward_pass(model, monkeypatch):
    """The update's view: 12 steps through the carry, then a chunk of 10 tokens in one piece
    that reads the carry as a constant, with an episode start inside the chunk (row 0: at
    its sixth token, so no tap of the convolution may cross it; row 1 carries its episode on)."""
    ref, S = model_of(model, monkeypatch)
    weights = ref.make_weights(S, 13)
    n, carried, t = 2, 12, 22
    tokens, prev, is_first = sequences(np.random.default_rng(2), n, t, S["vocab_held"], [(0, 17), (0, 4)])
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    want, _, _, _ = reference_forward(S, weights, n, tokens, prev, is_first, pos, ep, ref)
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
        out, _, counters = jax.jit(decoder.DecoderLayer(cfg, 0).apply)({"params": part}, x, cache, q_pos, q_seg)
        total = total + (out - x)
        assert float(counters["dropped"]) == 0.0
    assert float(jnp.abs(whole - x).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole - x), atol=2e-5)
    assert x.shape[-1] == D


def test_the_four_expert_shares_add_up_under_a_bias_that_changes_the_choice():
    """LFM2's expert layer shared by four chips (offsets 0, 2, 4, 6 of 8 experts; 0, 8, 16, 24
    of 32 at the published size): every chip holds the mixer whole, so it is counted once,
    and the four expert parts add up to the uncut reference's layer; the selection bias is
    wide enough here to change the chosen set of some tokens."""
    S = {**LFM2, "layers": 1, "layer_types": ["conv"], "dense_layers": 0, "bias_scale": 0.1}
    L = dict(lfm2.make_weights(S, 3)["params"]["layers_0"])
    rng = np.random.default_rng(4)
    n, t = 2, 12
    x = jnp.asarray(rng.standard_normal((n, t, S["hidden_size"])), jnp.float32)
    is_first = np.zeros((n, t), np.float32)
    is_first[:, 0] = 1
    is_first[1, 7] = 1
    ep, pos, _, _ = lfm2.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    whole, _, _ = jax.jit(lambda L, x: lfm2.layer(S, 0, L, x, lfm2.empty_context(S, n, 0), jnp.asarray(pos), jnp.asarray(ep)))(L, x)

    q_pos, q_seg = decoder.positions(jnp.asarray(is_first), jnp.zeros(n, jnp.int32))

    def share(cut, part):
        cfg = config_of(cut)
        tail = decoder.zero_state(cfg, n, jnp.float32)["layers"][0]
        return jax.jit(decoder.DecoderLayer(cfg, 0).apply)({"params": part}, x, tail, q_pos, q_seg)

    mixed, _, _ = share(S, {**L, "w_down": jnp.zeros_like(L["w_down"])})  # x + the mixer: what every chip holds whole
    total, moved = mixed - x, 0.0
    for i in range(4):
        e = slice(2 * i, 2 * i + 2)
        out, _, counters = share({**S, "experts_held": 2, "expert_offset": 2 * i}, {**L, "w_gate": L["w_gate"][e], "w_up": L["w_up"][e], "w_down": L["w_down"][e]})
        total = total + (out - mixed)
        moved = float(counters["bias_moved"])
        assert float(counters["dropped"]) == 0.0
    assert 0 < moved < n * t  # the bias changed some tokens' experts, not all
    assert float(jnp.abs(whole - mixed).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole - x), atol=2e-5)


# ---- Moonlight's layer: latent attention (MLA), a shared expert beside the routed ones, a scaled router ----


def carried_and_chunk(S, weights, seed, n=2, carried=12, t=22, firsts=((0, 17), (0, 4))):
    """Twelve tokens a row through the program's carry, and the same rows in the reference's
    context; then the ten that follow as one chunk (an episode starts inside row 0's)."""
    tokens, prev, is_first = sequences(np.random.default_rng(seed), n, t, S["vocab_held"], firsts)
    ep, pos, _, _ = moon.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg)
    step = jax.jit(lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step))
    state = decoder.zero_state(cfg, n, jnp.float32)
    for i in range(carried):
        _, _, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
    a, b = slice(0, carried), slice(carried, t)
    arrays = lambda c: (tokens[:, c], prev[:, c], jnp.asarray(is_first[:, c]), jnp.asarray(pos[:, c]), jnp.asarray(ep[:, c]))  # noqa: E731
    context = moon.empty_context(S, n, carried)
    _, _, made, _ = moon.forward(S, weights, context, *arrays(a))
    context = moon.append(context, made, jnp.asarray(pos[:, a]), jnp.asarray(ep[:, a]))
    return policy, state, context, arrays(b), arrays(slice(0, t)), carried


ATTENTION_LEAVES = ("wq", "wkv_a", "wkv_b", "wo", "kv_norm", "attn_norm")


@pytest.mark.parametrize("feed_forward", ["dense", "experts_shared"])
def test_the_latent_space_form_is_the_naive_form_with_its_gradients(feed_forward):
    """The program attends the cache in the latent's space (``W_kv_b`` folded into the queries
    and the output, one key head 128 lanes wide whose values are its first columns); the
    reference forms every head's keys and values of every row from the row's latent.
    Outputs agree to float32 rounding, and so do the gradients of every attention leaf:
    ``W_kv_b`` takes its gradient through the cached rows too, which themselves are constants."""
    S = {**MOON, "layers": 1, "dense_layers": 1 if feed_forward == "dense" else 0}
    weights = moon.make_weights(S, 17)
    policy, state, context, chunk, _, _ = carried_and_chunk(S, weights, 3)
    mix = jnp.asarray(np.random.default_rng(5).standard_normal((2, 10, S["hidden_size"])), jnp.float32)
    program = lambda w: (policy.apply(w, *chunk[:3], state)[0] * mix).sum()  # noqa: E731
    naive = lambda w: (moon.forward(S, w, context, *chunk)[0] * mix).sum()  # noqa: E731
    (got, g_got), (want, g_want) = jax.jit(jax.value_and_grad(program))(weights), jax.jit(jax.value_and_grad(naive))(weights)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for name in ATTENTION_LEAVES:
        a, b = np.asarray(g_got["params"]["layers_0"][name]), np.asarray(g_want["params"]["layers_0"][name])
        assert np.abs(b).max() > 1e-4, name
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6 * np.abs(b).max() + 1e-7, err_msg=name)


def test_the_up_projections_gradient_runs_through_the_cached_rows_and_the_cache_takes_none():
    """Two layers, twelve carried rows, a chunk of ten.  The reference with the earlier rows'
    ``c`` and ``k_pe`` as constants (``stop_gradient`` where it reads them) is what the program
    computes, leaf by leaf.  ``W_kv_b``'s gradient is not what the chunk's own keys alone give
    (the cached rows are most of what a query sees), and a carry that took a cotangent, the
    earlier rows recomputed from the weights being trained, gives ``W_kv_a`` another gradient:
    the program's is not that one."""
    S = {**MOON, "layers": 2}
    weights = moon.make_weights(S, 19)
    policy, state, context, chunk, whole, carried = carried_and_chunk(S, weights, 4)
    mix = jnp.asarray(np.random.default_rng(6).standard_normal((2, 10, S["hidden_size"])), jnp.float32)
    program = jax.jit(jax.grad(lambda w: (policy.apply(w, *chunk[:3], state)[0] * mix).sum()))(weights)
    constant = jax.jit(jax.grad(lambda w: (moon.forward(S, w, context, *chunk)[0] * mix).sum()))(weights)
    empty = moon.empty_context(S, 2, 0)
    recomputed = jax.jit(jax.grad(lambda w: (moon.forward(S, w, empty, *whole)[0][:, carried:] * mix).sum()))(weights)
    cut_off = jax.jit(jax.grad(lambda w: (moon.forward(S, w, jax.tree.map(jnp.zeros_like, context) | {"ep": context["ep"] * 0 - 1}, *chunk)[0] * mix).sum()))(weights)
    far = lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max() / np.abs(np.asarray(b)).max())  # noqa: E731
    for layer in ("layers_0", "layers_1"):
        for name in ATTENTION_LEAVES:
            assert far(program["params"][layer][name], constant["params"][layer][name]) < 2e-4, (layer, name)
        assert far(program["params"][layer]["wkv_b"], cut_off["params"][layer]["wkv_b"]) > 0.05  # the cached rows carry gradient to W_kv_b
    assert far(program["params"]["layers_0"]["wkv_a"], recomputed["params"]["layers_0"]["wkv_a"]) > 0.05  # and take none themselves
    d_cache = jax.grad(lambda s: (policy.apply(weights, *chunk[:3], s)[0] * mix).sum(), allow_int=True)(state)
    assert all(not np.asarray(layer["latent"]).any() for layer in d_cache["layers"])


def test_the_eight_expert_shares_add_up_with_attention_and_the_shared_expert_counted_once():
    """Moonlight's expert layer shared by eight chips (one of 8 experts each here; 8 of 64 at
    the published size): every chip holds the attention and the shared expert whole and
    computes them alike, so they are counted once, and the eight routed parts add up to the
    uncut reference's layer, under a selection bias wide enough to change some tokens' experts."""
    S = {**MOON, "layers": 1, "dense_layers": 0, "bias_scale": 0.1}
    L = dict(moon.make_weights(S, 3)["params"]["layers_0"])
    rng = np.random.default_rng(4)
    n, t = 2, 12
    x = jnp.asarray(rng.standard_normal((n, t, S["hidden_size"])), jnp.float32)
    is_first = np.zeros((n, t), np.float32)
    is_first[:, 0] = 1
    is_first[1, 7] = 1
    ep, pos, _, _ = moon.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    whole, _, _ = jax.jit(lambda L, x: moon.layer(S, 0, L, x, moon.empty_context(S, n, 0), jnp.asarray(pos), jnp.asarray(ep)))(L, x)
    q_pos, q_seg = decoder.positions(jnp.asarray(is_first), jnp.zeros(n, jnp.int32))

    def share(cut, part):
        cfg = config_of(cut)
        cache = decoder.zero_state(cfg, n, jnp.float32)["layers"][0]
        return jax.jit(decoder.DecoderLayer(cfg, 0).apply)({"params": part}, x, cache, q_pos, q_seg)

    alike, _, _ = share(S, {**L, "w_down": jnp.zeros_like(L["w_down"])})  # x + attention + the shared expert: what every chip computes alike
    total, moved = alike - x, 0.0
    for i in range(8):
        e = slice(i, i + 1)
        out, _, counters = share({**S, "experts_held": 1, "expert_offset": i}, {**L, "w_gate": L["w_gate"][e], "w_up": L["w_up"][e], "w_down": L["w_down"][e]})
        total = total + (out - alike)
        moved = float(counters["bias_moved"])
        assert float(counters["dropped"]) == 0.0
    assert 0 < moved < n * t  # the bias changed some tokens' experts, not all
    assert float(jnp.abs(whole - alike).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole - x), atol=2e-5)
    no_shared, _, _ = jax.jit(lambda L, x: moon.layer({**S, "shared_here": False}, 0, L, x, moon.empty_context(S, n, 0), jnp.asarray(pos), jnp.asarray(ep)))(L, x)
    assert float(jnp.abs(whole - no_shared).max()) > 1e-2  # the shared expert is a part of the layer worth counting


def test_the_routed_weights_carry_the_scale_and_the_shared_expert_does_not():
    S = {**MOON, "layers": 1, "dense_layers": 0}
    L = dict(moon.make_weights(S, 23)["params"]["layers_0"])
    n, t = 2, 6
    x = jnp.asarray(np.random.default_rng(2).standard_normal((n, t, S["hidden_size"])), jnp.float32)
    q_pos, q_seg = decoder.positions(jnp.zeros((n, t)).at[:, 0].set(1.0), jnp.zeros(n, jnp.int32))

    def out(scale, part):
        cfg = config_of({**S, "routed_scale": scale})
        return decoder.DecoderLayer(cfg, 0).apply({"params": part}, x, decoder.zero_state(cfg, n, jnp.float32)["layers"][0], q_pos, q_seg)[0]

    alike = out(2.446, {**L, "w_down": jnp.zeros_like(L["w_down"])})  # attention and the shared expert: the same whatever the scale
    np.testing.assert_array_equal(np.asarray(alike), np.asarray(out(1.0, {**L, "w_down": jnp.zeros_like(L["w_down"])})))
    routed, plain = out(2.446, L) - alike, out(1.0, L) - alike
    assert float(jnp.abs(plain).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(routed), 2.446 * np.asarray(plain), rtol=1e-4, atol=1e-6)
    top_w, _, _ = decoder.route(x.reshape(n * t, -1), L["router"], 2, True, L["expert_bias"])
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0, rtol=1e-6)  # renormalised before the scale, which the layer applies


def test_moonlights_carry_holds_a_third_kind_of_state_and_its_tree_is_the_references():
    S = {**MOON, "layers": 5}
    cfg = config_of(S)
    ids = jnp.zeros((1,), jnp.int32)
    state = decoder.zero_state(cfg, 3, jnp.bfloat16)
    assert [sorted(layer) for layer in state["layers"]] == [["latent", "pos"]] * 5
    assert state["layers"][0]["latent"].shape == (3, 48, 128) and state["layers"][0]["latent"].dtype == jnp.bfloat16
    kinds = carry_kinds(state)
    assert kinds["latent"] == {"layers": 5, "bytes": 5 * 3 * 48 * (128 * 2 + 4)} and kinds["cache"]["layers"] == kinds["conv"]["layers"] == 0
    emptied = decoder.emptied(jax.tree.map(lambda x: x + 1, state["layers"][0]), jnp.asarray([True, False, False]))
    assert np.asarray(emptied["pos"])[0].tolist() == [-1] * 48 and np.asarray(emptied["pos"])[1].tolist() == [0] * 48
    one = decoder.zero_state(cfg, 1, jnp.float32)
    tree = jax.eval_shape(lambda k: decoder.DecoderPolicy(cfg).init(k, ids, ids, jnp.ones((1, 1)), one, method=decoder.DecoderPolicy.step), jax.random.PRNGKey(0))
    have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert have == moon.flat_shapes(S)
    cast = decoder.cast_matmul_weights(moon.make_weights(S, 1), jnp.bfloat16)["params"]
    narrow = lambda layer: {k for k, v in cast[layer].items() if v.dtype == jnp.bfloat16}  # noqa: E731
    assert narrow("layers_0") == {"wq", "wkv_a", "wkv_b", "wo", "dense_gate", "dense_up", "dense_down"}
    assert narrow("layers_1") == {"wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down"}
    assert cast["head"].dtype == jnp.bfloat16 and cast["embed"].dtype == jnp.float32 and cast["layers_1"]["kv_norm"].dtype == jnp.float32


def test_the_choice_follows_score_plus_bias_and_the_weights_follow_the_score():
    """Two tokens, four experts, two a token.  The scores alone would choose experts 0 and 1;
    the bias lifts expert 3 over expert 1.  The weights are the chosen experts' scores
    without the bias, over their sum (+ 1e-6); no gradient reaches the bias."""
    logits = jnp.asarray([[2.0, 1.0, -3.0, 0.5], [0.1, 3.0, 2.0, -1.0]], jnp.float32)
    x, w = jnp.eye(2, dtype=jnp.float32), logits  # x @ w = logits
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.2], jnp.float32)
    score = np.asarray(jax.nn.sigmoid(logits))
    top_w, top_i, moved = decoder.route(x, w, 2, True, bias)
    assert np.asarray(top_i).tolist() == [[0, 3], [1, 2]] and np.asarray(moved).tolist() == [True, False]
    want = np.stack([score[0, [0, 3]], score[1, [1, 2]]])
    np.testing.assert_allclose(np.asarray(top_w), want / (want.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert not np.allclose(np.asarray(top_w)[0], (score[0, [0, 3]] + [0.0, 0.2]) / (score[0, [0, 3]].sum() + 0.2), atol=1e-3)  # the bias is in no weight
    plain_w, plain_i, none = decoder.route(x, w, 2, True)
    assert np.asarray(plain_i).tolist() == [[0, 1], [1, 2]] and none is None
    np.testing.assert_allclose(np.asarray(plain_w).sum(-1), 1.0, rtol=1e-6)
    g = jax.grad(lambda b: decoder.route(x, w, 2, True, b)[0].sum())(bias)
    assert np.asarray(g).tolist() == [0.0] * 4


def test_the_tied_tables_gradient_is_the_sum_of_the_lookups_and_the_heads():
    """One leaf is the embedding and the head: its gradient is what the lookups scatter
    into their rows plus the head's dense product, which the same loss gives two separate
    tables (the program's own pass, its head formed from ``head_of``)."""
    S = {**LFM2, "layers": 2, "layer_types": ["conv", "full_attention"]}
    cfg = config_of(S)
    weights = lfm2.make_weights(S, 5)
    n, t = 2, 9
    tokens, prev, is_first = sequences(np.random.default_rng(8), n, t, S["vocab_held"], [(0,), (0, 4)])
    actions = jnp.asarray(np.random.default_rng(9).integers(0, S["vocab_held"], (n * t,)), jnp.int32)
    policy, state = decoder.DecoderPolicy(cfg), decoder.zero_state(cfg, n, jnp.float32)

    def loss(lookup_table, head_table):
        params = {"params": {**weights["params"], "embed": lookup_table}}
        hidden, values, _, _, _ = policy.apply(params, tokens, prev, is_first, state)
        lp, ent = chunked_log_prob_and_entropy(hidden.reshape(n * t, -1), decoder.head_of({"embed": head_table}), actions, 6, jnp.float32)
        return (lp * 0.3 + ent).sum() + values.sum()

    table = weights["params"]["embed"]
    tied = jax.grad(lambda e: loss(e, e))(table)
    by_lookup, by_head = jax.grad(loss, argnums=(0, 1))(table, table)
    used = np.unique(np.concatenate([tokens.reshape(-1), prev.reshape(-1)]))
    assert np.abs(np.asarray(by_lookup)[used]).max() > 0 and not np.asarray(by_lookup)[np.setdiff1d(np.arange(S["vocab_held"]), used)].any()
    assert np.abs(np.asarray(by_head)).min() > 0  # dense: every row of the head has a gradient
    np.testing.assert_allclose(np.asarray(tied), np.asarray(by_lookup + by_head), rtol=1e-5, atol=1e-6)
    assert "head" not in jax.eval_shape(policy.init, jax.random.PRNGKey(0), tokens, prev, is_first, state)["params"]


def test_the_selection_bias_is_unchanged_by_an_update_and_by_adam():
    """Through the jitted update of ``ppo_recurrent`` itself at a tiny size, with a weight
    decay that would move any leaf the optimizer is handed: every trained leaf changes,
    ``expert_bias`` is bit for bit what it was (no gradient reaches it: the test of ``route``;
    what the decay made of it in Adam's moments is held back)."""
    import gymnasium as gym

    from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent, make_zero_state
    from sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent import make_ppo_recurrent_train_fn
    from sheeprl_tpu.analysis.ir.synth import compose_tiny, tiny_ctx

    cfg = compose_tiny(
        [
            "exp=ppo_recurrent_decoder", "algo=ppo_recurrent_lfm2_8b_a1b", "algo.rollout_steps=6", "algo.update_epochs=2", "env.num_envs=2",
            "algo.decoder.hidden_size=16", "algo.decoder.head_dim=8", "algo.decoder.heads_held=2", "algo.decoder.kv_heads_held=1",
            "algo.decoder.moe_num_primary_experts=4", "algo.decoder.experts_held=2", "algo.decoder.moe_num_active_primary_experts=2",
            "algo.decoder.moe_ffn_hidden_size=8", "algo.decoder.intermediate_size=24", "algo.decoder.vocab_held=16", "algo.decoder.cache_capacity=8",
            "algo.optimizer.weight_decay=0.1", "algo.optimizer.lr=1e-2",
        ]
    )  # fmt: skip
    ctx = tiny_ctx(cfg)
    V, T, N = 16, 6, 2
    agent, params = build_agent(ctx, gym.spaces.Discrete(V), gym.spaces.Dict({"token": gym.spaces.Box(0, V - 1, (1,), np.int32)}), cfg)
    bias = lambda tree, layer: np.asarray(tree["params"][f"layers_{layer}"]["expert_bias"])  # noqa: E731
    params = jax.tree_util.tree_map_with_path(lambda path, x: x + 0.3 if path[-1].key == "expert_bias" else x, params)
    before = jax.tree.map(np.asarray, params)
    opt, train_fn = make_ppo_recurrent_train_fn(ctx, agent, cfg, ["token"])
    rng = np.random.default_rng(3)
    ids = lambda: jnp.asarray(rng.integers(0, V, (T, N, 1)), jnp.float32)  # noqa: E731
    is_first = jnp.zeros((T, N, 1)).at[0].set(1.0).at[3, 1].set(1.0)
    vec = lambda: jnp.asarray(rng.standard_normal((T, N)), jnp.float32)  # noqa: E731
    seq = {"token": ids(), "actions": ids(), "prev_actions": ids().astype(jnp.int32), "is_first": is_first, "logprobs": vec() - 3.0, "values": vec(), "returns": vec(), "advantages": vec()}
    new, _, metrics = train_fn(params, opt.init(params), seq, make_zero_state(cfg)(N), jax.random.PRNGKey(0), 0.2, 0.0)
    changed = jax.tree_util.tree_map_with_path(lambda path, a, b: (path[-1].key, bool(np.any(np.asarray(a) != b))), new, before)
    for name, moved in jax.tree.leaves(changed, is_leaf=lambda x: isinstance(x, tuple)):
        assert moved == (name != "expert_bias"), name
    for layer in range(1, 5):
        np.testing.assert_array_equal(bias(new, layer), bias(before, layer))
    assert 0.0 <= float(metrics["MoE/bias_moved_share"]) <= 1.0


def test_smallthinkers_parameter_tree_and_carry_are_what_they_were():
    """The tree ``DecoderPolicy`` initialises for SmallThinker's layer is the one its
    reference lays out (names and shapes; no leaf of the new kinds), the carry holds a
    cache a layer and nothing else, and the cast touches the same leaves."""
    cfg = config_of(SIZES)
    ids = jnp.zeros((1,), jnp.int32)
    state = decoder.zero_state(cfg, 1, jnp.float32)
    tree = jax.eval_shape(lambda k: decoder.DecoderPolicy(cfg).init(k, ids, ids, jnp.ones((1, 1)), state, method=decoder.DecoderPolicy.step), jax.random.PRNGKey(0))
    have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert have == ref.flat_shapes(SIZES)
    assert sorted(tree["params"]["layers_0"]) == ["attn_norm", "ffn_norm", "router", "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    assert all(sorted(layer) == ["k", "pos", "v"] for layer in state["layers"]) and sorted(state) == ["layers", "pos"]
    assert [layer["k"].shape[1] for layer in state["layers"]] == [32, 8, 32, 8]
    none = {"layers": 0, "bytes": 0}
    assert carry_kinds(state) == {"cache": {"layers": 4, "bytes": sum(x.nbytes for x in jax.tree.leaves(state["layers"]))}, "conv": none, "latent": none}


def test_lfm2s_carry_holds_two_kinds_of_state_and_its_tree_is_the_references():
    cfg = config_of(LFM2)
    ids = jnp.zeros((1,), jnp.int32)
    state = decoder.zero_state(cfg, 3, jnp.bfloat16)
    assert [sorted(layer) for layer in state["layers"]] == [["conv"], ["k", "pos", "v"], ["conv"], ["conv"], ["conv"]]
    assert state["layers"][0]["conv"].shape == (3, 2, 32) and state["layers"][0]["conv"].dtype == jnp.bfloat16
    kinds = carry_kinds(state)
    assert kinds["conv"] == {"layers": 4, "bytes": 4 * 3 * 2 * 32 * 2} and kinds["cache"]["layers"] == 1
    one = decoder.zero_state(cfg, 1, jnp.float32)
    tree = jax.eval_shape(lambda k: decoder.DecoderPolicy(cfg).init(k, ids, ids, jnp.ones((1, 1)), one, method=decoder.DecoderPolicy.step), jax.random.PRNGKey(0))
    have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert have == lfm2.flat_shapes(LFM2)
    assert kinds["latent"] == {"layers": 0, "bytes": 0} and sorted(tree["params"]["layers_1"]) == sorted(lfm2.layer_shapes(LFM2, 1))  # no leaf of the new kinds


def take_path(monkeypatch, path):
    """Every call of ``decoder.expert_layer`` takes ``path``, whatever its token count."""
    monkeypatch.setattr(decoder, "EVERY_HELD_TOKENS", 10**9 if path == "every_held" else 0)


@pytest.mark.parametrize("path", ["grouped", "every_held"])
def test_no_assignment_is_dropped_when_every_token_picks_the_same_expert(path, monkeypatch):
    """No capacity: the grouped products take groups as long as the routing makes them, and
    every token through every held expert leaves out no chosen pair either."""
    take_path(monkeypatch, path)
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


#: the three cells' acting calls at a small width: (tokens, experts held, experts a token)
ACTING_CALLS = {"moonlight": (32, 8, 6), "lfm2": (64, 8, 4), "smallthinker": (64, 16, 6)}


def routed(routing, n, held, k, rng):
    """``(m [n, 64], top_w, top_i)`` over four times the held experts, the chip holding the
    second quarter.  ``no_held``: a quarter of the tokens chose no expert held here;
    ``one_expert``: every token chose held expert 1, and otherwise experts not held;
    ``biased``: a sigmoid router whose selection bias lifts two held experts over the rest."""
    E = 4 * held
    m = jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
    w_router = jnp.asarray(rng.standard_normal((64, E)) * 0.3, jnp.float32)
    if routing == "biased":
        top_w, top_i, moved = decoder.route(m, w_router, k, True, jnp.zeros(E).at[held + 2].set(0.5).at[held + 5].set(0.5))
        assert 0 < int(moved.sum()) < n
        return m, top_w, top_i
    top_w, top_i, _ = decoder.route(m, w_router, k, True)
    top_i, away = np.array(top_i), np.concatenate([np.arange(held), np.arange(2 * held, E)])
    if routing == "no_held":
        top_i[: n // 4] = [rng.choice(away, k, replace=False) for _ in range(n // 4)]
    else:
        top_i = np.stack([np.concatenate([[held + 1], rng.choice(away, k - 1, replace=False)]) for _ in range(n)])
    return m, top_w, jnp.asarray(top_i, jnp.int32)


@pytest.mark.parametrize("routing", ["no_held", "one_expert", "biased"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("cell", list(ACTING_CALLS))
def test_every_held_expert_gives_what_the_grouped_products_give(cell, dtype, routing, monkeypatch):
    """An acting call's expert layer, both ways: every token through every held expert
    against grouped products of the tokens each expert was chosen for.  The same output,
    within one rounding of the compute dtype (float32: its own), and the same counters."""
    n, held, k = ACTING_CALLS[cell]
    rng = np.random.default_rng(k * held + n)
    m, top_w, top_i = routed(routing, n, held, k, rng)
    weights = [jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32) for shape in ((held, 64, 32), (held, 64, 32), (held, 32, 64))]
    got = {}
    for path in ("grouped", "every_held"):
        take_path(monkeypatch, path)
        got[path] = jax.jit(lambda *a: decoder.expert_layer(*a, held, jnp.dtype(dtype), jax.nn.silu))(m, top_w, top_i, *weights)
    (want, want_n), (out, out_n) = got["grouped"], got["every_held"]
    want, out = np.asarray(want), np.asarray(out)
    rounding = 2.0**-8 if dtype == "bfloat16" else 2e-6
    assert np.abs(out - want).max() <= rounding * np.abs(want).max() and np.abs(want).max() > 0.1
    assert {name: float(x) for name, x in out_n.items()} == {name: float(x) for name, x in want_n.items()}
    none = ~np.any((np.asarray(top_i) >= held) & (np.asarray(top_i) < 2 * held), -1)  # tokens that chose no expert held here
    assert not out[none].any()
    if routing == "no_held":
        assert none[: n // 4].all()
    if routing == "one_expert":
        assert float(out_n["held"]) == float(out_n["load_max"]) == n


#: the three decoder cells: configuration, traffic, and the small sizes above that carry the keys this file's configs read
CELLS = {"smallthinker": ("smallthinker21b_1of4", "rl_gen", SIZES), "lfm2": ("lfm2_8b_a1b_1of4", "rl_gen", LFM2), "moonlight": ("moonlight16b_1of8", "rl_gen32", MOON)}


def rehearsal_of(model):
    """A cell's sizes as its CPU rehearsal runs them (its configuration's file) and the rows
    and tokens a row of its traffic: the acting call's token count and the update's."""
    name, traffic, small = CELLS[model]
    root = pathlib.Path(__file__).resolve().parents[2] / "perfbench"
    config = json.loads((root / "configs" / f"{name}.json").read_text())
    sizes = {**config["sizes"], **config["rehearsal"]["sizes"]}
    mix = json.loads((root / "traffic" / f"{traffic}.json").read_text())
    return {**small, **{key: sizes[key] for key in small if key in sizes}}, mix["num_envs"], mix["rollout_steps"]


@pytest.mark.parametrize("model", list(CELLS))
def test_an_acting_step_goes_through_every_held_expert_and_the_update_keeps_its_grouped_products(model):
    """At each decoder cell's rehearsal widths and its traffic's token counts (an acting call
    of one token a row, an update of the whole rollout): the acting step's program holds no
    grouped product, the update's forward pass its three a layer, and the trace notes each
    expert layer's path under the call's kind."""
    from sheeprl_tpu.obs import perf as obs_perf

    S, envs, steps = rehearsal_of(model)
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg, jnp.bfloat16)
    state = decoder.zero_state(cfg, envs, jnp.bfloat16)
    ids, chunk = jnp.zeros((envs,), jnp.int32), jnp.zeros((envs, steps), jnp.int32)
    weights = jax.eval_shape(lambda key: policy.init(key, ids, ids, jnp.ones((envs, 1)), state, method=decoder.DecoderPolicy.step), jax.random.PRNGKey(0))
    obs_perf.reset()
    try:
        act = str(jax.make_jaxpr(lambda w: policy.apply(w, ids, ids, jnp.ones((envs, 1)), state, method=decoder.DecoderPolicy.step))(weights))
        update = str(jax.make_jaxpr(lambda w: policy.apply(w, chunk, chunk, jnp.ones((envs, steps)), state))(weights))
        notes = dict(obs_perf._notes["expert_path"])
    finally:
        obs_perf.reset()
    layers = range(cfg.dense_layers, cfg.layers)
    assert envs <= decoder.EVERY_HELD_TOKENS < envs * steps and len(layers) == cfg.expert_layers > 0
    assert "ragged_dot" not in act and update.count("ragged_dot_general[") == 3 * len(layers)
    assert notes == {**{f"act_layer_{i}": "every_held" for i in layers}, **{f"layer_{i}": "grouped" for i in layers}}


@pytest.mark.parametrize("model", ["smallthinker", "lfm2", "moonlight"])
def test_an_acting_steps_log_probabilities_are_the_updates_within_bfloat16_rounding(model, monkeypatch):
    """A bfloat16 policy, every expert held, 4 rows x 40 tokens: the acting steps (one token a
    row: every held expert) against the update's one pass over the whole chunk (160 tokens:
    grouped products), log-probabilities of every vocabulary entry.  The acting step is as
    near the update as it was through grouped products, within one bfloat16 rounding of a
    log-probability, and the two acting paths are that near each other."""
    ref, S = model_of(model, monkeypatch)
    S = {**S, "experts_held": S["num_experts"]}
    weights = ref.make_weights(S, 11)
    n, t = 4, 40
    tokens, prev, is_first = sequences(np.random.default_rng(1), n, t, S["vocab_held"], [(0, 25), (0, 13, 30), (0, 9, 10, 31), (0,)])
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg, jnp.bfloat16)
    assert decoder.expert_path(n) == "every_held" and decoder.expert_path(n * t) == "grouped"
    hidden = jax.jit(policy.apply)(weights, tokens, prev, is_first, decoder.zero_state(cfg, n, jnp.bfloat16))[0]
    update = np.asarray(jax.nn.log_softmax(policy.apply(weights, hidden, method=decoder.DecoderPolicy.logits)))
    acting = {}
    for path, cutoff in (("every_held", decoder.EVERY_HELD_TOKENS), ("grouped", 0)):
        monkeypatch.setattr(decoder, "EVERY_HELD_TOKENS", cutoff)
        step = jax.jit(lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step))
        state, rows = decoder.zero_state(cfg, n, jnp.bfloat16), []
        for i in range(t):
            (logits,), _, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
            rows.append(np.asarray(jax.nn.log_softmax(logits)))
        acting[path] = np.stack(rows, 1)
    rounding = 2.0**-8 * np.abs(update).max()
    gap = lambda a, b: float(np.abs(a - b).max())  # noqa: E731
    assert gap(acting["every_held"], update) <= gap(acting["grouped"], update) + rounding
    assert gap(acting["every_held"], acting["grouped"]) <= rounding


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


def test_the_new_layers_matmul_weights_are_cast_and_the_tied_table_taps_and_bias_stay():
    cast = decoder.cast_matmul_weights(lfm2.make_weights(LFM2, 1), jnp.bfloat16)["params"]
    narrow = lambda layer: {k for k, v in cast[layer].items() if v.dtype == jnp.bfloat16}  # noqa: E731
    assert narrow("layers_0") == {"conv_in", "conv_out", "dense_gate", "dense_up", "dense_down"}
    assert narrow("layers_1") == {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"} and narrow("layers_2") == {"conv_in", "conv_out", "w_gate", "w_up", "w_down"}
    assert cast["embed"].dtype == jnp.float32  # the lookups read it exactly; the tied head casts it where it multiplies
    held = decoder.hold_buffers(jax.tree.map(jnp.ones_like, cast))
    assert not held["layers_1"]["expert_bias"].any() and held["layers_1"]["router"].all() and held["embed"].all()
