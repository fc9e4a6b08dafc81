"""Nemotron-3-Nano's blocks in the decoder policy (``sheeprl_tpu/models/decoder.py``: blocks of
one part, the Mamba-2 mixer over ``ops/ssd_scan.py``, non-gated relu-squared experts beside a
shared one) against the benchmark's plain reference
(``perfbench/configs/nemotron3nano30b_1of16_reference.py``, which computes the mixer by its
recurrence, token by token) on seeded weights, at a small size on the CPU: the published
pattern's first nine blocks MEMEM*EME, width 32, 4 Mamba heads of 8 in 2 groups, a state of
16, scan chunks of 8, 8 experts, float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.configs import nemotron3nano30b_1of16_reference as ref
from sheeprl_tpu.models import decoder

SMALL = {
    "hidden_size": 32, "head_dim": 8, "heads_held": 4, "kv_heads_held": 2, "mamba_heads": 4, "mamba_head_dim": 8, "ssm_groups": 2,
    "ssm_state": 16, "conv_kernel": 4, "chunk_size": 8, "num_experts": 8, "experts_held": 8, "expert_offset": 0, "experts_per_token": 2,
    "expert_width": 16, "shared_width": 32, "routed_scale": 2.5, "vocab_held": 48, "layers": 9, "pattern": "MEMEM*EME", "norm_eps": 1e-5,
    "router_eps": 1e-6, "norm_topk_prob": True, "cache_capacity": 64, "router_scale": 2.0, "branch_scale": 0.25, "bias_scale": 0.05,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
}  # fmt: skip


def config_of(S) -> decoder.DecoderConfig:
    mixers, ffn = zip(*(decoder.PATTERN[kind] for kind in S["pattern"][: S["layers"]]))
    return decoder.DecoderConfig(
        hidden_size=S["hidden_size"], head_dim=S["head_dim"], heads_held=S["heads_held"], kv_heads_held=S["kv_heads_held"],
        num_experts=S["num_experts"], experts_held=S["experts_held"], experts_per_token=S["experts_per_token"], expert_width=S["expert_width"],
        vocab_held=S["vocab_held"], layers=S["layers"], window=0, mixers=mixers, rope_layout=(0,) * S["layers"], rms_norm_eps=S["norm_eps"],
        expert_offset=S["expert_offset"], capacity=S["cache_capacity"], conv_taps=S["conv_kernel"], router="sigmoid", router_reads="ffn_norm",
        activation="relu2", shared_width=S["shared_width"], routed_scale=S["routed_scale"], ffn_layout=ffn, mamba_heads=S["mamba_heads"],
        mamba_head_dim=S["mamba_head_dim"], ssm_groups=S["ssm_groups"], ssm_state=S["ssm_state"], ssm_chunk=S["chunk_size"],
    )  # fmt: skip


def sequences(rng, n, t, firsts):
    tokens = rng.integers(0, SMALL["vocab_held"], (n, t)).astype(np.int32)
    actions = rng.integers(0, SMALL["vocab_held"], (n, t)).astype(np.int32)
    is_first = np.zeros((n, t), np.float32)
    for row, steps in enumerate(firsts):
        is_first[row, list(steps)] = 1.0
    prev = np.concatenate([np.zeros((n, 1), np.int32), actions[:, :-1]], 1)
    ep, pos, _, _ = ref.episodes_and_positions(is_first, np.zeros(n, np.int32), np.zeros(n, np.int32))
    return tokens, prev, is_first, pos, ep


def reference_forward(S, weights, n, tokens, prev, is_first, pos, ep):
    with jax.default_matmul_precision("highest"):
        run = jax.jit(lambda w, *arrays: ref.forward(S, w, ref.empty_context(S, n, 0), *arrays))
        return run(weights, tokens, prev, jnp.asarray(is_first), jnp.asarray(pos), jnp.asarray(ep))


def test_the_nine_blocks_match_the_reference_and_hold_its_tree():
    S = SMALL
    weights = ref.make_weights(S, 7)
    tokens, prev, is_first, pos, ep = sequences(np.random.default_rng(0), 3, 20, [(0,), (0, 11), (0, 5, 6)])
    want, want_v, _, _, _ = reference_forward(S, weights, 3, tokens, prev, is_first, pos, ep)
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg)
    state = decoder.zero_state(cfg, 3, jnp.float32)
    tree = jax.eval_shape(lambda: policy.init(jax.random.PRNGKey(0), tokens[:, :1], prev[:, :1], is_first[:, :1], state))
    have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert have == ref.flat_shapes(S)
    got, got_v, _, q_pos, aux = jax.jit(policy.apply)(weights, tokens, prev, is_first, state)
    np.testing.assert_array_equal(np.asarray(q_pos), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), atol=2e-5)
    assert float(aux["MoE/dropped"]) == 0.0 and float(aux["MoE/held_share"]) == 1.0 and 0 < float(aux["MoE/bias_moved_share"]) < 1
    assert float(aux["SSM/resets_in_chunk_share"]) == pytest.approx(4 / 9)  # of 3 rows x 3 chunks: every row's first, and row 1's second


def test_acting_steps_from_a_carry_give_the_updates_outputs_and_the_same_state():
    """12 steps a row through the carry, then 20 more: one token at a time (acting), and in one
    chunk that reads the carry as a constant (the update, three scan chunks).  Both give the
    reference's full pass over all 32 tokens, and the chunk leaves each Mamba block the state
    that the 20 acting steps wrote.  Row 0 starts an episode inside the chunk, row 1 carries its
    episode through it, row 2 starts one at the chunk's first token."""
    S = SMALL
    weights = ref.make_weights(S, 13)
    n, carried, t = 3, 12, 32
    tokens, prev, is_first, pos, ep = sequences(np.random.default_rng(2), n, t, [(0, 17), (0, 4), (0, 12, 25)])
    want, _, _, _, _ = reference_forward(S, weights, n, tokens, prev, is_first, pos, ep)
    want_logits = np.asarray(want @ weights["params"]["head"])
    cfg = config_of(S)
    policy = decoder.DecoderPolicy(cfg)
    step = jax.jit(lambda state, tok, prv, first: policy.apply(weights, tok, prv, first, state, method=decoder.DecoderPolicy.step))
    state = decoder.zero_state(cfg, n, jnp.float32)
    for i in range(carried):
        _, _, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
    start = state
    got, _, written, _, _ = jax.jit(policy.apply)(weights, tokens[:, carried:], prev[:, carried:], is_first[:, carried:], start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, carried:]), atol=5e-5)
    for i in range(carried, t):
        (logits,), _, state = step(state, tokens[:, i], prev[:, i], is_first[:, i : i + 1])
        np.testing.assert_allclose(np.asarray(logits), want_logits[:, i], atol=5e-5, err_msg=f"step {i}")
    mamba = [l for l, kind in enumerate(S["pattern"]) if kind == "M"]
    assert len(mamba) == 4
    for l in mamba:
        np.testing.assert_allclose(np.asarray(written[l]["ssm"]), np.asarray(state["layers"][l]["ssm"]), atol=1e-5, rtol=1e-5)
        assert float(jnp.abs(state["layers"][l]["ssm"]).max()) > 1e-2
        np.testing.assert_allclose(np.asarray(written[l]["conv"][:, -3:]), np.asarray(state["layers"][l]["conv"]), atol=1e-6)


def test_the_carry_holds_a_fourth_kind_of_state_and_a_start_empties_it():
    cfg = config_of(SMALL)
    state = decoder.zero_state(cfg, 3, jnp.bfloat16)
    kinds = [sorted(layer) for layer in state["layers"]]
    assert kinds == [["conv", "ssm"], [], ["conv", "ssm"], [], ["conv", "ssm"], ["k", "pos", "v"], [], ["conv", "ssm"], []]
    assert state["layers"][0]["ssm"].shape == (3, 4, 8, 16) and state["layers"][0]["ssm"].dtype == jnp.float32  # whatever the compute dtype
    assert state["layers"][0]["conv"].shape == (3, 3, 4 * 8 + 2 * 2 * 16) and state["layers"][0]["conv"].dtype == jnp.bfloat16
    full = jax.tree.map(lambda x: jnp.ones_like(x), state["layers"][0])
    emptied = decoder.emptied(full, jnp.asarray([False, True, False]))
    ssm, conv = np.asarray(emptied["ssm"], np.float32), np.asarray(emptied["conv"], np.float32)
    assert not ssm[1].any() and not conv[1].any() and ssm[[0, 2]].all() and conv[[0, 2]].all()
    assert decoder.emptied({}, jnp.asarray([True, True, True])) == {}


def test_the_sixteen_expert_shares_add_up_with_the_shared_expert_counted_once():
    """An expert block shared by sixteen chips (one of 16 experts each here; 8 of 128 at the
    published size): every chip holds the router and the shared expert whole and computes the
    shared expert alike, so it is counted once, and the sixteen routed parts add up to the
    uncut reference's block, under a selection bias wide enough to change some tokens' experts."""
    S = {**SMALL, "layers": 1, "pattern": "E", "num_experts": 16, "experts_held": 16, "experts_per_token": 3, "bias_scale": 0.1}
    L = dict(ref.make_weights(S, 3)["params"]["layers_0"])
    rng = np.random.default_rng(4)
    n, t = 2, 12
    x = jnp.asarray(rng.standard_normal((n, t, S["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = jax.jit(lambda L, x: ref.experts(S, L, x, lambda v: v))(L, x)
    q_pos, q_seg = decoder.positions(jnp.zeros((n, t)), jnp.zeros(n, jnp.int32))

    def share(cut, part):
        cfg = config_of(cut)
        return jax.jit(decoder.DecoderLayer(cfg, 0).apply)({"params": part}, x, {}, q_pos, q_seg)

    alike, _, _ = share(S, {**L, "w_down": jnp.zeros_like(L["w_down"])})  # x + the shared expert: what every chip computes alike
    total, moved = alike - x, 0.0
    for i in range(16):
        e = slice(i, i + 1)
        out, _, counters = share({**S, "experts_held": 1, "expert_offset": i}, {**L, "w_up": L["w_up"][e], "w_down": L["w_down"][e]})
        total = total + (out - alike)
        moved = float(counters["bias_moved"])
        assert float(counters["dropped"]) == 0.0
    assert 0 < moved < n * t  # the bias changed some tokens' experts, not all
    assert float(jnp.abs(whole - alike).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole - x), atol=2e-5)
    no_shared, _ = jax.jit(lambda L, x: ref.experts({**S, "shared_here": False}, L, x, lambda v: v))(L, x)
    assert float(jnp.abs(whole - no_shared).max()) > 1e-2  # the shared expert is a part of the block worth counting


@pytest.mark.parametrize("path", ["grouped", "every_held"])
def test_a_relu_squared_expert_has_no_gate_on_either_path(path, monkeypatch):
    monkeypatch.setattr(decoder, "EVERY_HELD_TOKENS", 10**9 if path == "every_held" else 0)
    rng = np.random.default_rng(5)
    N, D, F, E = 24, 16, 8, 4
    m = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((E, F, D)), jnp.float32)
    top_i = jnp.asarray(rng.integers(0, 2 * E, (N, 2)), jnp.int32)  # half of the choices are experts held elsewhere
    top_w = jnp.asarray(rng.uniform(0, 1, (N, 2)), jnp.float32)
    out, counters = decoder.expert_layer(m, top_w, top_i, None, w_up, w_down, 0, jnp.float32, decoder.ACTIVATIONS["relu2"])
    want = np.zeros((N, D), np.float32)
    for row in range(N):
        for k in range(2):
            e = int(top_i[row, k])
            if e < E:
                want[row] += float(top_w[row, k]) * np.asarray(jnp.square(jax.nn.relu(m[row] @ w_up[e])) @ w_down[e])
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    assert float(counters["dropped"]) == 0.0 and "relu2" in decoder.UNGATED


def test_the_configs_read_the_published_shared_width_and_the_pattern():
    """Nemotron publishes its shared expert's width apart (3,712, not 1 x 1,856); Moonlight and
    LFM2 keep the width derived from their expert width to the digit."""
    from sheeprl_tpu.config.core import compose

    def of(algo):
        return decoder.DecoderConfig.from_cfg(compose(overrides=["exp=ppo_recurrent_decoder", f"algo={algo}"]).algo.decoder)

    nemotron, moonlight, lfm2 = of("ppo_recurrent_nemotron3nano30b"), of("ppo_recurrent_moonlight16b"), of("ppo_recurrent_lfm2_8b_a1b")
    assert (nemotron.shared_width, moonlight.shared_width, lfm2.shared_width) == (3712, 2 * 1408, 0)
    assert nemotron.mixers == ("mamba", "none", "mamba", "none", "mamba", "full", "none", "mamba", "none")
    assert nemotron.ffn_layout == (0, 1, 0, 1, 0, 0, 1, 0, 1) and nemotron.expert_layers == 4
    assert (nemotron.mamba_heads, nemotron.mamba_head_dim, nemotron.ssm_groups, nemotron.ssm_state, nemotron.ssm_chunk) == (64, 64, 8, 128, 128)
    assert (nemotron.conv_taps, nemotron.ssm_conv_width, nemotron.activation, nemotron.rope_layout[5]) == (4, 6144, "relu2", 0)
    assert moonlight.ffn_layout == () == lfm2.ffn_layout and moonlight.expert_layers == 4
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        decoder.DecoderConfig.from_cfg({**compose(overrides=["exp=ppo_recurrent_decoder", "algo=ppo_recurrent_nemotron3nano30b"]).algo.decoder, "hybrid_override_pattern": "MEM"})


def test_the_mamba_blocks_matmul_weights_are_cast_and_the_rest_stay():
    cfg = config_of({**SMALL, "layers": 1, "pattern": "M"})
    state = decoder.zero_state(cfg, 1, jnp.float32)
    ids = jnp.zeros((1,), jnp.int32)
    params = jax.jit(lambda key: decoder.DecoderPolicy(cfg).init(key, ids, ids, jnp.ones((1, 1)), state, method=decoder.DecoderPolicy.step))(jax.random.PRNGKey(0))
    cast = decoder.cast_matmul_weights(params, jnp.bfloat16)["params"]["layers_0"]
    assert {k for k, v in cast.items() if v.dtype == jnp.bfloat16} == {"mamba_in", "mamba_out"}
    assert np.allclose(np.exp(np.asarray(params["params"]["layers_0"]["A_log"])), [1, 2, 3, 4])
    step = np.log1p(np.exp(np.asarray(params["params"]["layers_0"]["dt_bias"])))
    assert step.min() == pytest.approx(1e-3, rel=1e-3) and step.max() == pytest.approx(1e-1, rel=1e-3)
