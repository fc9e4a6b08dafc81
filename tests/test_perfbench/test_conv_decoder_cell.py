"""LFM2's cell (``lfm2_8b_a1b_1of4.rl_gen``) through the harness on the CPU at the
configuration's rehearsal sizes (the cell's five layers at width 32: a convolution mixer
with the dense feed-forward, full attention with q/k norm and experts, three convolution
mixers with experts; 8 experts of which 2 held and 2 a token; float32): three PPO updates
against the plain reference; the control in lower precision and the planted faults, a
convolution tail that an episode's start does not empty among them, come out as not
correct.

One process drives everything here (module-scoped runs), so the program's jitted
functions compile once a run.  Nothing in this file is a time or a rate.
"""

import json

import pytest

CELL = "lfm2_8b_a1b_1of4.rl_gen"
SEED = 2147483693  # above 2**31, as the driver's are


@pytest.fixture(scope="module")
def sound(out_dir):
    from perfbench import harness

    return harness.drive(CELL, SEED, 0.5, False, rehearsal=True)


@pytest.fixture(scope="module")
def reference(sound):
    return sound["adapter"].reference_readings(sound["rows"], sound["program"])


def test_three_updates_are_correct_and_the_line_has_the_cells_metrics(sound, capsys):
    from perfbench import harness

    harness.emit(harness.report(sound))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rehearsal.grad_steps_per_s", "rehearsal.env_steps_per_s", "rehearsal.setup_s"}  # not iter_ms.p95
    assert set(line["compared"]) == set(sound["cell"].limits(True)) and len(line["compared"]) == 11
    assert {"grad_gap.conv", "grad_gap.dense", "loss_gap.old_logprob"} <= set(line["compared"])
    for c in line["compared"].values():
        assert c["value"] <= c["limit"] / 10  # read on the CPU: 3e-8 .. 2e-6 against 1e-4 .. 1e-3


def test_the_window_counts_the_work_and_nothing_compiles_in_it(sound):
    w, S = sound["window"], sound["sizes"]
    assert w["grad_steps"] == S["update_epochs"] * w["blocks"] > 0
    assert w["env_steps"] == S["num_envs"] * w["iterations"]
    assert w["compile_requests"] == 0, "something compiled inside the window"
    assert w["spans"]["dispatch"]["calls"] == w["blocks"]


def test_the_rollouts_compared_hold_episode_ends_of_both_kinds(sound, reference):
    seen = sound["adapter"].coverage(reference)
    assert seen["terminated_in_compared_rows"] > 0 and seen["truncated_in_compared_rows"] > 0
    assert seen["leaves_under_grad_floor"] == 4  # the four routers' selection biases: no gradient, by construction
    assert seen["expert_choices_flipped_by_bf16"] <= seen["tokens_x_layers"] // 10
    assert seen["tokens_x_layers"] == 4 * sound["sizes"]["num_envs"] * sound["sizes"]["rollout_steps"]  # the dense layer meets no router


def test_the_first_epoch_recomputes_the_acting_log_probabilities(sound):
    """Ratio 1 before any step of an update: the chunk read through the carried cache and
    the carried convolution tails says what the acting steps said one token at a time."""
    for step in sound["program"]["steps"]:
        reported = step["reported"]
        assert reported["Health/ratio_first_epoch"] == pytest.approx(1.0, abs=1e-5)
        assert reported["MoE/dropped"] == 0.0
        assert 0.0 < reported["MoE/held_share"] < 1.0 and reported["MoE/load_max_over_mean"] >= 1.0
        assert 0.0 < reported["MoE/bias_moved_share"] < 1.0


def test_the_selection_bias_reads_zero_on_both_sides_of_the_change(sound, reference):
    groups = sound["adapter"].compared()["groups"]
    grouped = {i for g in groups.values() for i in g["leaves"]}
    S, ref = sound["sizes"], sound["adapter"].ref
    names = list(ref.flat_shapes(S))
    bias = [i for i, n in enumerate(names) if n.endswith("expert_bias")]
    assert len(bias) == 4 and not grouped & set(bias)
    for side in (sound["program"], reference):
        assert [side["grad_norms"][i] for i in bias] == [0.0] * 4
        assert max(side["change_norms"][i] for i in bias) < 1e-7  # the seed's weights made twice differ in a last bit; no step moved it
    assert {names[i].rsplit("/", 1)[1] for i in groups["conv"]["leaves"]} == {"conv_in", "conv_kernel", "conv_out"}
    assert {names[i].rsplit("/", 1)[1] for i in groups["dense"]["leaves"]} == {"dense_gate", "dense_up", "dense_down"}
    assert [names[i] for i in groups["tables"]["leaves"]] == ["params/embed"]  # the tied table: no head beside it


def test_the_new_readers_read_the_programs_own_names(sound, monkeypatch):
    from perfbench.readers import conv_decoder, spans

    steps = sound["program"]["steps"]
    assert conv_decoder.router_bias_moved_share(sound) == pytest.approx(100 * sum(s["reported"]["MoE/bias_moved_share"] for s in steps) / 3)
    # a program without the counter or the scopes (the parent commit, the other model): nothing, not an error
    assert conv_decoder.router_bias_moved_share({"program": {"steps": [{"reported": {"MoE/dropped": 0.0}}]}}) is None
    assert conv_decoder.router_bias_moved_share({}) is None
    assert conv_decoder.conv_device_ms({"traced": False}) is None and conv_decoder.dense_ffn_device_ms({"traced": False}) is None
    red = {
        "steps_per_execution": 2.0,
        "device": {"jit_train_fn": {"executions": 3, "module_s": 0.6, "scopes": {"policy/conv fwd": 0.03, "policy/conv bwd": 0.06, "policy/dense_ffn bwd": 0.012, "policy/experts fwd": 0.3}}},
    }
    monkeypatch.setattr(spans, "of_run", lambda run: red if run.get("traced") else None)
    assert conv_decoder.conv_device_ms({"traced": True}) == pytest.approx(15.0) and conv_decoder.dense_ffn_device_ms({"traced": True}) == pytest.approx(2.0)
    del red["device"]["jit_train_fn"]["scopes"]["policy/dense_ffn bwd"]
    assert conv_decoder.dense_ffn_device_ms({"traced": True}) is None


def test_control_in_lower_precision_is_not_correct(sound, reference):
    """The reference in the program's place, computed in bfloat16 (the nearest precision
    below the float32 this rehearsal states), fails at least one number."""
    from perfbench import check

    adapter = sound["adapter"]
    control = adapter.reference_readings(sound["rows"], sound["program"], quant="bf16")
    numbers = check.compare(control, reference, **adapter.compared())
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


FAULTS = {
    "renormalisation_left_out": {"norm_topk_prob": False},
    "bias_left_out_of_the_choice": {"bias_scale": 0.0},
    "a_tap_too_few": {"conv_taps": 2},  # a kernel of two taps where the model has three
    "gae_lambda_of_one": {"gae_lambda": 1.0},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_is_not_correct(sound, reference, fault):
    """The reference in the program's place with one thing wrong: every one fails a limit."""
    from perfbench import check
    from perfbench.adapters.sequence_policy import SequencePolicyAdapter

    adapter = sound["adapter"]
    wrong = SequencePolicyAdapter({**sound["sizes"], **FAULTS[fault]}, sound["seed"], adapter.ref)
    wrong._ref_logp = adapter._ref_logp
    numbers = check.compare(wrong.reference_readings(sound["rows"], sound["program"], quant="f32", fault="planted"), reference, **adapter.compared())
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


def test_half_of_the_batch_left_out_is_not_correct(sound, reference):
    from perfbench import check

    adapter = sound["adapter"]
    numbers = check.compare(adapter.reference_readings(sound["rows"], sound["program"], fault="half_batch"), reference, **adapter.compared())
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


def test_a_convolution_tail_not_emptied_at_an_episodes_start_is_not_correct(sound, out_dir, monkeypatch):
    """The program itself with one thing wrong: an acting step that empties the cache rows
    of an episode that starts, but leaves the convolution tails as they were.  The new
    episode's second token then reads a gated input of the episode before it, and the
    log-probabilities the acting path wrote are not the reference's."""
    from perfbench import harness
    from sheeprl_tpu.models import decoder

    real = decoder.emptied
    monkeypatch.setattr(decoder, "emptied", lambda state, first: state if "conv" in state else real(state, first))
    faulty = harness.report(harness.drive(CELL, SEED, 0.3, False, rehearsal=True))
    assert faulty["correct"] is False
    held = {name: c["value"] <= c["limit"] for name, c in faulty["compared"].items()}
    assert not held["loss_gap.old_logprob"], faulty["compared"]


def test_the_configuration_holds_every_published_width():
    """Against the catalog's row (``LFM2-8B-A1B``, config.json as published), written out here."""
    from perfbench import harness
    from perfbench.flops_conv_decoder import parameters, step_flops

    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }  # fmt: skip
    layer_types = [
        "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv",
    ]  # fmt: skip
    cell = harness.Cell(CELL)
    c, S = cell.config, cell.sizes(False)
    assert {k: c[k] for k in published} == published and c["layer_types"] == layer_types and len(layer_types) == 24
    assert layer_types.count("conv") == 18 and layer_types[2:6] == ["full_attention", "conv", "conv", "conv"]
    assert (S["hidden_size"], S["head_dim"], S["heads_held"], S["kv_heads_held"], S["expert_width"], S["dense_width"]) == (2048, 64, 32, 8, 1792, 7168)
    assert (S["num_experts"], S["experts_per_token"], S["conv_taps"], S["rope_theta"], S["norm_eps"]) == (32, 4, 3, 1e6, 1e-5)
    held = {"layers": 5, "dense_layers": 1, "experts_held": 8, "vocab_held": 16384}
    assert {k: c[k] for k in held} == held == {k: S[k] for k in held}
    assert S["layer_types"] == c["layer_types_held"] == [layer_types[0]] + layer_types[2:6]
    assert set(held) | {"env"} == set(c["reduced"]) == set(c["reduced_why"])
    assert {"tied_head", "head_dim", "qk_norm", "router", "expert_bias", "value_head", "input"} <= set(c["assumed"])
    assert 507e6 < parameters(S) < 508.5e6
    f = step_flops(S)
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total")) and 5.0e12 < f["total"] < 5.2e12
    assert 0.5 < (f["conv"] + f["dense_ffn"]) / f["total"] < 0.56  # the new parts do most of the counted work
    text = (harness.ROOT / "perfbench/configs/lfm2_8b_a1b_1of4_reference.py").read_text().split('"""', 2)[2]
    assert "sheeprl_tpu" not in text and "smallthinker" not in text and 'default_matmul_precision("highest")' in text
    # the program's own configuration says the same
    from sheeprl_tpu.config.core import compose
    from sheeprl_tpu.models.decoder import DecoderConfig

    d = DecoderConfig.from_cfg(compose(overrides=[o for o in c["overrides"] if not o.startswith("env")] + ["env=token_score"]).algo.decoder)
    assert (d.hidden_size, d.head_dim, d.heads_held, d.kv_heads_held, d.num_experts, d.experts_held, d.experts_per_token) == (2048, 64, 32, 8, 32, 8, 4)
    assert (d.expert_width, d.dense_width, d.dense_layers, d.vocab_held, d.layers, d.conv_taps) == (1792, 7168, 1, 16384, 5, 3)
    assert d.mixers == ("conv", "full", "conv", "conv", "conv") and d.router == "sigmoid" and d.router_reads == "ffn_norm"
    assert d.qk_norm and d.tie_embeddings and d.activation == "silu" and d.rope_theta == 1e6 and d.rms_norm_eps == 1e-5
