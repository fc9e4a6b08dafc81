"""Moonlight's cell (``moonlight16b_1of8.rl_gen32``) through the harness on the CPU at the
configuration's rehearsal sizes (the cell's five layers at width 32: latent attention with
the dense feed-forward, then four of latent attention with routed and shared experts; a
latent of 16, heads of 8 + 4 and 8; 8 experts of which 2 held and 2 a token; float32):
three PPO updates against the plain reference, which computes MLA naively; the control in
lower precision and the planted faults, a latent cache that an episode's start does not
empty among them, come out as not correct.

One process drives everything here (module-scoped runs), so the program's jitted
functions compile once a run.  Nothing in this file is a time or a rate.
"""

import json

import pytest

CELL = "moonlight16b_1of8.rl_gen32"
SEED = 2147483711  # above 2**31, as the driver's are


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
    assert {"grad_gap.attention", "grad_gap.shared", "grad_gap.dense", "loss_gap.old_logprob"} <= set(line["compared"])
    for c in line["compared"].values():
        assert c["value"] <= c["limit"] / 10  # read on the CPU: 7e-8 .. 5e-7 against 1e-4 .. 1e-3


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
    """Ratio 1 before any step of an update: the chunk attended blockwise in the latent's
    space over the carried caches says what the acting steps said one token at a time."""
    for step in sound["program"]["steps"]:
        reported = step["reported"]
        assert reported["Health/ratio_first_epoch"] == pytest.approx(1.0, abs=1e-5)
        assert reported["MoE/dropped"] == 0.0
        assert 0.0 < reported["MoE/held_share"] < 1.0 and reported["MoE/load_max_over_mean"] >= 1.0
        assert 0.0 < reported["MoE/bias_moved_share"] < 1.0
    assert sound["program"]["steps"][0]["reported"]["Attn/key_blocks_visited_share"] == 0.0  # the first update's caches are empty
    assert sound["program"]["steps"][2]["reported"]["Attn/key_blocks_visited_share"] > 0.0


def test_the_groups_are_the_issues_and_the_bias_reads_zero_on_both_sides(sound, reference):
    groups = sound["adapter"].compared()["groups"]
    grouped = {i for g in groups.values() for i in g["leaves"]}
    S, ref = sound["sizes"], sound["adapter"].ref
    names = list(ref.flat_shapes(S))
    leaf = lambda g: {names[i].rsplit("/", 1)[1] for i in groups[g]["leaves"]}  # noqa: E731
    assert set(groups) == {"attention", "shared", "experts", "router", "dense", "tables"}
    assert leaf("attention") == {"wq", "wkv_a", "wkv_b", "wo", "kv_norm"} and len(groups["attention"]["leaves"]) == 25
    assert leaf("shared") == {"shared_gate", "shared_up", "shared_down"} and leaf("dense") == {"dense_gate", "dense_up", "dense_down"}
    assert [names[i] for i in groups["tables"]["leaves"]] == ["params/embed", "params/head"]  # untied
    bias = [i for i, n in enumerate(names) if n.endswith("expert_bias")]
    assert len(bias) == 4 and not grouped & set(bias)
    for side in (sound["program"], reference):
        assert [side["grad_norms"][i] for i in bias] == [0.0] * 4
        assert max(side["change_norms"][i] for i in bias) < 1e-7  # the seed's weights made twice differ in a last bit; no step moved it


def test_the_new_readers_read_the_programs_own_names(sound, monkeypatch):
    from perfbench.readers import latent_decoder, spans

    # a program without the scopes, the kernels or the counter (the parent commit, the other models): nothing, not an error
    for reader in (latent_decoder.latent_attention_device_ms, latent_decoder.shared_expert_device_ms, latent_decoder.act_latent_attention_device_ms, latent_decoder.latent_attention_roofline):
        assert reader({"traced": False}) is None and reader({}) is None
    assert latent_decoder.latent_attention_roofline(sound) is None  # an untraced run kept no reports and has no capture
    assert sound["adapter"].capture_reports == []
    red = {
        "steps_per_execution": 2.0,
        "device": {
            "jit_train_fn": {"executions": 3, "module_s": 0.6, "scopes": {"policy/attention_latent fwd": 0.03, "policy/attention_latent bwd": 0.06, "policy/shared_expert bwd": 0.012, "policy/attention_full fwd": 0.3}},
            "jit_act": {"executions": 100, "module_s": 0.5, "scopes": {"policy/attention_latent fwd": 0.4, "policy/experts fwd": 0.1}},
        },
    }
    monkeypatch.setattr(spans, "of_run", lambda run: red if run.get("traced") else None)
    assert latent_decoder.latent_attention_device_ms({"traced": True}) == pytest.approx(15.0)
    assert latent_decoder.shared_expert_device_ms({"traced": True}) == pytest.approx(2.0)
    assert latent_decoder.act_latent_attention_device_ms({"traced": True}) == pytest.approx(4.0)
    del red["device"]["jit_act"]["scopes"]["policy/attention_latent fwd"]
    assert latent_decoder.act_latent_attention_device_ms({"traced": True}) is None


def test_the_roofline_counts_visited_blocks_by_kernel_call_over_the_kernels_seconds(monkeypatch):
    """Two captured updates of a made-up run: each shows the forward kernel twice (the pass
    and its recomputation) and the backward one once for one latent layer; the shares are
    the updates' own.  By hand: flags a pass x share x (2 forward + 1 backward) block
    counts over the kernels' seconds and the peak (a share of the flags times the blocks is
    that share of every slot, which is how the reader counts it)."""
    from perfbench.flops_latent_decoder import latent_block_flops
    from perfbench.readers import latent_decoder

    S = {"rollout_steps": 64, "heads_held": 16, "num_envs": 32, "cache_capacity": 8192, "kv_lora_rank": 512, "qk_rope_head_dim": 64}
    block = latent_block_flops(1024, 512, S)
    assert block == {"forward": 2.0 * 1024 * 512 * (576 + 512), "backward": 2.0 * 1024 * 512 * (576 + 512 + 576)}
    calls = [("forward", 0.004), ("forward", 0.004), ("backward", 0.012)]
    monkeypatch.setattr(latent_decoder, "kernel_events", lambda run: [calls, calls])
    adapter = type("Kept", (), {"capture_reports": [{"Attn/key_blocks_visited_share": 0.125, "MoE/dropped": 0.0}, {"Attn/key_blocks_visited_share": 0.25}]})()
    run = {"adapter": adapter, "sizes": S, "device": {"kind": "TPU v5 lite", "count": 1}, "peaks": {"TPU v5 lite": {"flops_per_s_bf16": 197e12}}, "traced": True}
    by_hand = (0.125 + 0.25) * 32 * 16 * (2 * block["forward"] + block["backward"]) / (2 * 0.020 * 197e12)
    assert latent_decoder.latent_attention_roofline(run) == pytest.approx(100.0 * by_hand) and 0.0 < by_hand < 1.0
    adapter.capture_reports = adapter.capture_reports[:1]  # an update cut by the capture's edge: the mean share for every execution
    assert latent_decoder.latent_attention_roofline(run) == pytest.approx(100.0 * by_hand * 0.125 / 0.1875)
    adapter.capture_reports = [{"MoE/dropped": 0.0}]  # a program that reports no share
    assert latent_decoder.latent_attention_roofline(run) is None


def test_control_in_lower_precision_is_not_correct(sound, reference):
    """The reference in the program's place, computed in bfloat16 (the nearest precision
    below the float32 this rehearsal states), fails at least one number."""
    from perfbench import check

    adapter = sound["adapter"]
    control = adapter.reference_readings(sound["rows"], sound["program"], quant="bf16")
    numbers = check.compare(control, reference, **adapter.compared())
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


FAULTS = {
    "scale_left_off_the_routed_weights": {"routed_scale": 1.0},
    "shared_expert_left_out": {"shared_here": False},
    "bias_left_out_of_the_choice": {"bias_scale": 0.0},
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


def test_a_latent_cache_not_emptied_at_an_episodes_start_is_not_correct(sound, out_dir, monkeypatch):
    """The program itself with one thing wrong: an acting step that leaves a latent cache's
    rows as they were when an episode starts.  The new episode's tokens then attend to the
    latents of the episode before it, and the log-probabilities the acting path wrote are
    not the reference's."""
    from perfbench import harness
    from sheeprl_tpu.models import decoder

    real = decoder.emptied
    monkeypatch.setattr(decoder, "emptied", lambda state, first: state if "latent" in state else real(state, first))
    faulty = harness.report(harness.drive(CELL, SEED, 0.3, False, rehearsal=True))
    assert faulty["correct"] is False
    held = {name: c["value"] <= c["limit"] for name, c in faulty["compared"].items()}
    assert not held["loss_gap.old_logprob"], faulty["compared"]


def test_the_configuration_holds_every_published_width():
    """Against the catalog's row (``Moonlight-16B-A3B``, config.json as published), written out here."""
    from perfbench import harness
    from perfbench.flops_latent_decoder import parameters, step_flops

    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
        "kv_lora_rank": 512, "max_position_embeddings": 8192, "model_type": "deepseek_v3", "moe_intermediate_size": 1408, "moe_layer_freq": 1,
        "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_hidden_layers": 27, "num_key_value_heads": 16, "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000, "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
    }  # fmt: skip
    cell = harness.Cell(CELL)
    c, S = cell.config, cell.sizes(False)
    assert {k: c[k] for k in published} == published and c["source"] == "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"
    assert (S["hidden_size"], S["heads_held"], S["kv_lora_rank"], S["qk_nope_head_dim"], S["qk_rope_head_dim"], S["v_head_dim"]) == (2048, 16, 512, 128, 64, 128)
    assert (S["expert_width"], S["shared_width"], S["dense_width"], S["num_experts"], S["experts_per_token"]) == (1408, 2 * 1408, 11264, 64, 6)
    assert (S["routed_scale"], S["rope_theta"], S["norm_eps"], S["router_eps"], S["cache_capacity"]) == (2.446, 5e4, 1e-5, 1e-20, 8192)
    held = {"layers": 5, "experts_held": 8, "vocab_held": 20480}
    assert {k: c[k] for k in held} == held == {k: S[k] for k in held} and S["dense_layers"] == c["first_k_dense_replace"] == 1
    assert S["experts_held"] * 8 == c["n_routed_experts"] and S["vocab_held"] * 8 == c["vocab_size"]  # an eighth each: the floors
    assert set(held) | {"env"} == set(c["reduced"]) == set(c["reduced_why"])
    assert {"attention", "rope", "router", "expert_bias", "feed_forward", "router_eps", "constant_in_the_update", "value_head", "input", "facts_keys"} <= set(c["assumed"])
    assert "eight chips" in c["deployment"] and "shared expert" in c["deployment"]
    assert (cell.traffic["num_envs"], cell.traffic["rollout_steps"], cell.traffic["update_epochs"], cell.traffic["min_length"], cell.traffic["max_length"], cell.traffic["cache_capacity"]) == (32, 64, 2, 512, 8192, 8192)
    rl_gen = harness.load_json(harness.ROOT / "perfbench/traffic/rl_gen.json")
    assert {k: v for k, v in cell.traffic.items() if k not in ("num_envs", "why")} == {k: v for k, v in rl_gen.items() if k not in ("num_envs", "why")}
    # the count by hand: attention 13.76 M, dense layer 82.98 M, expert layer 100.41 M, the two tables 83.89 M
    attention = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 + 16 * 128 * 2048
    dense_layer = attention + 3 * 2048 * 11264 + 2 * 2048
    expert_layer = attention + 3 * 2048 * (8 * 1408 + 2816) + 2048 * 64 + 64 + 2 * 2048
    assert parameters(S) == dense_layer + 4 * expert_layer + 2 * 20480 * 2048 + 2048 + 2048 + 1 and 568.4e6 < parameters(S) < 568.6e6
    f, n = step_flops(S), 32 * 64
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total")) and 3.9e12 < f["total"] < 4.1e12
    assert f["attention_projections"] == pytest.approx(5 * 6 * n * (attention - 512))  # every projection once over the chunk's tokens, three passes
    assert f["latent_attention"] == pytest.approx(5 * 2 * n * 16 * (770 * 2 * 1088 + 32 * 3 * 1088))
    assert f["shared_expert"] == pytest.approx(4 * 6 * n * 3 * 2048 * 2816) and f["experts"] == pytest.approx(4 * 6 * n * 0.75 * 3 * 2048 * 1408)
    assert f["dense_ffn"] == pytest.approx(6 * n * 3 * 2048 * 11264) and f["head"] == pytest.approx(6 * n * 2048 * 20481)
    assert 0.33 < (f["latent_attention"] + f["attention_projections"]) / f["total"] < 0.40  # the new mixer does over a third of the counted work at the window's fill
    text = (harness.ROOT / "perfbench/configs/moonlight16b_1of8_reference.py").read_text().split('"""', 2)[2]
    assert "sheeprl_tpu" not in text and "smallthinker" not in text and "lfm2" not in text and 'default_matmul_precision("highest")' in text
    # the program's own configuration says the same
    from sheeprl_tpu.config.core import compose
    from sheeprl_tpu.models.decoder import DecoderConfig

    d = DecoderConfig.from_cfg(compose(overrides=[o for o in c["overrides"] if not o.startswith("env")] + ["env=token_score"]).algo.decoder)
    assert (d.hidden_size, d.head_dim, d.heads_held, d.kv_lora_rank, d.qk_nope_head_dim, d.qk_rope_head_dim, d.v_head_dim) == (2048, 192, 16, 512, 128, 64, 128)
    assert (d.num_experts, d.experts_held, d.experts_per_token, d.expert_width, d.shared_width, d.routed_scale) == (64, 8, 6, 1408, 2816, 2.446)
    assert (d.dense_width, d.dense_layers, d.vocab_held, d.layers, d.capacity, d.latent_width) == (11264, 1, 20480, 5, 8192, 640)
    assert d.mixers == ("latent",) * 5 and d.router == "sigmoid" and d.router_reads == "ffn_norm" and d.activation == "silu"
    assert not d.tie_embeddings and not d.qk_norm and d.rope_theta == 5e4 and d.rms_norm_eps == 1e-5 and d.norm_topk_prob


def test_the_flops_counts_context_is_the_timed_windows_fill():
    """``sizes.mean_context`` is what the traffic's own generator gives over the window the
    cell times: every sequence starts at once, the window opens at iteration 258 and holds
    about 1,640 iterations (the chip's runs), so the 25 updates in it are over the chunks
    that start at iterations 256 .. 1,792; a token sees of the cache the keys written
    before its chunk in its own episode."""
    import numpy as np

    from perfbench import harness
    from perfbench.envs import clock, token_env

    cell = harness.Cell(CELL)
    S, T = cell.sizes(False), cell.traffic
    chunk, first, last = T["rollout_steps"], 256, 1792
    kept, seen = list(clock.ENVS), []
    try:
        for seed in (2147483711, 2147483777, 2147483801):
            for rank in range(T["num_envs"]):
                env = token_env.TokenEnv(seed + rank, rank, S["vocab_held"], T["min_length"], T["max_length"], T["early_ends"], T["early_end_within"])
                env.reset()
                started = np.zeros(last + chunk, np.int64)  # for every iteration, the one its episode started at
                for t in range(last + chunk):
                    started[t] = t - env._t
                    _, _, terminated, truncated, _ = env.step(0)
                    if terminated or truncated:
                        env.reset()
                for c in range(first, last + 1, chunk):
                    seen.append(np.maximum(c - started[c : c + chunk], 0).mean())
    finally:
        clock.ENVS[:] = kept
    assert S["mean_context"] == pytest.approx(np.mean(seen), rel=0.1), np.mean(seen)
    assert S["mean_context"] < 0.5 * 2048  # rl_gen's value, a token's mean position long after the start, is not this window's
