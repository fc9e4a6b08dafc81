"""Nemotron-3-Nano's cell (``nemotron3nano30b_1of16.rl_gen32x256``) through the harness on the
CPU at the configuration's rehearsal sizes (the cell's nine blocks MEMEM*EME at width 32: 4
Mamba heads of 8 in 2 groups, a state of 16, scan chunks of 8 under rollouts of 20, 4 query
and 2 key heads of 8, 8 experts of which 2 held and 2 a token; two sequence minibatches an
epoch; float32): three PPO updates against the plain reference, which computes the Mamba
mixer by its recurrence, token by token; the controls (lower precision, a state carried in
the reference computed in bfloat16, and the reference with the state a Mamba block carries
from one rollout into the next dropped, come out as not correct.

One process drives everything here (a module-scoped run), so the program's jitted
functions compile once.  Nothing in this file is a time or a rate.
"""

import json

import pytest

CELL = "nemotron3nano30b_1of16.rl_gen32x256"
SEED = 2147483711  # above 2**31: a seed past 32 signed bits


@pytest.fixture(scope="module")
def sound(out_dir):
    from perfbench import harness

    return harness.drive(CELL, SEED, 0.5, False, rehearsal=True)


@pytest.fixture(scope="module")
def reported(sound):
    """The run's result line, and the reference's readings that judged it."""
    from perfbench import harness

    return harness.report(sound), sound["judged"]["reference"]


@pytest.fixture(scope="module")
def reference(reported):
    return reported[1]


def verdict(sound, reference, readings):
    from perfbench import check

    numbers = check.compare(readings, reference, **sound["adapter"].compared())
    return check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


def test_three_updates_over_two_minibatches_are_correct(sound, reported, capsys):
    from perfbench import harness

    harness.emit(reported[0])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rehearsal.grad_steps_per_s", "rehearsal.env_steps_per_s", "rehearsal.setup_s"}
    assert set(line["compared"]) == set(sound["cell"].limits(True)) and "grad_gap.mamba" in line["compared"]
    for c in line["compared"].values():
        assert c["value"] <= c["limit"] / 5  # read on the CPU: 0 .. 1.3e-6 against 1e-5 .. 2e-5
    w, S = sound["window"], sound["sizes"]
    assert S["num_batches"] == 2 and w["grad_steps"] == S["update_epochs"] * S["num_batches"] * w["blocks"] > 0
    assert w["compile_requests"] == 0, "something compiled inside the window"
    perms = [roll["perm"] for roll in sound["adapter"].rollouts(sound["rows"])]
    assert all(p.shape == (2, 2, 2) and sorted(p[e].ravel().tolist()) == [0, 1, 2, 3] for p in perms for e in range(2))


def test_the_compared_rollouts_cut_the_scan_and_end_episodes_of_both_kinds(sound, reference):
    seen = sound["adapter"].coverage(reference)
    assert seen["terminated_in_compared_rows"] > 0 and seen["truncated_in_compared_rows"] > 0
    assert seen["leaves_under_grad_floor"] == 4  # the four routers' selection biases: no gradient, by construction
    assert seen["tokens_x_layers"] == 4 * sound["sizes"]["num_envs"] * sound["sizes"]["rollout_steps"]  # the mixers meet no router
    for step in sound["program"]["steps"]:
        reported = step["reported"]
        assert reported["Health/ratio_first_epoch"] == pytest.approx(1.0, abs=1e-5)
        assert 0.0 < reported["SSM/resets_in_chunk_share"] < 1.0 and reported["MoE/dropped"] == 0.0
    facts = sound["adapter"].facts()
    assert len(facts["resets_in_chunk_share by update"]) == sound["adapter"].blocks and facts["ssm state bytes"] == 4 * 4 * 4 * 8 * 16 * 4


WRONG = {"bf16": ({}, {"quant": "bf16"}), "carried_state_dropped": ({"ssm_carry": False}, {"fault": "planted"})}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_the_reference_in_lower_precision_or_without_the_carried_state_is_not_correct(sound, reference, wrong):
    """The reference in the program's place, computed in bfloat16 (the nearest precision below
    the float32 this rehearsal states), or with the state a Mamba block carries from one
    rollout into the next dropped: each fails a limit.  (The state rounded to bfloat16 is read
    on the chip by ``perfbench/tools/ssm_readings.py``.)"""
    from perfbench.adapters.ssm_policy import SsmPolicyAdapter

    adapter = sound["adapter"]
    sizes, how = WRONG[wrong]
    if sizes:
        adapter = SsmPolicyAdapter({**sound["sizes"], **sizes}, sound["seed"], adapter.ref)
        adapter._ref_logp, adapter.keys = sound["adapter"]._ref_logp, sound["adapter"].keys
    correct, numbers = verdict(sound, reference, adapter.reference_readings(sound["rows"], sound["program"], **how))
    assert not correct, numbers


def test_a_carried_ssm_state_in_another_dtype_is_not_correct():
    """``loss_gap.ssm_state_float32``: the program's carried SSM states are held to the float32
    the configuration states; the same update handed a bfloat16 state reads a gap of 1."""
    import jax.numpy as jnp

    from perfbench import check, harness
    from perfbench.adapters.ssm_policy import SsmPolicyAdapter

    cell = harness.Cell(CELL)
    limits = cell.limits(True)
    for dtype, correct in ((jnp.float32, True), (jnp.bfloat16, False)):
        adapter = SsmPolicyAdapter(cell.sizes(True), SEED, None)
        state0 = {"pos": jnp.zeros(2, jnp.int32), "layers": ({"ssm": jnp.zeros((2, 4, 8, 16), dtype), "conv": jnp.zeros((2, 3, 96))}, {})}
        for _ in range(3):
            adapter.call_update(lambda *args: (None, None, {}), None, None, None, state0, None, 0.2, 0.0)
        gap = abs(adapter.carried_dtype_reading() - 1.0)  # as check.compare reads a loss against the reference's 1
        name = "loss_gap.ssm_state_float32"
        assert check.verdict({name: gap}, {name: limits[name]})["correct"] is correct


def test_the_window_counts_the_update_in_flight_in_parts(monkeypatch):
    """Past warm-up the counts the harness reads hold the update in flight by its share of a
    cycle's time, so a window of ten cycles and a half reads the loop's pace wherever its ends
    fall; the count is continuous across a dispatch, ``grad_steps / blocks`` stays an update's
    steps, and warm-up, the update's own bookkeeping and what is read after the run count
    whole updates."""
    import time

    from perfbench.adapters.ssm_policy import SsmPolicyAdapter

    now = [0.0]
    monkeypatch.setattr(time, "perf_counter", lambda: now[0])
    adapter = SsmPolicyAdapter({}, SEED, None)
    adapter.grad_steps, adapter.blocks = 12, 3  # past the compared updates: nothing recorded
    update = adapter._record(lambda *args: ({}, None, {}), 4)
    assert update.__wrapped__ is not None

    def dispatch(t):
        now[0] = t
        update({}, None, {"logprobs": None}, {"layers": []}, None, 0.2, 0.0)

    for t in (0.0, 3.0, 6.0, 9.0):
        dispatch(t)
    now[0] = 10.5
    assert (adapter.grad_steps, adapter.blocks) == (28, 7)  # warm-up: whole updates
    adapter.drain()
    open_steps, open_blocks = adapter.grad_steps, adapter.blocks
    assert (open_steps, open_blocks) == (30, 7.5)
    now[0] = 11.9
    assert adapter.grad_steps == 32
    now[0] = 12.3
    assert adapter.grad_steps == 32  # at most one update in flight
    dispatch(12.3)
    assert (adapter._whole_steps, adapter._whole_blocks, adapter.grad_steps) == (32, 8, 32)
    for k in range(1, 10):
        dispatch(12.3 + 3.0 * k)
    now[0] = 42.2  # the window's end just before an update's dispatch
    steps, blocks = adapter.grad_steps - open_steps, adapter.blocks - open_blocks
    assert (steps, blocks) == (42, 10.5) and adapter._whole_steps - 28 == 40  # whole updates alone: 40
    assert steps / (42.2 - 10.5) == pytest.approx(4 / 3.0, rel=0.01)
    adapter.uninstall()
    assert (adapter.grad_steps, adapter.blocks) == (68, 17)


def test_the_new_readers_read_the_programs_own_names(monkeypatch):
    from perfbench.flops_ssm_decoder import scan_costs
    from perfbench.readers import spans, ssm_decoder

    for reader in (ssm_decoder.ssm_device_ms, ssm_decoder.act_ssm_device_ms, ssm_decoder.ssd_scan_roofline):
        assert reader({"traced": False}) is None and reader({}) is None  # the parent commit, the other models: nothing, not an error
    red = {
        "steps_per_execution": 4.0,
        "device": {
            "jit_train_fn": {"executions": 2, "module_s": 3.0, "scopes": {"policy/mamba fwd": 0.2, "policy/mamba bwd": 0.4, "policy/ssd_scan fwd": 0.1, "policy/ssd_scan bwd": 0.3, "policy/experts fwd": 1.0}},
            "jit_act": {"executions": 100, "module_s": 0.8, "scopes": {"policy/mamba fwd": 0.2, "policy/ssd_scan fwd": 0.3, "policy/experts fwd": 0.1}},
        },
    }
    monkeypatch.setattr(spans, "of_run", lambda run: red if run.get("traced") else None)
    S = {"mamba_heads": 64, "mamba_head_dim": 64, "ssm_groups": 8, "ssm_state": 128, "chunk_size": 128, "num_envs": 32, "num_batches": 2, "rollout_steps": 256, "pattern": "MEMEM*EME", "layers": 9, "precision": "bf16-mixed"}
    run = {"traced": True, "sizes": S, "device": {"kind": "TPU v5 lite", "count": 1}, "peaks": {"TPU v5 lite": {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}}}
    assert ssm_decoder.ssm_device_ms(run) == pytest.approx(1e3 * 1.0 / 8)
    assert ssm_decoder.act_ssm_device_ms(run) == pytest.approx(5.0)
    one = scan_costs(16, 256, S)
    # by hand: two chunks of 128 a row; 2 x (128 x 128 x (8 x 128 + 64 x 64) + 2 x 128 x 64 x 64 x 128) a chunk and row
    assert one["flops"] == 2.0 * 16 * 2 * (128 * 128 * (1024 + 4096) + 2 * 128 * 64 * 64 * 128)
    assert one["bytes"] == 16 * 256 * (4096 * 2 + 2048 * 2 + 64 * 4 + 4096 * 4) + 16 * 5 * 64 * 64 * 128 * 4
    assert one["flops"] / one["bytes"] < 240  # under the chip's ridge: the bytes bound it
    least = 4 * 8 * 4 * one["bytes"] / 819e9  # 8 gradient steps, 4 Mamba blocks, the forward pass counted four times
    assert ssm_decoder.ssd_scan_roofline(run) == pytest.approx(100.0 * least / 0.4) and 0 < least / 0.4 < 1
    del red["device"]["jit_train_fn"]["scopes"]["policy/ssd_scan fwd"], red["device"]["jit_train_fn"]["scopes"]["policy/ssd_scan bwd"]
    assert ssm_decoder.ssd_scan_roofline(run) is None


CATALOG_CONFIG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}  # fmt: skip


def test_the_configuration_holds_every_published_width():
    """Against the catalog's row (``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, config.json as
    published), written out here; the parameters counted by hand."""
    from perfbench import harness
    from perfbench.flops_ssm_decoder import parameters, step_flops
    from sheeprl_tpu.config.core import compose
    from sheeprl_tpu.models.decoder import DecoderConfig

    cell = harness.Cell(CELL)
    c, S = cell.config, cell.sizes(False)
    assert {k: c[k] for k in CATALOG_CONFIG} == CATALOG_CONFIG
    assert c["source"] == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"
    assert S["pattern"] == c["hybrid_override_pattern"][: S["layers"]] == "MEMEM*EME"
    assert (S["hidden_size"], S["heads_held"], S["kv_heads_held"], S["head_dim"]) == (2688, 32, 2, 128)
    assert (S["mamba_heads"], S["mamba_head_dim"], S["ssm_groups"], S["ssm_state"], S["conv_kernel"], S["chunk_size"]) == (64, 64, 8, 128, 4, 128)
    assert (S["num_experts"], S["experts_per_token"], S["expert_width"], S["shared_width"], S["routed_scale"]) == (128, 6, 1856, 3712, 2.5)
    assert (S["norm_eps"], S["router_eps"], S["cache_capacity"]) == (1e-5, 1e-20, 8192)
    held = {"layers": 9, "experts_held": 8, "vocab_held": 16384}
    assert {k: c[k] for k in held} == held == {k: S[k] for k in held}
    assert S["experts_held"] * 16 == c["n_routed_experts"] and S["vocab_held"] * 8 == c["vocab_size"]
    assert set(held) | {"env"} == set(c["reduced"]) == set(c["reduced_why"])
    assert {"mamba", "conv_taps", "no_dt_clamp", "ssm_state", "nope", "router_eps", "residual_in_fp32", "value_head", "input", "weights"} <= set(c["assumed"])
    assert "sixteen chips" in c["deployment"] and "shared expert" in c["deployment"]
    # by hand: a Mamba block 38.74 M, the attention block 23.41 M, an expert block 20.30 M + 8 x 9.98 M, the two tables 88.08 M
    mamba = 2688 + 2688 * (4096 + 6144 + 64) + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688
    attention = 2688 + 2688 * (2 * 4096 + 2 * 256)
    experts = 2688 + 2688 * 128 + 128 + 2 * 2688 * (8 * 1856 + 3712)
    assert parameters(S) == 4 * mamba + attention + 4 * experts + 2 * 16384 * 2688 + 2 * 2688 + 1 and 666.9e6 < parameters(S) < 667.0e6
    f = step_flops(S)
    assert f["total"] == pytest.approx(sum(v for k, v in f.items() if k != "total"))
    assert 0.40 < (f["mamba_projections"] + f["ssd_scan"]) / (f["total"] - f["optimizer"]) < 0.55  # the Mamba blocks do about half the counted work
    text = (harness.ROOT / "perfbench/configs/nemotron3nano30b_1of16_reference.py").read_text().split('"""', 2)[2]
    assert "sheeprl_tpu" not in text and "moonlight" not in text and 'default_matmul_precision("highest")' in text
    d = DecoderConfig.from_cfg(compose(overrides=[o for o in c["overrides"] if not o.startswith("env")] + ["env=token_score"]).algo.decoder)
    assert (d.hidden_size, d.head_dim, d.heads_held, d.kv_heads_held, d.mamba_heads, d.mamba_head_dim, d.ssm_groups, d.ssm_state) == (2688, 128, 32, 2, 64, 64, 8, 128)
    assert (d.num_experts, d.experts_held, d.experts_per_token, d.expert_width, d.shared_width, d.routed_scale) == (128, 8, 6, 1856, 3712, 2.5)
    assert (d.vocab_held, d.layers, d.capacity, d.conv_taps, d.ssm_chunk, d.rms_norm_eps) == (16384, 9, 8192, 4, 128, 1e-5)
    assert d.router == "sigmoid" and d.router_reads == "ffn_norm" and d.activation == "relu2" and not d.tie_embeddings and d.rope_layout == (0,) * 9


def test_the_flops_counts_context_is_the_timed_windows_fill():
    """``sizes.mean_context``: what the traffic's own generator gives a token of the attention
    block over the chunks the timed window updates (it opens at iteration 1,026-1,329 and
    holds about 2,700 iterations on the chip, so iterations 1,024 .. 3,584): the keys written
    before its chunk in its own episode."""
    import numpy as np

    from perfbench import harness
    from perfbench.envs import clock, token_env

    cell = harness.Cell(CELL)
    S, T = cell.sizes(False), cell.traffic
    chunk, first, last = T["rollout_steps"], 1024, 3584
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
