"""The decoder policy's cell (``smallthinker21b_1of4.rl_gen``) through the harness on the
CPU at the configuration's rehearsal sizes (window 8, 8 experts of which 2 held, 2 full
+ 2 window layers, float32): three PPO updates against the plain reference; the
control in lower precision and the planted faults come out as not correct.

One process drives everything here (a module-scoped run), so the program's jitted
functions compile once.  Nothing in this file is a time or a rate.
"""

import json

import pytest

CELL = "smallthinker21b_1of4.rl_gen"


@pytest.fixture(scope="module")
def sound(out_dir):
    from perfbench import harness

    return harness.drive(CELL, 2147483693, 0.5, False, rehearsal=True)  # a seed above 2**31, as the driver's are


@pytest.fixture(scope="module")
def reference(sound):
    return sound["adapter"].reference_readings(sound["rows"], sound["program"])


def test_three_updates_are_correct_and_the_line_has_the_cells_metrics(sound, capsys):
    from perfbench import harness

    harness.emit(harness.report(sound))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rehearsal.grad_steps_per_s", "rehearsal.env_steps_per_s", "rehearsal.setup_s"}  # not iter_ms.p95
    assert set(line["compared"]) == set(sound["cell"].limits(True)) and len(line["compared"]) == 9
    for c in line["compared"].values():
        assert c["value"] <= c["limit"] / 10  # read on the CPU: 1e-7 .. 2e-6 against 1e-4 .. 1e-3


def test_the_window_counts_the_work_and_nothing_compiles_in_it(sound):
    w, S = sound["window"], sound["sizes"]
    assert w["grad_steps"] == S["update_epochs"] * w["blocks"] > 0
    assert w["env_steps"] == S["num_envs"] * w["iterations"]
    assert w["compile_requests"] == 0, "something compiled inside the window"
    assert w["spans"]["dispatch"]["calls"] == w["blocks"]


def test_the_rollouts_compared_hold_episode_ends_of_both_kinds(sound, reference):
    seen = sound["adapter"].coverage(reference)
    assert seen["terminated_in_compared_rows"] > 0 and seen["truncated_in_compared_rows"] > 0
    assert seen["leaves_under_grad_floor"] == 0
    assert seen["expert_choices_flipped_by_bf16"] <= seen["tokens_x_layers"] // 10


def test_the_first_epoch_recomputes_the_acting_log_probabilities(sound):
    """Ratio 1 before any step of an update: the chunk read through the carried cache
    says what the acting steps said one token at a time; and no assignment is dropped."""
    for step in sound["program"]["steps"]:
        reported = step["reported"]
        assert reported["Health/ratio_first_epoch"] == pytest.approx(1.0, abs=1e-5)
        assert reported["MoE/dropped"] == 0.0
        assert 0.0 < reported["MoE/held_share"] < 1.0 and reported["MoE/load_max_over_mean"] >= 1.0


def test_the_counter_reader_reads_the_updates_own_counter(sound):
    from perfbench.readers import decoder

    steps = sound["program"]["steps"]
    assert decoder.expert_load_max_over_mean(sound) == pytest.approx(sum(s["reported"]["MoE/load_max_over_mean"] for s in steps) / 3)
    assert decoder.expert_load_max_over_mean({"program": {"steps": [{"reported": {}}]}}) is None
    assert decoder.experts_device_ms({"traced": False}) is None and decoder.act_call_ms({"traced": False}) is None


def test_the_counters_of_the_compared_updates_are_read_and_printed(sound, reference):
    from perfbench.readers import decoder

    assert decoder.first_epoch_ratio_gap(sound) == pytest.approx(0.0, abs=1e-5) and decoder.moe_dropped(sound) == 0.0
    assert decoder.first_epoch_ratio_gap({"program": {"steps": [{"reported": {}}]}}) is None and decoder.moe_dropped({}) is None
    seen = sound["adapter"].coverage(reference)
    assert len(seen["Health/ratio_first_epoch"]) == 3 and seen["MoE/dropped"] == [0.0, 0.0, 0.0] and len(seen["MoE/held_share"]) == 3


def test_the_scope_readers_split_the_two_modules_of_a_capture(monkeypatch):
    """On a reduction as ``readers/spans.py`` makes one (the CPU's capture has no device
    plane): the whole update and its parts a gradient step, each module's unscoped share."""
    from perfbench.readers import decoder, spans

    red = {
        "steps_per_execution": 2.0,
        "device": {
            "jit_train_fn": {"executions": 3, "module_s": 0.6, "scopes": {"policy/embed fwd": 0.01, "policy/embed bwd": 0.05, "policy/router bwd": 0.012, "health fwd": 0.03, "policy/experts fwd": 0.3, spans.UNSCOPED: 0.006}},
            "jit_act": {"executions": 100, "module_s": 0.3, "scopes": {"policy/experts fwd": 0.15, spans.UNSCOPED: 0.05}},
            "jit_block": {"executions": 1, "module_s": 9.0, "scopes": {"health fwd": 9.0}},
        },
    }
    monkeypatch.setattr(spans, "of_run", lambda run: red if run.get("traced") else None)
    run = {"traced": True}
    assert decoder.update_step_device_ms(run) == pytest.approx(100.0)
    assert decoder.embed_device_ms(run) == pytest.approx(10.0) and decoder.router_device_ms(run) == pytest.approx(2.0)
    assert decoder.policy_health_device_ms(run) == pytest.approx(5.0)  # not the other family's block
    assert decoder.update_unscoped_share(run) == pytest.approx(100 * 0.006 / 0.408)
    assert decoder.act_unscoped_share(run) == pytest.approx(25.0) and decoder.act_step_device_ms(run) == pytest.approx(3.0)
    red["device"].pop("jit_act")
    assert decoder.act_unscoped_share(run) is None  # a program without the module: nothing, not an error
    for reader in (decoder.update_step_device_ms, decoder.embed_device_ms, decoder.update_unscoped_share, decoder.act_unscoped_share):
        assert reader({"traced": False}) is None


def test_only_the_first_episodes_of_the_early_envs_are_short():
    """The traffic's lengths hold for every episode but the first of the ``early_ends``
    envs, which ends inside the rows that ``correct`` compares, by both kinds of end."""
    from perfbench.envs import clock, token_env

    kept = list(clock.ENVS)
    try:
        kinds = set()
        for rank in range(6):
            env = token_env.TokenEnv(seed=2147483693, rank=rank, vocab=16, min_length=40, max_length=90, early_ends=4, early_end_within=24)
            lengths, ends = [], []
            for _ in range(4):
                env.reset()
                done = False
                while not done:
                    _, _, terminated, truncated, _ = env.step(0)
                    done = terminated or truncated
                lengths.append(env._t)
                ends.append("terminated" if terminated else "truncated")
            assert all(40 <= n <= 90 for n in lengths[1:]), lengths
            assert (2 <= lengths[0] <= 24) if rank < 4 else (40 <= lengths[0] <= 90), (rank, lengths)
            assert ends[0] != ends[1] == ends[3] != ends[2]
            kinds.add(ends[0])
        assert kinds == {"terminated", "truncated"}
    finally:
        clock.ENVS[:] = kept


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
    "rope_on_the_full_layers": {"rope_layout": [1, 1, 1, 1]},
    "window_off_by_one": {"window": 9},
    "gae_lambda_of_one": {"gae_lambda": 1.0},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_is_not_correct(sound, reference, fault):
    """The reference in the program's place with one thing wrong: every one fails a limit."""
    from perfbench import check
    from perfbench.adapters.sequence_policy import SequencePolicyAdapter

    wrong = SequencePolicyAdapter({**sound["sizes"], **FAULTS[fault]}, sound["seed"], sound["adapter"].ref)
    wrong._ref_logp = sound["adapter"]._ref_logp
    numbers = check.compare(wrong.reference_readings(sound["rows"], sound["program"], quant="f32", fault="planted"), reference, **wrong.compared())
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


def test_half_of_the_batch_left_out_is_not_correct(sound, reference):
    from perfbench import check

    adapter = sound["adapter"]
    numbers = check.compare(adapter.reference_readings(sound["rows"], sound["program"], fault="half_batch"), reference, **adapter.compared())
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"], numbers


def test_the_configuration_holds_every_published_width():
    from perfbench import harness
    from perfbench.flops_decoder import parameters

    cell = harness.Cell(CELL)
    c, S = cell.config, cell.sizes(False)
    assert (c["hidden_size"], c["head_dim"], c["moe_ffn_hidden_size"], c["moe_num_active_primary_experts"], c["sliding_window_size"], c["rope_theta"]) == (2560, 128, 768, 6, 4096, 1500000)
    assert (S["hidden_size"], S["head_dim"], S["expert_width"], S["experts_per_token"], S["num_experts"], S["window"], S["rope_theta"]) == (2560, 128, 768, 6, 64, 4096, 1.5e6)
    published = {"num_hidden_layers": 52, "moe_num_primary_experts": 64, "num_attention_heads": 28, "num_key_value_heads": 4, "vocab_size": 151936}
    assert {k: c[k] for k in published} == published  # the published keys keep their values; the cut has keys of its own
    held = {"layers": 4, "experts_held": 16, "heads_held": 7, "kv_heads_held": 1, "vocab_held": 37984}
    assert {k: c[k] for k in held} == held == {k: S[k] for k in held}
    assert set(held) | {"env"} == set(c["reduced"]) == set(c["reduced_why"])
    assert 593e6 < parameters(S) < 595e6
    assert "sheeprl_tpu" not in (harness.ROOT / "perfbench/configs/smallthinker21b_1of4_reference.py").read_text().split('"""', 2)[2]
