"""The harness end to end on the CPU at a tiny size, told that it is a rehearsal; the
control and the planted faults come out as not correct.

One process drives everything here (module-scoped runs), so the program's jitted
functions compile once.  Nothing in this file is a time or a rate: the CPU's numbers
are printed under ``rehearsal.<name>`` and checked only for being there.
"""

import json

import pytest

CELL = "dv3_XL.crafter"
SECONDS = 1.0
NO_CACHE = []  # the runs share a persistent compile cache in a temporary directory


@pytest.fixture(scope="module")
def sound(out_dir):
    from perfbench import harness

    # a seed above 2**31, as the driver's are
    return harness.drive(CELL, 2147483659, SECONDS, False, rehearsal=True, extra_overrides=NO_CACHE)


def test_result_line_has_exactly_the_contract_keys(sound, capsys):
    from perfbench import harness

    harness.emit(harness.report(sound))
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert list(line)[: len(harness.RESULT_KEYS)] == list(harness.RESULT_KEYS)
    assert set(line) == set(harness.RESULT_KEYS) | {"compared"} and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"  # and so no metric carries a device metric's name
    assert set(line["metrics"]) == {
        "rehearsal.grad_steps_per_s",
        "rehearsal.env_steps_per_s",
        "rehearsal.iter_ms.p95",
        "rehearsal.setup_s",
    }
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_window_counts_all_the_work(sound):
    w = sound["window"]
    assert w["seconds"] >= SECONDS and w["iterations"] == len(w["gaps_s"])
    assert sum(w["gaps_s"]) == pytest.approx(w["seconds"], rel=1e-9)
    assert w["env_steps"] == w["iterations"] and w["grad_steps"] == w["blocks"] > 0  # 1 env, one-step blocks
    assert abs(w["grad_steps"] - 0.5 * w["iterations"]) <= 1  # ratio 0.5
    assert w["rows_written_at_close"] - w["rows_written_at_open"] == w["env_steps"]
    assert w["compile_requests"] == 0, "something compiled inside the window"
    assert w["spans"]["dispatch"]["calls"] == w["blocks"] and w["spans"]["buffer_add"]["calls"] >= w["iterations"]
    assert 0 < w["player_s"] < w["seconds"]
    assert all(0 < ms < 1e3 * w["seconds"] for ms in w["full_collections_ms"])  # usually none in a second


def test_sound_run_is_correct(sound):
    from perfbench import harness

    judged = harness.judge(sound)
    assert judged["correct"], judged["compared"]
    assert set(judged["compared"]) == set(sound["cell"].limits(True))


def test_control_in_lower_precision_is_not_correct(sound):
    """The reference in the program's place, computed in bfloat16 (the nearest precision
    below the float32 this rehearsal states), fails at least one number."""
    from perfbench import check

    adapter, program, rows = sound["adapter"], sound["program"], sound["rows"]
    reference = adapter.reference_readings(rows, program)
    control = adapter.reference_readings(rows, program, quant="bf16")
    limits, what = sound["cell"].limits(True), adapter.compared()
    assert not check.verdict(check.compare(control, reference, **what), limits)["correct"]
    again = adapter.reference_readings(rows, program)
    assert check.verdict(check.compare(again, reference, **what), limits)["correct"]


def faulty_adapter(fault):
    """The configuration's adapter with the timed gradient block broken underneath."""
    import numpy as np

    from perfbench.adapters.dreamer_v3 import DreamerV3Adapter

    class StateUnchanged(DreamerV3Adapter):
        def call_block(self, block, carry, *args):
            _, metrics = block(carry, *args)  # the step runs and its result is dropped
            return carry, metrics

    class HalfBatch(DreamerV3Adapter):
        def call_block(self, block, carry, mirror, envs, starts, *args):
            half = envs.shape[1] // 2  # the second half of the batch never reaches the step
            envs = np.concatenate([envs[:, :half], envs[:, :half]], axis=1)
            starts = np.concatenate([starts[:, :half], starts[:, :half]], axis=1)
            return block(carry, mirror, envs, starts, *args)

    return {"state_unchanged": StateUnchanged, "half_batch": HalfBatch}[fault]


def test_swapped_kl_weights_are_not_correct(sound):
    """The reference in the program's place with ``kl_dynamic`` and ``kl_representation``
    swapped: no loss changes (the two KL terms are equal in value), the transition
    model's gradient is a fifth of what it was, a gap of 0.8 by the measure."""
    from perfbench import check
    from perfbench.adapters.dreamer_v3 import DreamerV3Adapter

    S = sound["sizes"]
    swapped = DreamerV3Adapter({**S, "kl_dynamic": S["kl_representation"], "kl_representation": S["kl_dynamic"]}, sound["seed"], sound["adapter"].ref)
    reference = sound["adapter"].reference_readings(sound["rows"], sound["program"])
    numbers = check.compare(swapped.reference_readings(sound["rows"], sound["program"]), reference, **swapped.compared())
    assert numbers["loss_gap.world_model"] < 1e-6
    assert numbers["grad_gap.transition"] == pytest.approx(0.8, abs=1e-3)
    assert not check.verdict(numbers, sound["cell"].limits(True))["correct"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_planted_fault_in_the_timed_path_is_not_correct(out_dir, fault):
    """The rest of a run driven with the timed path broken underneath."""
    from perfbench import harness

    run = harness.drive(CELL, 12, SECONDS, False, rehearsal=True, adapter_cls=faulty_adapter(fault), extra_overrides=NO_CACHE)
    judged = harness.judge(run)
    assert not judged["correct"], judged["numbers"]
    if fault == "state_unchanged":
        assert judged["numbers"]["change_gap"] == pytest.approx(1.0, abs=1e-3)


def test_the_compared_steps_exercise_the_kl_above_the_free_nats(sound):
    """The benchmark's weights put every state's KL above the free nats, so both KL terms
    carry gradient and no leaf falls under the gradient floor."""
    from perfbench import harness

    seen = sound["adapter"].coverage(harness.judge(sound)["reference"])
    assert min(seen["kl_min"]) > seen["free_nats"]
    assert seen["leaves_under_grad_floor"] == 0 and seen["leaves"] > 100


def test_same_seed_same_inputs(sound, out_dir):
    from perfbench.envs.pixel_env import PixelEnv

    a, b = PixelEnv(seed=2**31 + 7, rank=3), PixelEnv(seed=2**31 + 7, rank=3)
    fa, fb = a.reset()[0]["rgb"], b.reset()[0]["rgb"]
    assert (fa == fb).all() and fa.shape == (3, 64, 64) and fa.std() > 0
    assert a.step(1)[1] == b.step(1)[1]
    assert (PixelEnv(seed=8).reset()[0]["rgb"] != fa).any()
