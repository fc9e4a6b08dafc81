"""The reduction from a capture to device metrics, on recorded files kept here.

``synthetic.xplane.pb`` was written by hand (an XSpace with one host plane and one
device plane) so that every number below is known exactly: ops at 1.0-3.0 (fusion),
2.5-4.0 (convolution), 5.0-8.0 (while), 5.5-6.0 (fusion, inside the while), 9.0-9.5
(copy) and 0.5-0.9 ms (fusion, before the span); module executions of ``jit_block`` at
1.0-4.0, 5.0-8.0 and 10.5-13.5 ms and of ``jit_player_step`` at 9.0-9.5 ms; the host's
``perfbench_anchor`` annotation begins at 1.0 ms.  ``chip.xplane.pb``, where present, is a capture of a
TPU v5e kept to pin the names the real profiler writes.
"""

from pathlib import Path

import pytest

from perfbench.readers import basic, xplane

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def synthetic():
    return xplane.load(DATA / "synthetic.xplane.pb")


def device_plane(pd):
    return [p for p in pd.planes if xplane.is_device_plane(p.name)][0]


def test_busy_union_and_idle_share(synthetic):
    s = xplane.device_summary(device_plane(synthetic), (0.001, 0.011))
    assert s["busy_s"] == pytest.approx(0.0065, rel=1e-9)  # overlaps counted once
    assert s["window_s"] == pytest.approx(0.010, rel=1e-9)
    assert s["idle_share"] == pytest.approx(0.35, rel=1e-9)
    assert [round(b - a, 6) for a, b in s["gaps"]] == [0.001, 0.001, 0.0015]


def test_per_kind_sums_are_clipped_to_the_span(synthetic):
    kinds = xplane.device_summary(device_plane(synthetic), (0.001, 0.011))["kinds"]
    assert kinds["fusion"] == pytest.approx(0.002)  # the 0.4 ms before the span and the 0.5 ms inside the loop are out
    assert kinds["convolution"] == pytest.approx(0.0015)  # overlapping, not nested: counted
    assert kinds["while.7"] == pytest.approx(0.003)  # a loop is named as the trace prints it
    assert kinds["copy"] == pytest.approx(0.0005)
    assert sum(kinds.values()) == pytest.approx(0.007)


def test_module_events_count_whole_executions_only(synthetic):
    mods = xplane.device_summary(device_plane(synthetic), (0.001, 0.011))["modules"]
    assert mods["jit_block"] == {"seconds": pytest.approx(0.006), "count": 2}
    assert mods["jit_player_step"]["count"] == 1


def test_host_anchor_and_gap_labels(synthetic):
    assert xplane.host_anchor(synthetic) == pytest.approx(0.001)
    host = [("env_step", 0.0041, 0.0049), ("dispatch", 0.0080, 0.0083)]
    assert xplane.label_gap((0.004, 0.005), host) == "env_step"
    assert xplane.label_gap((0.008, 0.009), host) == "dispatch+other"
    assert xplane.label_gap((0.0095, 0.011), host) == "other host work"


def test_summarize_ties_the_host_clock_to_the_capture(tmp_path):
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes((DATA / "synthetic.xplane.pb").read_bytes())
    # perf_counter read 100.0 when start_trace returned and 100.010 when the capture was stopped
    info = {"t_started": 100.0, "t1": 100.010, "grad_steps": 2}
    out = xplane.summarize(tmp_path, info, [("env_step", 100.0031, 100.0039)])
    assert out["anchored"] and out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.0065) and out["idle_share"] == pytest.approx(0.35)
    assert out["breakdown"]["device_ops"][0] == ["while.7", pytest.approx(0.003)]
    assert ["env_step", pytest.approx(0.001)] in out["breakdown"]["idle_gaps"]
    run = {"trace": out, "window": {"blocks": 10, "grad_steps": 10}}
    assert basic.train_step_device_ms(run) == pytest.approx(3.0)
    assert basic.device_idle_share(run) == pytest.approx(35.0)


@pytest.mark.parametrize(
    "name,kind",
    [
        ("%fusion.123 = bf16[16,64]{1,0} fusion(...)", "fusion"),
        ("fusion.7", "fusion"),
        ("jit_block(5822021)", "jit_block"),
        ("copy-start.3", "copy-start"),
        ("while", "while"),
        ("convolution_transpose_fusion.12", "convolution_transpose_fusion"),
    ],
)
def test_op_kind_drops_the_numbering(name, kind):
    assert xplane.op_kind(name) == kind


def test_a_reader_that_finds_nothing_returns_nothing():
    run = {"trace": None, "window": {"blocks": 0, "grad_steps": 0, "seconds": 1.0, "env_steps": 0, "gaps_s": []}}
    assert basic.train_step_device_ms(run) is None and basic.device_idle_share(run) is None
    assert basic.grad_steps_per_s(run) is None and basic.iter_ms_p95(run) is None


def test_chip_capture_names():
    path = DATA / "chip.xplane.pb"
    if not path.is_file():
        pytest.skip("no chip capture kept")
    pd = xplane.load(path)
    plane = device_plane(pd)
    s = xplane.device_summary(plane)
    assert plane.name.startswith("/device:TPU:") and s["op_events"] > 0
    assert 0 < s["busy_s"] <= s["window_s"] and s["modules"]["jit_f"]["count"] == 4
    assert sum(s["kinds"].values()) == pytest.approx(s["busy_s"]) and "while" in s["kinds"]
    anchor = xplane.host_anchor(pd)  # the harness's annotation is found in a real capture
    assert anchor is not None and anchor < s["span"][0]
