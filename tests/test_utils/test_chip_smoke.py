"""``chip_smoke.py`` and ``bench.py`` process discipline, checked without a chip:
the smoke refuses to run (or to print a result) off-TPU, and the bench driver
stays off JAX, names the device on every row and fails when a section fails."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_chip_smoke_refuses_a_cpu_backend_and_prints_no_result():
    proc = _run(["chip_smoke.py"], REPO)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "platform=cpu" in proc.stderr  # says what it found ...
    assert proc.stdout == ""  # ... and prints no result


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py", "--cpu-tiny-for-tests"], tmp_path)
    assert proc.returncode != 0
    assert "sheeprl_tpu" in proc.stderr  # the program is not there to drive
    assert proc.stdout == ""


@pytest.mark.slow
def test_chip_smoke_test_switch_runs_every_phase_tiny_on_cpu(tmp_path):
    proc = _run(["chip_smoke.py", "--cpu-tiny-for-tests", "--out", str(tmp_path / "out")], REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    *_, details, last = proc.stdout.splitlines()
    result = json.loads(last)  # the last line is the contract's object and nothing more
    assert set(result) == {"ok", "device"} and set(result["device"]) == {"platform", "kind", "count"}
    assert result["ok"] is True and result["device"]["platform"] == "cpu"
    summary = json.loads(details.removeprefix("chip_smoke: summary "))
    assert summary["grad_steps"] == {"train_host": 8, "train_device": 8}
    assert summary["device"] == result["device"]


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert json.loads(chip_smoke.result_line(True, device)) == {"ok": True, "device": device}
    failed = json.loads(chip_smoke.result_line(False, {**device, "extra": 1}))
    assert failed == {"ok": False, "device": device}


def test_bench_parent_never_imports_jax():
    proc = _run(["-c", "import sys, bench; sys.exit('jax' in sys.modules or 'numpy' in sys.modules)"], REPO)
    assert proc.returncode == 0, proc.stderr


def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_under_test", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_failed_section_fails_the_run_after_the_others_ran(monkeypatch, capsys):
    bench = _load_bench()
    spawned = []

    def fake_spawn(name, env):
        spawned.append((name, env["JAX_PLATFORMS"]))
        return 3 if name == "fault" else 0

    monkeypatch.setattr(bench, "spawn_section", fake_spawn)
    monkeypatch.setenv("BENCH_DROQ", "0")
    assert bench.main([]) == 1
    names = [name for name, _ in spawned]
    assert names == [n for n in bench.SECTIONS if n != "droq"]  # all ran, the skipped one aside
    assert dict(spawned)["ir_audit"] == "cpu"  # the one CPU section is placed there by statement
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert rows == [{"section": "fault", "error": "section exited with code 3"}]

    monkeypatch.setattr(bench, "spawn_section", lambda name, env: 0)
    assert bench.main([]) == 0


def test_bench_section_rows_name_the_device_and_errors_propagate(monkeypatch, capsys):
    bench = _load_bench()

    def boom():
        raise RuntimeError("cost_analysis() reported no flops")

    monkeypatch.setitem(bench.SECTIONS, "fault", (lambda: [{"metric": "m", "value": 1.0}], "BENCH_FAULT"))
    monkeypatch.setitem(bench.SECTIONS, "droq", (boom, "BENCH_DROQ"))
    monkeypatch.setattr("sheeprl_tpu.utils.compile_cache.enable_compile_cache", lambda cfg: "unused")
    assert bench.run_section("fault") == 0
    (row,) = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert row["metric"] == "m" and row["platform"] == "cpu" and row["device_kind"] and row["device_count"] >= 1
    with pytest.raises(RuntimeError, match="no flops"):  # a child's uncaught error is a non-zero exit
        bench.run_section("droq")
