"""The command's exits: no TPU, no result; nothing but the benchmark, no result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
ARGS = ["--workload", BENCHMARK["workloads"][0]["name"], "--seed", "3", "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    return subprocess.run(
        [sys.executable, *BENCHMARK["command"][1:], *ARGS], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def test_without_a_tpu_the_real_path_exits_non_zero_and_prints_no_result():
    proc = run(ROOT)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_alone_in_a_directory_it_exits_non_zero_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCHMARK["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
