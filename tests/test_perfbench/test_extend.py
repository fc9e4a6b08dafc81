"""A later PR adds a configuration, a cell and a per-layer metric by adding files and
entries, without editing a file that is there: the harness finds each by its name."""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_harness_finds_added_files_by_name(tmp_path, monkeypatch):
    from perfbench import harness

    bench = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", bench, ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())

    config = json.loads((bench / "configs" / "dv3_XL.json").read_text())
    config["name"] = "dv3_dummy"
    config["sizes"]["dense_units"] = 640
    (bench / "configs" / "dv3_dummy.json").write_text(json.dumps(config))
    (bench / "traffic" / "dummy_mix.json").write_text(
        json.dumps({"num_envs": 2, "replay_ratio": 0.25, "episode_length": 100, "reward_scale": 1.0, "frame_blocks": 4})
    )
    cell = {"name": "dv3_dummy.dummy_mix", "config": "dv3_dummy", "traffic": "dummy_mix", "chips": 1, "why": "a test"}
    (bench / "workloads" / "dv3_dummy.dummy_mix.json").write_text(json.dumps({**cell, "limits": {"change_gap": 0.5}}))
    (tmp_path / "dummy_reader.py").write_text("def answer(run):\n    return 42.0 if run else None\n")
    metric = {"name": "dummy_metric", "unit": "count", "better": "higher", "source": "program_counter"}
    (bench / "metrics" / "dummy_metric.json").write_text(
        json.dumps({**metric, "layer": "L9 test", "moves": "setup_s", "reader": "dummy_reader:answer"})
    )
    benchmark["configs"].append(
        {"name": "dv3_dummy", "source": "a paper", "file": "perfbench/configs/dv3_dummy.json", "reduced": [], "why": "a test"}
    )
    benchmark["workloads"].append(cell)
    benchmark["per_layer"].append({**metric, "layer": "L9 test", "moves": "setup_s", "workloads": [cell["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    monkeypatch.syspath_prepend(str(tmp_path))

    found = harness.Cell("dv3_dummy.dummy_mix", root=tmp_path, bench=bench)
    assert found.config["name"] == "dv3_dummy" and found.sizes(False)["dense_units"] == 640
    assert found.traffic["num_envs"] == 2 and found.limits(False) == {"change_gap": 0.5}
    overrides = found.overrides(5, False, tmp_path / "cache", tmp_path / "logs")
    assert "env.num_envs=2" in overrides and "algo.replay_ratio=0.25" in overrides and "seed=5" in overrides
    layer = {m["name"]: m for m in found.metrics("per_layer")}
    assert "dummy_metric" in layer and "hbm_peak_gib" not in layer
    assert harness.resolve(layer["dummy_metric"]["reader"])({"x": 1}) == 42.0
    assert "iter_ms.p95" not in {m["name"] for m in found.metrics("end_to_end")}
    # the cells that were there are untouched by the additions
    old = harness.Cell("dv3_XL.crafter", root=tmp_path, bench=bench)
    assert "dummy_metric" not in {m["name"] for m in old.metrics("per_layer")}
    sys.modules.pop("dummy_reader", None)
