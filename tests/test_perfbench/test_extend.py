"""A later PR adds a configuration, a cell and a per-layer metric by adding files and
entries, without editing a file that is there: the harness finds each by its name.
That holds for a cell of another algorithm family too: ``family_ppo/`` beside this
file is one (the repo's own ``exp=ppo`` at a tiny MLP size: adapter, generator, plain
reference and data files), with no file of it under ``perfbench/``.  It is in no
``BENCHMARK.json`` of the repo: a KB-sized policy cannot meet the memory floor."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FAMILY = Path(__file__).resolve().parent / "family_ppo"
PPO_CELL = "ppo_mlp.rollout"
#: a family's words, which the harness's own modules may not hold
FAMILY_WORDS = ("pixel_env", "world_model", "kl_free_nats", "replay_ratio", "frame_blocks", "n_actions", "ring_rows", "rgb")
#: ``Cell("dv3_XL.crafter").overrides(5, False, Path("cache"), Path("logs"))`` as PR 26's harness composed it
PARENT_OVERRIDES = [
    "exp=dreamer_v3",
    "algo=dreamer_v3_XL",
    "env=discrete_dummy",
    "env.id=perfbench_pixel",
    "env.wrapper._target_=perfbench.envs.pixel_env.PixelEnv",
    "env.sync_env=True",
    "env.capture_video=False",
    "env.reward_as_observation=True",
    "env.screen_size=64",
    "env.action_repeat=1",
    "env.frame_stack=1",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.cnn_keys.decoder=[rgb]",
    "algo.mlp_keys.encoder=[reward]",
    "algo.mlp_keys.decoder=[]",
    "algo.per_rank_batch_size=16",
    "algo.per_rank_sequence_length=64",
    "algo.learning_starts=1024",
    "algo.total_steps=2000000000",
    "algo.run_test=False",
    "mesh.precision=bf16-mixed",
    "buffer.size=500000",
    "buffer.memmap=False",
    "buffer.checkpoint=False",
    "buffer.device=True",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "compile_cache.enabled=True",
    "env.num_envs=1",
    "algo.replay_ratio=0.5",
    "env.wrapper.seed=0",
    "env.wrapper.rank=0",
    "env.wrapper.n_actions=17",
    "env.wrapper.episode_length=250",
    "env.wrapper.reward_scale=0.5",
    "env.wrapper.blocks=8",
    "mesh.devices=1",
    "seed=5",
    "compile_cache.dir=cache",
    "log_root=logs",
    "run_name=run",
]


def data_copy(tmp_path):
    """The benchmark's data files (no code) copied where a test may add to them."""
    bench = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", bench, ignore=shutil.ignore_patterns("__pycache__", "*.py"))
    return bench, json.loads((ROOT / "BENCHMARK.json").read_text())


def test_harness_finds_added_files_by_name(tmp_path, monkeypatch):
    from perfbench import harness

    bench, benchmark = data_copy(tmp_path)

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


@pytest.mark.parametrize("module", ["harness.py", "check.py", "run.py"])
def test_the_harness_names_nothing_of_a_family(module):
    text = (ROOT / "perfbench" / module).read_text()
    assert [w for w in FAMILY_WORDS if w in text] == []


def test_the_cell_that_was_there_gets_the_overrides_it_got():
    from perfbench import harness

    assert harness.Cell("dv3_XL.crafter").overrides(5, False, Path("cache"), Path("logs")) == PARENT_OVERRIDES


def test_a_placeholder_that_nothing_fills_names_the_files(tmp_path):
    from perfbench import harness

    bench, benchmark = data_copy(tmp_path)
    traffic = json.loads((bench / "traffic" / "crafter.json").read_text())
    del traffic["frame_blocks"]
    (bench / "traffic" / "crafter.json").write_text(json.dumps(traffic))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    cell = harness.Cell("dv3_XL.crafter", root=tmp_path, bench=bench)
    with pytest.raises(SystemExit, match=r"dv3_XL\.json asks for 'env\.wrapper\.blocks=\{frame_blocks\}'.*crafter\.json nor .*KeyError\('frame_blocks'\)"):
        cell.overrides(5, False, tmp_path, tmp_path)


@pytest.fixture(scope="module")
def ppo_run(out_dir, tmp_path_factory):
    """The second family's cell driven through the harness on the CPU: its files are
    added to a copy of the benchmark's data, its entries to a copy of ``BENCHMARK.json``."""
    from perfbench import harness

    root = tmp_path_factory.mktemp("second_family")
    bench, benchmark = data_copy(root)
    for sub in ("configs", "traffic", "workloads"):
        for f in (FAMILY / sub).glob("*.json"):
            shutil.copy(f, bench / sub / f.name)
    cell = json.loads((FAMILY / "workloads" / f"{PPO_CELL}.json").read_text())
    benchmark["configs"].append(
        {"name": "ppo_mlp", "source": "a test", "file": "perfbench/configs/ppo_mlp.json", "reduced": [], "why": "a test"}
    )
    benchmark["workloads"].append({k: cell[k] for k in ("name", "config", "traffic", "chips", "why")})
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark))
    sys.path.insert(0, str(FAMILY.parent))
    try:
        yield harness.drive(PPO_CELL, 2147483777, 0.5, False, rehearsal=True, root=root, bench=bench)
    finally:
        sys.path.remove(str(FAMILY.parent))


def test_a_second_family_runs_through_the_harness_by_added_files_alone(ppo_run):
    from perfbench import harness

    assert not [p for p in (ROOT / "perfbench").rglob("*") if "ppo" in p.name.lower()]
    assert ppo_run["cell"].overrides(5, True, Path("cache"), Path("logs"))[-13:-5] == [
        "env.num_envs=4",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=32",
        "env.wrapper.seed=0",
        "env.wrapper.rank=0",
        "env.wrapper.obs_dim=10",
        "env.wrapper.n_actions=5",
        "env.wrapper.episode_length=6",
    ]
    w = ppo_run["window"]
    assert w["grad_steps"] == 4 * w["blocks"] > 0 and w["env_steps"] == 4 * w["iterations"]  # 4 epochs an update, 4 envs
    assert w["compile_requests"] == 0, "something compiled inside the window"
    line = harness.report(ppo_run)
    assert line["correct"] is True, line["compared"]
    assert {"rehearsal.grad_steps_per_s", "rehearsal.env_steps_per_s", "rehearsal.setup_s"} <= set(line["metrics"])
    assert set(line["compared"]) == set(ppo_run["cell"].limits(True))


def test_a_second_family_with_another_clip_coefficient_is_not_correct(ppo_run):
    """The planted fault: the stand-in's reference is given a clip coefficient that the
    program does not use (PPO's default 0.2 against the configuration's 0.02)."""
    from family_ppo.adapter import PPOAdapter
    from perfbench import harness

    wrong = PPOAdapter({**ppo_run["sizes"], "clip_coef": 0.2}, ppo_run["seed"], ppo_run["adapter"].ref)
    judged = harness.judge({**ppo_run, "adapter": wrong})
    assert not judged["correct"], judged["numbers"]
