"""Every data file of the benchmark loads and names things that exist."""

import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DV3_LIMITED = {"loss_gap.world_model", "loss_gap.actor", "loss_gap.critic", "loss_gap.kl", "grad_gap", "grad_gap.median", "grad_gap.transition", "grad_gap.world_model", "change_gap"}


def load(path):
    with open(path) as f:
        return json.load(f)


BENCHMARK = load(ROOT / "BENCHMARK.json")
E2E = {m["name"]: m for m in BENCHMARK["end_to_end"]}
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}


def files(sub):
    return sorted((BENCH / sub).glob("*.json"))


def compared_names(config):
    """The numbers ``check.compare`` makes for a configuration, by what its family's adapter says is compared."""
    from perfbench import harness

    what = harness.resolve(config["adapter"])(config["sizes"], 0, harness.resolve(config["reference"])).compared()
    names = {f"loss_gap.{name}" for name in what["losses"]} | {f"grad_gap.{group}" for group in what["groups"]}
    return names | {"grad_gap", "grad_gap.median", "change_gap"}


DV3_CONFIGS = [c for c in BENCHMARK["configs"] if load(ROOT / c["file"])["adapter"].endswith(":DreamerV3Adapter")]


def test_benchmark_has_exactly_the_contract_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for word in BENCHMARK["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in BENCHMARK["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("entry", BENCHMARK["end_to_end"] + BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(entry):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if entry["name"] in E2E else {"layer", "moves"}
    assert set(entry) <= allowed
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher") and entry["source"] in SOURCES
    if entry["name"] in E2E:
        assert entry["source"] in ("host_clock", "device_trace") and 0.01 <= entry["bound"] <= 0.1
    else:
        assert entry["moves"] in E2E
        assert "\n" not in entry["layer"] and 1 <= len(entry["layer"]) <= 200
        for cell in entry.get("workloads", CELLS):
            moved = E2E[entry["moves"]]
            assert cell in moved.get("workloads", CELLS), f"{cell} does not report {entry['moves']}"
    for cell in entry.get("workloads", []):
        assert cell in CELLS
    spec = load(BENCH / "metrics" / f"{entry['name']}.json")
    for key in ("unit", "better", "source"):
        assert spec[key] == entry[key]
    module, _, attr = spec["reader"].partition(":")
    assert callable(getattr(importlib.import_module(module), attr))


@pytest.mark.parametrize("path", files("metrics"), ids=lambda p: p.stem)
def test_metric_file_is_in_the_benchmark(path):
    spec = load(path)
    assert spec["name"] == path.stem
    assert spec["name"] in E2E or spec["name"] in {m["name"] for m in BENCHMARK["per_layer"]}


@pytest.mark.parametrize("path", files("workloads"), ids=lambda p: p.stem)
def test_workload_file(path):
    w = load(path)
    assert w["name"] == path.stem and NAME.match(w["name"])
    assert (BENCH / "configs" / f"{w['config']}.json").is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    if w["name"] in CELLS:
        assert CELLS[w["name"]] == {k: w[k] for k in ("name", "config", "traffic", "chips", "why")}
    # every limit is of a number the comparison makes, and the state-unchanged fault (a gap of 1) fails
    names = compared_names(load(BENCH / "configs" / f"{w['config']}.json"))
    assert w["limits"] and set(w["limits"]) <= names and w["limits"]["change_gap"] < 1.0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    e2e = [m["name"] for m in BENCHMARK["end_to_end"] if cell in m.get("workloads", CELLS)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in m.get("workloads", CELLS) for m in BENCHMARK["per_layer"])
    assert (BENCH / "workloads" / f"{cell}.json").is_file()


@pytest.mark.parametrize("path", files("traffic"), ids=lambda p: p.stem)
def test_traffic_file(path):
    """A traffic mix is data: it says why and from where, and fills every placeholder of
    the ``traffic_overrides`` of each configuration it is paired with in a cell."""
    from perfbench import harness

    t = load(path)
    assert NAME.match(path.stem) and t["why"] and t["source"] and t["num_envs"] >= 1
    cells = [w for w in CELLS.values() if w["traffic"] == path.stem]
    assert cells, "a traffic mix that no cell uses"
    for w in cells:
        composed = harness.Cell(w["name"]).traffic_overrides(rehearsal=False)
        assert f"env.num_envs={t['num_envs']}" in composed and not [o for o in composed if "{" in o]
    if "replay_ratio" in t:  # a replay family's mix
        assert 0 < t["replay_ratio"] <= 1
    if "frame_blocks" in t:  # the pixel generator's
        assert 64 % t["frame_blocks"] == 0


@pytest.mark.parametrize("entry", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_file_names_what_the_harness_asks_of_a_family(entry):
    """Of any family: the entry agrees with the file, and the file names an adapter with
    every member of ``adapters/base.py``'s protocol, a reference, a flops count, the
    overrides a traffic mix becomes, and rehearsal limits of numbers the comparison makes."""
    from perfbench import harness
    from perfbench.adapters.base import Adapter

    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in BENCHMARK["paths"])
    c = load(ROOT / entry["file"])
    assert c["name"] == entry["name"] and c["source"] == entry["source"] and c["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in c["reduced_why"]
        assert not re.search(r"(_dim$|_rank$|_size$|hidden|units|multiplier)", key), "a width may not be reduced"
    adapter = harness.resolve(c["adapter"])(c["sizes"], 0, harness.resolve(c["reference"]))
    members = [m for m in vars(Adapter) if not m.startswith("_")] + list(Adapter.__annotations__)
    assert [m for m in members if not hasattr(adapter, m)] == []
    assert harness.resolve(c["flops"])(c["sizes"])["total"] > 0
    assert c["traffic_overrides"] and "env.num_envs={num_envs}" in c["traffic_overrides"]
    assert any(o.startswith("env.wrapper._target_=") for o in c["overrides"])
    assert c["rehearsal"]["limits"] and set(c["rehearsal"]["limits"]) <= compared_names(c)


@pytest.mark.parametrize("entry", DV3_CONFIGS, ids=lambda c: c["name"])
def test_dreamer_v3_config_file_states_what_the_program_runs(entry):
    """The sizes the reference reads are the sizes the composed program config holds."""
    from sheeprl_tpu.config.core import compose

    c = load(ROOT / entry["file"])
    assert compared_names(c) == DV3_LIMITED
    cfg = compose(overrides=c["overrides"] + ["env.num_envs=1", "seed=1"])
    S, wm = c["sizes"], cfg.algo.world_model
    assert S["recurrent_state_size"] == wm.recurrent_model.recurrent_state_size
    assert S["dense_units"] == cfg.algo.dense_units == cfg.algo.actor.dense_units == cfg.algo.critic.dense_units
    assert S["mlp_layers"] == cfg.algo.mlp_layers
    assert S["cnn_channels_multiplier"] == wm.encoder.cnn_channels_multiplier
    assert S["transition_hidden_size"] == wm.transition_model.hidden_size
    assert S["representation_hidden_size"] == wm.representation_model.hidden_size
    assert (S["stochastic_size"], S["discrete_size"]) == (wm.stochastic_size, wm.discrete_size)
    assert (S["reward_bins"], S["critic_bins"]) == (wm.reward_model.bins, cfg.algo.critic.bins)
    assert (S["horizon"], S["batch_size"], S["sequence_length"]) == (
        cfg.algo.horizon,
        cfg.algo.per_rank_batch_size,
        cfg.algo.per_rank_sequence_length,
    )
    assert S["gamma"] == cfg.algo.gamma and S["lmbda"] == cfg.algo.lmbda and S["tau"] == cfg.algo.critic.tau
    assert S["ent_coef"] == cfg.algo.actor.ent_coef and S["unimix"] == cfg.algo.unimix
    assert S["kl_free_nats"] == wm.kl_free_nats and S["kl_dynamic"] == wm.kl_dynamic
    for tree, node in (("world_model", wm), ("actor", cfg.algo.actor), ("critic", cfg.algo.critic)):
        o = S["optimizers"][tree]
        assert (o["lr"], o["eps"], o["clip"]) == (node.optimizer.lr, node.optimizer.eps, node.clip_gradients)
    assert S["precision"] == cfg.mesh.precision and S["ring_rows"] == cfg.buffer.size
    assert S["learning_starts"] == cfg.algo.learning_starts and cfg.buffer.device is True
    assert set(c["rehearsal"]["limits"]) == DV3_LIMITED


def test_peaks_table_names_its_source():
    peaks = load(BENCH / "peaks.json")
    assert "source" in peaks and peaks["TPU v5 lite"]["flops_per_s_bf16"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
