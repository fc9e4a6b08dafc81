"""Explicit device placement for child processes (``distributed/chips.py``): the
Sebulba launcher and the serve fleet manager never hand a child the chip by
inheritance, and an over-subscribed topology is refused before any spawn."""

import pytest

from sheeprl_tpu.distributed import chips
from sheeprl_tpu.distributed import launcher
from sheeprl_tpu.distributed.placement import ROLE_ACTOR, ROLE_LEARNER

CHIP_MACHINE_ENV = {"JAX_PLATFORMS": "tpu,cpu", "TPU_TOPOLOGY": "2x2"}  # as the chip host sets it


def test_wants_accelerator_only_an_explicit_cpu_says_no():
    assert chips.wants_accelerator({})  # JAX's default picks the TPU where there is one
    assert chips.wants_accelerator(CHIP_MACHINE_ENV)
    assert not chips.wants_accelerator({"JAX_PLATFORMS": "cpu"})
    assert not chips.wants_accelerator({"JAX_PLATFORMS": " CPU "})


def test_cpu_env_and_accelerator_env_are_explicit():
    cpu = chips.cpu_env(CHIP_MACHINE_ENV)
    assert cpu == {**CHIP_MACHINE_ENV, "JAX_PLATFORMS": "cpu"}  # everything else passes through
    assert "cpu" in chips.describe(cpu)

    whole_host = chips.accelerator_env(CHIP_MACHINE_ENV)
    assert whole_host == CHIP_MACHINE_ENV and whole_host is not CHIP_MACHINE_ENV
    pinned = chips.accelerator_env(CHIP_MACHINE_ENV, chip=2)
    assert pinned["TPU_VISIBLE_CHIPS"] == "2"
    assert pinned["TPU_CHIPS_PER_PROCESS_BOUNDS"] == pinned["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "TPU_VISIBLE_CHIPS=2" in chips.describe(pinned)


def test_chip_budget_refuses_more_holders_than_chips(monkeypatch):
    monkeypatch.setattr(chips, "local_chip_count", lambda: 1)
    assert chips.check_chip_budget(1, "one replica", env=CHIP_MACHINE_ENV) == 1
    with pytest.raises(chips.ChipBudgetError, match="2 chip-holding processes but this host has 1"):
        chips.check_chip_budget(2, "two replicas", env=CHIP_MACHINE_ENV)
    # on the CPU backend, or on a host without TPUs, nothing can be over-subscribed
    assert chips.check_chip_budget(8, "cpu replicas", env={"JAX_PLATFORMS": "cpu"}) == 0
    monkeypatch.setattr(chips, "local_chip_count", lambda: 0)
    assert chips.check_chip_budget(8, "no tpu here", env={}) == 0


def test_sebulba_learner_holds_the_accelerator_and_actors_are_placed_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert launcher.role_env(ROLE_LEARNER)["JAX_PLATFORMS"] == "tpu,cpu"
    assert launcher.role_env(ROLE_ACTOR)["JAX_PLATFORMS"] == "cpu"


def _fleet_manager(tmp_path, max_replicas):
    from sheeprl_tpu.config.core import compose
    from sheeprl_tpu.serve.fleet.manager import FleetManager

    overrides = [
        "serve.fleet.enabled=True",
        f"serve.fleet.dir={tmp_path}",
        "serve.fleet.min_replicas=1",
        f"serve.fleet.max_replicas={max_replicas}",
    ]
    return FleetManager(overrides, compose(config_name="serve_cli", overrides=overrides))


def test_fleet_manager_refuses_more_replicas_than_chips_and_pins_the_rest(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(chips, "local_chip_count", lambda: 1)
    with pytest.raises(chips.ChipBudgetError, match="serve.fleet.max_replicas"):
        _fleet_manager(tmp_path, max_replicas=2)

    single = _fleet_manager(tmp_path, max_replicas=1)  # one chip: the single replica gets it, unpinned
    replica = single._make_slot("replica0", 0, "replica")
    assert "TPU_VISIBLE_CHIPS" not in single._slot_env(replica)
    assert single._slot_env(single._make_slot("front", 0, "front"))["JAX_PLATFORMS"] == "cpu"

    monkeypatch.setattr(chips, "local_chip_count", lambda: 4)
    fleet = _fleet_manager(tmp_path, max_replicas=2)
    env = fleet._slot_env(fleet._make_slot("replica1", 1, "replica"))
    assert env["JAX_PLATFORMS"] == "tpu,cpu" and env["TPU_VISIBLE_CHIPS"] == "1"
