"""CLI-level observability glue: obs config validation."""

import pytest

import sheeprl_tpu.algos  # noqa: F401  (fills the registry check_configs reads; no other file is counted on to)
from sheeprl_tpu.cli import check_configs
from sheeprl_tpu.config.core import compose


def _ppo_cfg(*overrides):
    return compose(overrides=["exp=ppo_dummy", *overrides])


def test_check_configs_accepts_valid_capture_window():
    check_configs(_ppo_cfg("obs.capture_steps=[2,5]"))
    check_configs(_ppo_cfg())  # null window


@pytest.mark.parametrize("window", ["[5,2]", "[0,3]", "[3]"])
def test_check_configs_rejects_bad_capture_window(window):
    with pytest.raises(ValueError, match="capture_steps"):
        check_configs(_ppo_cfg(f"obs.capture_steps={window}"))


def test_obs_config_group_defaults():
    cfg = _ppo_cfg()
    assert cfg.obs.enabled is False
    assert cfg.obs.trace is True
    assert cfg.obs.capture_steps is None
    assert cfg.obs.warmup_updates == 1
