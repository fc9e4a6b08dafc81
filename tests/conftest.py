"""Test harness: force an 8-device virtual CPU mesh before JAX initialises.

Mirrors the reference's multi-device CI trick (LT_DEVICES with gloo on localhost,
``tests/test_algos/test_algos.py:16-18``) using
``--xla_force_host_platform_device_count`` per SURVEY §4's TPU-build implication.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _race_detector_session():
    """jaxlint-threads runtime half under pytest: when the CI job exports
    ``SHEEPRL_TPU_RACE_DETECT=1`` (the concurrency suites — test_distributed /
    test_serve / test_obs — run once this way), every lock the tests create is
    instrumented; the session ends by dumping the JSONL race report into
    ``$SHEEPRL_TPU_RACE_DIR`` (default: the launch directory) where the CI step
    asserts zero lock-order cycles.  A no-op without the env var."""
    if os.environ.get("SHEEPRL_TPU_RACE_DETECT", "0") in ("", "0"):
        yield
        return
    from sheeprl_tpu.analysis.threads import runtime as race_runtime

    detector = race_runtime.RaceDetector(
        log_dir=os.environ.get("SHEEPRL_TPU_RACE_DIR") or os.getcwd(),
        held_threshold_ms=float(os.environ.get("SHEEPRL_TPU_RACE_HOLD_MS", "500")),
    )
    race_runtime.install(detector)
    try:
        yield
    finally:
        race_runtime.uninstall()
        path = detector.dump("pytest-session")
        counts = detector.counts()
        print(f"\nrace detector: {counts} -> {path}")


@pytest.fixture()
def tmp_logs(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    return tmp_path


@pytest.fixture(autouse=True)
def no_env_var_leaks():
    """Reference test strategy (``tests/conftest.py:26-60``): a test that mutates the
    framework's environment knobs without cleaning up poisons every test after it —
    fail loudly on the offender instead.  Scoped to the prefixes the framework reads
    (libraries set unrelated vars as import side effects; that's not a leak), minus
    the keys the harness itself manages."""
    exempt = {"XLA_FLAGS", "JAX_PLATFORMS", "SHEEPRL_TPU_QUIET"}
    prefixes = ("SHEEPRL", "MLFLOW", "JAX_", "XLA_")

    def snapshot():
        return {
            k: v
            for k, v in os.environ.items()
            if k.startswith(prefixes) and k not in exempt
        }

    before = snapshot()
    yield
    after = snapshot()
    added = set(after) - set(before)
    removed = set(before) - set(after)
    changed = {k for k in set(before) & set(after) if before[k] != after[k]}
    assert not (added or removed or changed), (
        f"test leaked environment variables: added={sorted(added)} "
        f"removed={sorted(removed)} changed={sorted(changed)}"
    )
