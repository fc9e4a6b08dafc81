"""Persistent XLA compilation cache (``compile_cache.{enabled,dir}`` wired in
``cli.run_algorithm`` — the first slice of ROADMAP item 3's cold-start story).

The warm-vs-cold contract: the first run populates the cache directory with one
serialized executable per compiled program; a second identical run compiles
NOTHING new (every program deserializes), observed as a stable cache-file count.
The wall-clock half of the story is the ``anakin_compile_seconds`` BENCH row
(``benchmarks/anakin_bench.py --compile-bench 1``, two fresh subprocesses).
"""

import json
from pathlib import Path

import jax
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.utils import compile_cache

TINY_ANAKIN = [
    "exp=ppo",
    "env=jax_cartpole",
    "algo.anakin=True",
    "algo.mlp_keys.encoder=[state]",
    "algo.rollout_steps=4",
    "algo.per_rank_batch_size=4",
    "algo.update_epochs=1",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.encoder.mlp_features_dim=8",
    "algo.total_steps=8",
    "algo.run_test=False",
    "env.num_envs=2",
    "env.capture_video=False",
    "dry_run=True",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "metric.log_every=1",
    "buffer.memmap=False",
]


@pytest.fixture
def restore_cache_config():
    """enable_compile_cache edits process-global jax config: put it back."""
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def test_env_var_places_the_cache_and_the_code_sets_no_directory(tmp_path, monkeypatch, restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR wins over compile_cache.dir, and the resolver
    leaves jax_compilation_cache_dir alone (JAX reads the variable itself)."""
    outside, configured = tmp_path / "from_outside", tmp_path / "from_config"
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV_VAR, str(outside))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache({"enabled": True, "dir": str(configured)}) == str(outside)
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache.resolve_cache_dir(str(configured)) == str(outside)
    assert not configured.exists()
    assert compile_cache.empty_cold_start_dir("row").startswith(str(outside))


def test_unset_env_var_means_one_fixed_path_inside_the_checkout(tmp_path, monkeypatch):
    """Never from ~, the working directory, tempfile, a pid or the clock."""
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV_VAR, raising=False)
    repo = Path(__file__).resolve().parents[2]
    expected = str(repo / ".xla_cache")
    assert compile_cache.resolve_cache_dir() == expected
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HOME", str(tmp_path))
    assert compile_cache.resolve_cache_dir() == compile_cache.resolve_cache_dir(None) == expected
    assert ".xla_cache/" in (repo / ".gitignore").read_text().splitlines()
    # an explicit compile_cache.dir is still honoured when nothing outside placed it
    assert compile_cache.resolve_cache_dir(str(tmp_path / "mine")) == str(tmp_path / "mine")


def test_cold_start_dir_is_fixed_and_emptied(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV_VAR, str(tmp_path))
    first = Path(compile_cache.empty_cold_start_dir("serve_startup"))
    assert first == tmp_path / "cold_start" / "serve_startup" and first.is_dir()
    (first / "stale-entry").write_text("x")
    assert Path(compile_cache.empty_cold_start_dir("serve_startup")) == first
    assert list(first.iterdir()) == []


def test_no_code_but_the_resolver_sets_the_cache_dir():
    repo = Path(__file__).resolve().parents[2]
    offenders = [
        str(path.relative_to(repo))
        for root in ("sheeprl_tpu", "benchmarks")
        for path in (repo / root).rglob("*.py")
        if "jax_compilation_cache_dir" in path.read_text()
    ] + [name for name in ("bench.py", "chip_smoke.py") if "jax_compilation_cache_dir" in (repo / name).read_text()]
    assert offenders == ["sheeprl_tpu/utils/compile_cache.py"]


def _cache_files(cache_dir):
    return sorted(p for p in cache_dir.rglob("*") if p.is_file())


def test_compile_cache_cold_then_warm(tmp_path):
    cache_dir = tmp_path / "xla_cache"
    args = TINY_ANAKIN + [
        "compile_cache.enabled=True",
        f"compile_cache.dir={cache_dir}",
    ]
    run(args + [f"log_root={tmp_path / 'run1'}"])
    cold_files = _cache_files(cache_dir)
    assert cold_files, "first (cold) run wrote no cache entries"

    # warm run: every program deserializes — the cache gains nothing new
    run(args + [f"log_root={tmp_path / 'run2'}"])
    warm_files = _cache_files(cache_dir)
    assert [p.name for p in warm_files] == [p.name for p in cold_files], (
        "second run recompiled programs the cache should have served"
    )


def test_compile_cache_disabled_leaves_dir_empty(tmp_path):
    cache_dir = tmp_path / "xla_cache_off"
    run(TINY_ANAKIN + [f"compile_cache.dir={cache_dir}", f"log_root={tmp_path / 'run'}"])
    assert not cache_dir.exists(), "compile_cache.enabled=False must not touch the cache dir"


@pytest.mark.slow
def test_compile_bench_warm_beats_cold():
    """The BENCH row's claim end to end: a fresh process with a warm persistent
    cache reaches its first fused dispatch faster than the cold process that
    filled it (subprocess-heavy — slow tier)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks"))
    try:
        import anakin_bench
    finally:
        sys.path.pop(0)
    res = anakin_bench.bench_compile_cache(num_envs=2, rollout_steps=4)
    assert res["cold_seconds"] > 0 and res["warm_seconds"] > 0
    assert res["warm_seconds"] < res["cold_seconds"], (
        f"warm start ({res['warm_seconds']:.2f}s) did not beat cold ({res['cold_seconds']:.2f}s)"
    )


@pytest.mark.slow
def test_compile_bench_row_shape(capsys):
    """Slow tier (2 subprocess probes): `--compile-bench 1` emits the
    anakin_compile_seconds row (the other rows are covered by
    test_anakin_bench_smoke; the cache behavior itself by the tests above)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "benchmarks"))
    try:
        import anakin_bench
    finally:
        sys.path.pop(0)
    anakin_bench.main(
        ["--num-envs", "4", "--steps", "16", "--host-steps", "8", "--rollout-steps", "4",
         "--ppo-envs", "2", "--iters", "1", "--host-envs", "2", "--skip-population",
         "--pop-envs", "2", "--pop-rollout", "4", "--compile-bench", "1"]
    )
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.strip()]
    by_metric = {r["metric"]: r for r in rows}
    row = by_metric["anakin_compile_seconds"]
    assert row["value"] > 0 and row["cold_seconds"] > 0 and row["warm_speedup"] > 0
