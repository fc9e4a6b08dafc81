"""Benchmark driver: every section in its own process, one at a time, on the chip.

``python bench.py`` is a parent that NEVER imports JAX: a process that has
touched JAX holds the chip, and a child that needs it then fails or hangs.  Each
section below runs as ``python bench.py --section <name>`` — a child that owns
the device while it measures — and every row it prints carries ``platform``,
``device_kind`` and ``device_count`` as JAX reported them in that child.  A
section that raises prints an ``error`` row, the remaining sections still run,
and the exit code is non-zero.  Nothing falls back to the CPU: the one section
that runs there (``ir_audit``, a static-analysis wall-clock) is placed on it
explicitly and its row says ``"platform": "cpu"``.

The children run with the persistent compile cache on
(``sheeprl_tpu/utils/compile_cache.py`` decides where: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.xla_cache``) and report the directory and their
hit/miss counts on stderr.

Sections (skip one with its ``BENCH_*=0`` switch — ``BENCH_IR``, ``BENCH_ANAKIN``,
``BENCH_PRECISION``, ``BENCH_FAULT``, ``BENCH_PERF``, ``BENCH_DROQ``, ``BENCH_E2E``):

* ``ir_audit``              wall-clock of the full ``jaxlint-ir`` audit (CPU tool).
* ``anakin``                fused-scan env-steps/s, fused PPO, population dispatch.
* ``precision``             bf16 fused-PPO vs f32, int8 serve vs f32 (in-process servers).
* ``fault``                 integrity-checked checkpoint save / verified restore.
* ``perf_overhead``         cost of the perf-attribution plane.
* ``droq``                  DroQ UTD-20 fused ring block.
* ``dreamer_v3_e2e``        the REAL DreamerV3-S training loop through the CLI on the
                            dummy env (env stepping + HBM replay + training + logging)
                            at replay ratio 1 and 4.
* ``dreamer_v3_train_step`` the jitted DreamerV3-S train step on synthetic
                            Atari-100K-shaped data — batch 16 x sequence 64 x 64x64x3,
                            with an MFU from XLA's cost analysis.  Printed LAST: the
                            collector reads the last JSON line as the headline.

The multi-process rows (Sebulba, serve, fleet, the Anakin cold/warm compile row)
are not sections: their drivers in ``benchmarks/`` spawn several JAX processes
and are run standalone until the benchmark is rebuilt as cells (ROADMAP S0).

Baseline (GPU-anchored, BASELINE.md "North-star anchor"): the reference reports 14 h on
1x RTX 3080 for Atari MsPacman-100K (README.md:46-53).  Its exp config
(``configs/exp/dreamer_v3_100k_ms_pacman.yaml``: ``total_steps=100000``,
``learning_starts=1024``, DV3 default ``replay_ratio: 1``) + the Ratio call at
``dreamer_v3.py:661-662`` give ~ 1.0 x (100000 - 1024) ~ 98,976 gradient steps in
14 h => **1.963 grad-steps/s end-to-end** on the 1-GPU baseline, at the same batch
16 x seq 64 x size S.  ``vs_baseline`` on the e2e row is measured_e2e / 1.963.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

REPO = os.path.dirname(os.path.abspath(__file__))

# Reference 1-GPU end-to-end rate derived from its published Atari MsPacman-100K
# wall-clock (docstring above): ~98,976 gradient steps / 14 h on 1x RTX 3080.
BASELINE_E2E_GRAD_STEPS_PER_SEC = 1.963


def bench_train_only(size: str = "S", batch: int = 16):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config.core import compose
    from sheeprl_tpu.obs import perf as obs_perf
    from sheeprl_tpu.parallel.mesh import MeshContext, build_mesh

    import gymnasium as gym

    cfg = compose(
        overrides=[
            "exp=dreamer_v3",
            f"algo=dreamer_v3_{size}",
            f"algo.per_rank_batch_size={batch}",
            "algo.per_rank_sequence_length=64",
        ]
    )
    cfg.algo.cnn_keys.encoder = ["rgb"]
    cfg.algo.mlp_keys.encoder = []

    ctx = MeshContext(mesh=build_mesh(devices=jax.devices()[:1]), precision="bf16-mixed", seed=0)

    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (6,)
    world_model, actor, critic, params, _ = build_agent(ctx, actions_dim, False, cfg, obs_space)
    train_step, init_opt_states = make_train_step(world_model, actor, critic, cfg, ["rgb"], [], {})
    opt_states = init_opt_states(params)
    moments = init_moments()

    T, B = cfg.algo.per_rank_sequence_length, cfg.algo.per_rank_batch_size
    rng = np.random.default_rng(0)
    data = {
        "rgb": jnp.asarray(rng.integers(0, 255, (T, B, 3, 64, 64), dtype=np.uint8)),
        "actions": jnp.asarray(rng.random((T, B, 6)).astype(np.float32)),
        "rewards": jnp.asarray(rng.random((T, B, 1)).astype(np.float32)),
        "terminated": jnp.zeros((T, B, 1), jnp.float32),
        "is_first": jnp.zeros((T, B, 1), jnp.float32),
    }

    train_jit = jax.jit(train_step)
    key = jax.random.PRNGKey(0)
    update_target = jnp.asarray(True)

    # FLOPs of one compiled step (XLA's own estimate) for the MFU figure — the
    # same ``analyze_compiled`` the in-run perf plane uses, so this bench and
    # ``Perf/mfu`` agree by construction.  No estimate is an error, not MFU 0.
    compiled = train_jit.lower(params, opt_states, moments, data, key, update_target).compile()
    flops_per_step, _ = obs_perf.analyze_compiled(compiled)
    if flops_per_step <= 0:
        raise RuntimeError("XLA cost_analysis() reported no flops for the DreamerV3 train step")

    metrics = None
    for _ in range(5):  # warm-up: compile + a few steps
        key, sub = jax.random.split(key)
        params, opt_states, moments, metrics = train_jit(params, opt_states, moments, data, sub, update_target)
    jax.block_until_ready(metrics)

    n_steps = int(os.environ.get("BENCH_STEPS", "30"))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        key, sub = jax.random.split(key)
        params, opt_states, moments, metrics = train_jit(params, opt_states, moments, data, sub, update_target)
    jax.block_until_ready(metrics)  # the last metrics depend on the whole step chain
    elapsed = time.perf_counter() - t0

    gsps = n_steps / elapsed
    return gsps, obs_perf.mfu_from_flops(flops_per_step, gsps, jax.devices()[0])


def bench_e2e(replay_ratio: int = 1, total_steps: Optional[int] = None, prefix: str = "") -> dict:
    """Real training loop (env + HBM replay + train + logging) on the dummy env.

    ``replay_ratio=4`` is the second point: a 4x-larger gradient block per acting
    step shows how much of the replay-ratio-1 rate is the per-iteration acting
    cost rather than the update."""
    import numpy as np

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.utils.logger import read_scalars

    tmp = tempfile.mkdtemp(prefix="bench_e2e_")
    if total_steps is None:
        total_steps = int(os.environ.get("BENCH_E2E_STEPS", "768"))
    t0 = time.perf_counter()
    try:
        run(
            [
                "exp=dreamer_v3_dummy",
                "algo=dreamer_v3_S",
                "env=discrete_dummy",
                "algo.cnn_keys.encoder=[rgb]",
                "algo.mlp_keys.encoder=[]",
                "env.screen_size=64",
                "env.num_envs=4",
                "env.sync_env=True",
                "env.capture_video=False",
                f"algo.total_steps={total_steps}",
                "algo.learning_starts=256",
                f"algo.replay_ratio={replay_ratio}",
                "algo.per_rank_batch_size=16",
                "algo.per_rank_sequence_length=64",
                "algo.run_test=False",
                "buffer.size=100000",
                "buffer.memmap=False",
                "buffer.checkpoint=False",
                "buffer.device=True",  # HBM-resident replay: index-only sampling
                "checkpoint.every=0",
                "checkpoint.save_last=False",
                # Window of 16 iterations per log: the deferred-metrics design syncs
                # only at the log cadence; log_every=1 would force a drain per
                # iteration and measure the sync overhead instead of the loop.
                "metric.log_every=64",
                "compile_cache.enabled=True",
                f"log_root={tmp}",
            ]
        )
        elapsed = time.perf_counter() - t0
        out = {f"{prefix}e2e_policy_steps_per_sec": round(total_steps / elapsed, 3)}
        runs = sorted(glob.glob(os.path.join(tmp, "**", "version_*"), recursive=True))
        scalars = read_scalars(runs[-1])
        for tag, key in (
            ("Time/sps_train", f"{prefix}e2e_sps_train"),
            ("Time/sps_env_interaction", f"{prefix}e2e_sps_env_interaction"),
        ):
            vals = scalars[tag]
            # steady-state: the first windows are dominated by the one-off jit
            # compile, not by training throughput
            steady = vals[2:] if len(vals) > 4 else vals
            out[key] = round(float(np.mean(steady)), 3)
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _benchmark_module(script: str):
    import importlib

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        return importlib.import_module(script)
    finally:
        sys.path.pop(0)


def _script_rows(script: str, argv: List[str]) -> List[dict]:
    """Run ``benchmarks/<script>.main(argv)`` in this process and collect the JSON
    rows it prints (in-process servers also print ``[serve] ...`` progress lines)."""
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        _benchmark_module(script).main(argv)
    return [json.loads(line) for line in buf.getvalue().splitlines() if line.strip().startswith("{")]


def section_anakin() -> List[dict]:
    """Anakin fused-scan rows (``benchmarks/anakin_bench.py``): on-device jax
    CartPole env-steps/s vs the host ``SyncVectorEnv`` path, the fused PPO
    collect+update grad-steps/s and the K-member population dispatch.  The
    cold/warm compile row needs two more JAX processes and stays standalone."""
    return _script_rows(
        "anakin_bench",
        [
            "--num-envs", os.environ.get("BENCH_ANAKIN_ENVS", "1024"),
            "--iters", os.environ.get("BENCH_ANAKIN_ITERS", "8"),
            "--compile-bench", "0",
        ],
    )


def section_precision() -> List[dict]:
    """Precision-tier rows (``benchmarks/precision_bench.py``): bf16 fused-PPO
    env-steps/s vs f32, int8 serve replies/s vs f32 (in-process servers), and the
    int8 parity stamp's greedy action agreement."""
    return _script_rows(
        "precision_bench",
        [
            "--num-envs", os.environ.get("BENCH_PRECISION_ENVS", "32"),
            "--iters", os.environ.get("BENCH_PRECISION_ITERS", "10"),
            "--clients", os.environ.get("BENCH_PRECISION_CLIENTS", "4"),
        ],
    )


def section_perf_overhead() -> List[dict]:
    """Perf-attribution plane cost rows (``benchmarks/perf_overhead_bench.py``)."""
    return _script_rows("perf_overhead_bench", [])


def section_droq() -> List[dict]:
    """DroQ UTD-20 grad-steps/s over the device-ring fused-block path (HBM
    transition ring + ONE donated dispatch for the 20 critic updates + actor
    update, in-jit index sampling), via ``benchmarks/replay_bench.py`` at DroQ
    walker-ish shapes."""
    args = argparse.Namespace(
        batch=128, hidden=256, obs_dim=17, act_dim=6, utd=20,
        blocks=int(os.environ.get("BENCH_DROQ_BLOCKS", "8")),
    )
    rates = _benchmark_module("replay_bench").bench_sac_family("droq", args)
    return [
        {
            "metric": "droq_utd20_grad_steps_per_sec",
            "value": round(rates["device_ring"], 3),
            "unit": f"grad_steps/s (device ring + fused block, batch {args.batch} x obs "
            f"{args.obs_dim} x hidden {args.hidden}, UTD {args.utd}, 1 chip)",
            "host_block_grad_steps_per_sec": round(rates["host_block"], 3),
            "host_per_step_grad_steps_per_sec": round(rates["host_per_step"], 3),
            "speedup_vs_host_per_step": round(rates["device_ring"] / rates["host_per_step"], 3),
        }
    ]


def section_fault() -> List[dict]:
    """Checkpoint fault-tolerance cost rows: wall-clock of one integrity-checked
    ``CheckpointManager.save`` (fsync + sha256 manifest) and of the matching
    verified restore path (``latest_valid`` discovery + checksum verify +
    deserialize) on a PPO-sized state pytree.  Lower is better — these bound the
    preemption grace window and the supervisor's resume latency."""
    import numpy as np

    from sheeprl_tpu.checkpoint.manager import CheckpointManager

    rng = np.random.default_rng(0)
    # ~64 MB of params/opt-state shaped like a mid-size host-loop checkpoint.
    state = {
        "params": {f"layer_{i}": rng.standard_normal((1024, 1024)).astype(np.float32) for i in range(8)},
        "opt_state": {f"mu_{i}": rng.standard_normal((1024, 1024)).astype(np.float32) for i in range(8)},
        "policy_step": 1024,
        "update": 16,
    }
    tmp = tempfile.mkdtemp(prefix="bench_fault_")
    try:
        manager = CheckpointManager(os.path.join(tmp, "checkpoints"), keep_last=3)
        reps = int(os.environ.get("BENCH_FAULT_REPS", "3"))
        save_times, restore_times = [], []
        for rep in range(reps):
            t0 = time.perf_counter()
            manager.save((rep + 1) * 100, state)
            save_times.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            latest = CheckpointManager.latest_valid(os.path.join(tmp, "checkpoints"))
            CheckpointManager.load(latest, fallback=True)
            restore_times.append(time.perf_counter() - t0)
        mb = sum(a.nbytes for tree in (state["params"], state["opt_state"]) for a in tree.values()) / 2**20
        return [
            {
                "metric": "checkpoint_save_seconds",
                "value": round(float(np.median(save_times)), 4),
                "unit": f"seconds (fsync'd integrity-manifest save, {mb:.0f} MB state, median of {reps})",
            },
            {
                "metric": "resume_restore_seconds",
                "value": round(float(np.median(restore_times)), 4),
                "unit": f"seconds (latest_valid + checksum verify + load, {mb:.0f} MB state, median of {reps})",
            },
        ]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def section_ir_audit() -> List[dict]:
    """Wall-clock of the full ``jaxlint-ir`` audit (``sheeprl_tpu/analysis/ir``):
    AOT-lower + compile + rule-check every entry point's jitted update and both
    Anakin dispatches against ``irbudgets.json``.  The CI ir-audit job runs this
    on every PR, so its runtime is a first-class budget (~120 s on one CPU core).
    A CPU tool by design: the driver places this section's process on the CPU
    backend and the row says so."""
    import contextlib

    from sheeprl_tpu.analysis.ir.__main__ import main as ir_main

    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):  # findings are not rows
        rc = ir_main(["-q"])
    elapsed = time.perf_counter() - t0
    return [
        {
            "metric": "ir_audit_seconds",
            "value": round(elapsed, 2),
            "unit": "seconds (full jaxlint-ir audit: 15 programs lowered+compiled+checked, CPU backend)",
            "exit_code": rc,
            "budget_seconds": 120,
            "within_budget": bool(elapsed < 120),
        }
    ]


def section_dreamer_v3_e2e() -> List[dict]:
    extras = bench_e2e()
    # Second point at replay ratio 4 (see bench_e2e).
    if os.environ.get("BENCH_E2E_R4", "1") != "0":
        extras.update(bench_e2e(replay_ratio=4, total_steps=512, prefix="r4_"))
    vs_baseline = extras["e2e_sps_train"] / BASELINE_E2E_GRAD_STEPS_PER_SEC
    return [
        {
            "metric": "dreamer_v3_S_e2e_grad_steps_per_sec",
            "value": extras.pop("e2e_sps_train"),
            "unit": "grad_steps/s of the whole loop (dummy env, HBM replay, batch 16 x seq 64, "
            "64x64x3 obs, replay ratio 1, 1 chip)",
            # Honest comparison: the reference published only an end-to-end
            # wall-clock, so compare e2e-to-e2e at matched batch/seq/model.
            "vs_baseline": round(vs_baseline, 4),
            "vs_baseline_kind": (
                "e2e_sps_train / reference_GPU_e2e(1.963 = 98976 grad steps / 14h, "
                "MsPacman-100K on 1x RTX 3080, batch 16 x seq 64, size S, replay_ratio 1)"
            ),
            "north_star_met": bool(vs_baseline >= 2.0),
            "north_star": "BASELINE.json: >=2x the reference 1-GPU grad-steps/s at matched batch/seq",
            **extras,
        }
    ]


def section_dreamer_v3_train_step() -> List[dict]:
    gsps, mfu = bench_train_only()
    return [
        {
            "metric": "dreamer_v3_S_grad_steps_per_sec",
            "value": round(gsps, 4),
            "unit": "grad_steps/s (train step only, batch 16 x seq 64, 64x64x3 obs, 1 chip)",
            "mfu": round(mfu, 4),
        }
    ]


#: name -> (function run in the child, BENCH_* switch that skips it; the headline
#: has none).  Order is print order; the DreamerV3 train-step row stays last.
SECTIONS: Dict[str, "tuple[Callable[[], List[dict]], Optional[str]]"] = {
    "ir_audit": (section_ir_audit, "BENCH_IR"),
    "anakin": (section_anakin, "BENCH_ANAKIN"),
    "precision": (section_precision, "BENCH_PRECISION"),
    "fault": (section_fault, "BENCH_FAULT"),
    "perf_overhead": (section_perf_overhead, "BENCH_PERF"),
    "droq": (section_droq, "BENCH_DROQ"),
    "dreamer_v3_e2e": (section_dreamer_v3_e2e, "BENCH_E2E"),
    "dreamer_v3_train_step": (section_dreamer_v3_train_step, None),
}
#: Sections placed on the CPU backend by statement, not by fallback.
CPU_SECTIONS = frozenset({"ir_audit"})


def run_section(name: str) -> int:
    """Child mode: this process owns the device.  Rows go to stdout, each naming
    the device JAX reports HERE; the compile-cache report goes to stderr."""
    from sheeprl_tpu.parallel.mesh import device_identity
    from sheeprl_tpu.utils.compile_cache import CacheStats, enable_compile_cache

    cache_dir = enable_compile_cache({"enabled": True})
    stats = CacheStats()
    identity = device_identity()
    try:
        for row in SECTIONS[name][0]():
            print(json.dumps({**row, **identity}), flush=True)
    finally:
        print(f"bench[{name}]: compile cache {cache_dir} {stats.snapshot()}", file=sys.stderr, flush=True)
        stats.close()
    return 0


def spawn_section(name: str, env: Dict[str, str]) -> int:
    """Run one section as its own process (stdout inherited: its rows are ours)."""
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--section", name], env=env, cwd=REPO
    ).returncode


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--section", choices=sorted(SECTIONS), help="run ONE section in this process (child mode)")
    args = parser.parse_args(argv)
    if args.section:
        return run_section(args.section)

    from sheeprl_tpu.distributed import chips  # stdlib only: the parent stays off JAX

    failed = []
    for name, (_, switch) in SECTIONS.items():
        if switch and os.environ.get(switch, "1") == "0":
            continue
        env = chips.cpu_env(os.environ) if name in CPU_SECTIONS else chips.accelerator_env(os.environ)
        print(f"bench: section {name} on {chips.describe(env)}", file=sys.stderr, flush=True)
        rc = spawn_section(name, env)
        if rc != 0:
            failed.append(name)
            print(json.dumps({"section": name, "error": f"section exited with code {rc}"}), flush=True)
    if failed:
        print(f"bench: FAILED sections: {', '.join(failed)}", file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
