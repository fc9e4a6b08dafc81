"""Sebulba topology throughput: multi-process actor/learner vs the thread path.

Emits BENCH-style JSON rows on stdout (``benchmarks/bench_compare.py`` treats
every ``sebulba_*`` metric as higher-better):

* ``sebulba_env_steps_per_sec`` — steady-state acting throughput of the
  2-actor placement (1-actor and the single-process thread-decoupled baseline
  ride as extras, plus the 2-actor/1-actor ``actor_scaling`` ratio);
* ``sebulba_learner_grad_steps_per_sec`` — steady-state gradient-step rate of
  the Sebulba learner while blocks stream in over the transport.

Method — two different clocks, both chosen so startup variance cannot pollute
the rate:

* **Sebulba** runs once per variant and the rate comes from the learner
  summary's ``grad_step_trace`` (``SHEEPRL_TPU_SEBULBA_SUMMARY``): one
  ``[t, cumulative_grad_steps]`` entry per consumed block, each block carrying
  ``env.num_envs`` env steps.  The rate is measured over the SECOND HALF of
  the trace — steady state, after actor connect/compile and the learner's
  train-fn compile, which otherwise dominate short runs and vary by seconds
  between runs.
* The **thread baseline** has no in-loop clock, so it runs twice and uses the
  whole-process wall delta ``(steps_big - steps_small)/(wall_big -
  wall_small)`` — spawn/JAX-init/compile cancel.  Its loop is fast (~1 ms/step
  at these shapes), so the budgets must be large (``--thread-steps-*``,
  default 512/4096) for the loop delta to rise above run-to-run startup noise;
  at Sebulba-sized budgets the delta is ~10 ms of noise on two ~45 s runs and
  the resulting "rate" is garbage.

Usage::

    python benchmarks/sebulba_bench.py
    python benchmarks/sebulba_bench.py --steps 160 \
        --thread-steps-small 512 --thread-steps-big 4096
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

BASE_OVERRIDES = [
    "exp=sac_decoupled",
    "env=continuous_dummy",
    "algo.mlp_keys.encoder=[state]",
    "algo.hidden_size=8",
    "algo.per_rank_batch_size=8",
    "algo.learning_starts=8",
    "algo.replay_ratio=0.5",
    "algo.run_test=False",
    "buffer.size=4096",
    "dry_run=False",
    "env.num_envs=2",
    "env.sync_env=True",
    "env.capture_video=False",
    "checkpoint.every=100000",
    "checkpoint.save_last=False",
    "metric.log_every=100000",
    "metric.disable_timer=True",
    "buffer.memmap=False",
]


def _child_env(summary: Optional[str] = None) -> Dict[str, str]:
    """The platform is the caller's: the launcher puts the learner on the
    accelerator and the actors on the CPU backend (``distributed/chips.py``), the
    thread baseline is one process on the accelerator.  This driver itself never
    imports JAX, and the children run one topology at a time."""
    env = dict(os.environ)
    env.pop("SHEEPRL_TPU_SEBULBA_SUMMARY", None)
    if summary:
        env["SHEEPRL_TPU_SEBULBA_SUMMARY"] = summary
    return env


def _run_thread(total_steps: int, log_root: str) -> float:
    """Thread-decoupled baseline: returns whole-process wall seconds."""
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, "-m", "sheeprl_tpu", *BASE_OVERRIDES,
         f"algo.total_steps={total_steps}", f"log_root={log_root}"],
        cwd=REPO,
        env=_child_env(),
        check=True,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT,
    )
    return time.perf_counter() - t0


def _run_sebulba(total_steps: int, num_actors: int, log_root: str) -> Dict[str, float]:
    """Sebulba placement: returns the learner summary (wall/env-steps/grad-steps)."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        summary_path = f.name
    try:
        subprocess.run(
            [sys.executable, "-m", "sheeprl_tpu.sebulba", *BASE_OVERRIDES,
             f"algo.total_steps={total_steps}",
             f"log_root={log_root}",
             f"distributed.num_actors={num_actors}",
             "distributed.connect_timeout_s=60"],
            cwd=REPO,
            env=_child_env(summary_path),
            check=True,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        with open(summary_path) as f:
            return json.load(f)
    finally:
        os.unlink(summary_path)


def _rate(steps_small: float, wall_small: float, steps_big: float, wall_big: float) -> float:
    dt = wall_big - wall_small
    return (steps_big - steps_small) / dt if dt > 0 else 0.0


def _steady_rates(summary: Dict[str, float], envs_per_block: int) -> "tuple[float, float]":
    """(env_steps/s, grad_steps/s) over the second half of the block trace.

    ``grad_step_trace`` holds one ``[t, cumulative_grad_steps]`` entry per
    consumed block; each block carries ``envs_per_block`` env steps.  Measuring
    from the trace midpoint discards actor connect + compile and the learner's
    own train compile — the seconds-scale, run-to-run-variable startup that a
    short run's total wall is dominated by."""
    trace = summary["grad_step_trace"]
    if len(trace) < 4:
        return 0.0, 0.0
    k = len(trace) // 2
    (t0, g0), (t1, g1) = trace[k], trace[-1]
    dt = t1 - t0
    if dt <= 0:
        return 0.0, 0.0
    return (len(trace) - 1 - k) * envs_per_block / dt, (g1 - g0) / dt


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=160, help="sebulba variant step budget")
    parser.add_argument("--thread-steps-small", type=int, default=512)
    parser.add_argument("--thread-steps-big", type=int, default=4096)
    args = parser.parse_args(argv)

    tmp = tempfile.mkdtemp(prefix="sebulba_bench_")
    steps, envs_per_block = args.steps, 2  # env.num_envs in BASE_OVERRIDES

    t1, t2 = args.thread_steps_small, args.thread_steps_big
    thread_sps = _rate(t1, _run_thread(t1, f"{tmp}/t1"), t2, _run_thread(t2, f"{tmp}/t2"))

    one = _run_sebulba(steps, 1, f"{tmp}/a1")
    one_sps, _ = _steady_rates(one, envs_per_block)

    two = _run_sebulba(steps, 2, f"{tmp}/a2")
    two_sps, two_gsps = _steady_rates(two, envs_per_block)

    print(json.dumps({
        "metric": "sebulba_learner_grad_steps_per_sec",
        "value": round(two_gsps, 3),
        "unit": f"grad_steps/s (sebulba learner, 2 actor processes, batch 8, {steps} steps, steady-state)",
        "xfer_bytes_received": int(two["bytes_received"]),
        "xfer_bytes_published": int(two["bytes_published"]),
        "publishes": int(two["publishes"]),
        **two["device"],  # as the learner that measured it saw it
    }))
    print(json.dumps({
        "metric": "sebulba_env_steps_per_sec",
        "value": round(two_sps, 3),
        "unit": f"env_steps/s (2 actor processes x 2 envs, dummy env, {steps} steps, steady-state)",
        "one_actor_env_steps_per_sec": round(one_sps, 3),
        "thread_decoupled_env_steps_per_sec": round(thread_sps, 3),
        "actor_scaling_2x_over_1x": round(two_sps / one_sps, 3) if one_sps > 0 else None,
        "speedup_vs_thread_decoupled": round(two_sps / thread_sps, 3) if thread_sps > 0 else None,
        "actors_platform": "cpu",  # the launcher's stated placement
        **two["device"],
    }))

    # Fleet-exporter overhead rides along (BENCH_OBS=0 skips it): the telemetry
    # plane's ≤2% step-time budget, measured against a live loopback aggregator.
    if os.environ.get("BENCH_OBS", "1") != "0":
        from obs_overhead_bench import run_bench as _obs_run_bench

        print(json.dumps(_obs_run_bench()))

    # Race-detector overhead rides along too (BENCH_RACE=0 skips it): the
    # jaxlint-threads runtime half instrumented over a producer/consumer
    # queue workload, detector-on vs detector-off.
    if os.environ.get("BENCH_RACE", "1") != "0":
        from race_detect_bench import run_bench as _race_run_bench

        print(json.dumps(_race_run_bench()))


if __name__ == "__main__":
    main()
