"""Anakin throughput benchmark: on-device fused-scan env stepping + training vs
the host vector-env path, on the pure-JAX CartPole (ISSUE-6 / ROADMAP item 1).

Three measurements, each a BENCH-style JSON row on stdout (feeds
``benchmarks/bench_compare.py``; all rows are higher-better):

* ``anakin_cartpole_steps_per_sec`` — raw env-steps/s of N vmapped
  :class:`~sheeprl_tpu.envs.jax.cartpole.CartPole` instances auto-reset-stepping
  inside one jitted ``lax.scan`` (random actions drawn in-jit).  Two host
  baselines ride as extras, both stepping gymnasium ``CartPole-v1``:
  ``host_sync_vector_steps_per_sec`` is THE path the training loops pay today —
  the repo's own ``make_vector_env`` ``SyncVectorEnv`` wrapper stack (dict-obs
  coercion, episode statistics, TimeLimit) at the presets' default env count
  (``--host-envs``, default 4) — so ``speedup_vs_host`` is ROADMAP item 1's
  "100-1000x current env throughput" acceptance row; ``host_raw_gym_saturated``
  is bare ``gym.make`` under ``SyncVectorEnv`` at a saturating env count (the
  python step loop plateaus near 90k steps/s on this class of machine no matter
  how many envs — exactly the single-core wall the Anakin mode removes), with
  ``speedup_vs_raw_gym_saturated`` the conservative lower bound;
* ``anakin_ppo_grad_steps_per_sec`` — grad-steps/s of the FULL fused PPO
  iteration (collection scan + GAE + the scanned minibatch update, ONE donated
  dispatch per iteration), with the implied env-steps/s as an extra;
* ``anakin_population_steps_per_sec`` — env-steps/s of the POPULATION PPO
  dispatch (ISSUE-8 / ROADMAP item 4): ``--members`` independent members — each
  with its own params/optimizer/env states/PRNG streams — trained in one
  donated dispatch via the member axis (``engine/population.py``).
  ``per_member_efficiency`` is K-member throughput ÷ (K × single-member
  throughput): 1.0 means K seeds ride for free, 0.5 means K members cost 2×
  one member — the per-dispatch and per-scan-step overheads amortizing across
  the population is exactly Podracer's "multiple agents per chip" win;
* ``anakin_compile_seconds`` — first-dispatch (trace+compile) seconds of the
  fused PPO program in a FRESH subprocess with a persistent XLA compilation
  cache (``compile_cache.{enabled,dir}``): the first run compiles cold and
  fills the cache, the second deserializes — the row's value is the WARM
  seconds (lower-better; ``cold_seconds``/``speedup`` ride as extras).  This is
  ROADMAP item 3's fleet cold-start story measured end to end.

Usage::

    python benchmarks/anakin_bench.py
    python benchmarks/anakin_bench.py --num-envs 64 --steps 4096 --host-steps 512
    python benchmarks/anakin_bench.py --members 16 --pop-envs 16
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

import gymnasium as gym  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from sheeprl_tpu.config.core import compose  # noqa: E402
from sheeprl_tpu.envs.jax import make_jax_env  # noqa: E402
from sheeprl_tpu.parallel.mesh import MeshContext, build_mesh  # noqa: E402


def _time_vector_env(envs, num_envs: int, steps: int, seed: int = 0) -> float:
    envs.reset(seed=seed)
    rng = np.random.default_rng(seed)
    actions = rng.integers(0, 2, (steps, num_envs))
    t0 = time.perf_counter()
    for t in range(steps):
        envs.step(actions[t])
    elapsed = time.perf_counter() - t0
    envs.close()
    return steps * num_envs / elapsed


def bench_host_sync_vector(num_envs: int, steps: int, seed: int = 0) -> float:
    """Env-steps/s of the host path the training loops ACTUALLY pay: gymnasium
    ``CartPole-v1`` through the repo's ``make_vector_env`` ``SyncVectorEnv``
    wrapper stack, with random actions."""
    from sheeprl_tpu.utils.env import make_vector_env

    cfg = compose(
        overrides=[
            "exp=ppo",
            "env=gym",
            "env.id=CartPole-v1",
            "algo.mlp_keys.encoder=[state]",
            f"env.num_envs={num_envs}",
            "env.capture_video=False",
            "env.sync_env=True",
            "buffer.memmap=False",
        ]
    )
    return _time_vector_env(make_vector_env(cfg, seed, 0), num_envs, steps, seed)


def bench_host_raw_gym(num_envs: int, steps: int, seed: int = 0) -> float:
    """Env-steps/s of bare ``gym.make`` under ``SyncVectorEnv`` — no repo
    wrappers, the host python loop's best case."""
    envs = gym.vector.SyncVectorEnv([lambda: gym.make("CartPole-v1") for _ in range(num_envs)])
    return _time_vector_env(envs, num_envs, steps, seed)


def bench_anakin_env_steps(num_envs: int, steps: int, seed: int = 0) -> float:
    """Env-steps/s of the vmapped pure-JAX CartPole auto-reset-stepping inside one
    jitted scan, random actions drawn in-jit (no policy — the raw env ceiling).
    Per-step keys/actions derive in ONE bulk threefry before the scan instead of
    per-step ``split`` chains — same distribution, ~1.5x on CPU where the PRNG
    hashing is a visible fraction of the tiny physics."""
    env = make_jax_env("cartpole")
    params = env.default_params()
    vstep = jax.vmap(env.step_autoreset, in_axes=(None, 0, 0, 0))

    @jax.jit
    def rollout(env_state, key):
        k_act, k_step = jax.random.split(key)
        actions = jax.random.randint(k_act, (steps, num_envs), 0, 2, dtype=jnp.int32)
        step_keys = jax.random.split(k_step, steps * num_envs).reshape(steps, num_envs, 2)

        def step(env_state, x):
            a, ks = x
            env_state, _obs, reward, _done, _info = vstep(params, env_state, a, ks)
            return env_state, reward

        env_state, rewards = jax.lax.scan(step, env_state, (actions, step_keys))
        return env_state, rewards.sum()

    keys = jax.random.split(jax.random.PRNGKey(seed), num_envs)
    env_state, _ = jax.vmap(env.reset, in_axes=(None, 0))(params, keys)
    env_state, total = rollout(env_state, jax.random.PRNGKey(seed + 1))  # warmup/compile
    jax.device_get(total)
    t0 = time.perf_counter()
    env_state, total = rollout(env_state, jax.random.PRNGKey(seed + 2))
    jax.device_get(total)
    elapsed = time.perf_counter() - t0
    return steps * num_envs / elapsed


def bench_anakin_ppo(num_envs: int, rollout_steps: int, iters: int, seed: int = 0) -> Dict[str, float]:
    """Grad-steps/s + env-steps/s of the full fused PPO Anakin iteration (the
    program ``engine/anakin.py`` dispatches per update)."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns
    from sheeprl_tpu.engine.anakin import init_episode_stats, make_ppo_anakin_iteration, reset_envs

    cfg = compose(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            "algo.anakin=True",
            "algo.mlp_keys.encoder=[state]",
            f"env.num_envs={num_envs}",
            f"algo.rollout_steps={rollout_steps}",
            f"algo.per_rank_batch_size={max(rollout_steps * num_envs // 4, 1)}",
            "algo.update_epochs=4",
            "env.capture_video=False",
            "buffer.memmap=False",
        ]
    )
    ctx = MeshContext(mesh=build_mesh(devices=jax.devices()[:1]), precision="fp32", seed=seed)
    env = make_jax_env("cartpole")
    env_params = env.default_params()
    obs_space = gym.spaces.Dict({"state": env.observation_space(env_params)})
    agent, params = build_agent(ctx, env.action_space(env_params), obs_space, cfg)
    fns = PPOTrainFns(ctx, agent, cfg, ["state"], max(iters, 1))
    opt_state = ctx.replicate(fns.opt.init(params))
    iteration = make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, "state")
    dispatch = jax.jit(iteration, donate_argnums=(0,))

    env_state, obs0 = reset_envs(env, env_params, num_envs, jax.random.PRNGKey(seed))
    carry = {
        "params": params,
        "opt_state": opt_state,
        "env_state": env_state,
        "obs": obs0,
        "key": jax.random.PRNGKey(seed + 1),
        "episode_stats": init_episode_stats(num_envs),
    }
    carry, metrics = dispatch(carry, 0.2, 0.0)  # warmup/compile
    jax.device_get(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, metrics = dispatch(carry, 0.2, 0.0)
    jax.device_get(metrics)
    elapsed = time.perf_counter() - t0
    env_steps = iters * rollout_steps * num_envs
    grad_steps = iters * fns.grad_steps_per_update
    return {
        "grad_steps_per_sec": grad_steps / elapsed,
        "env_steps_per_sec": env_steps / elapsed,
    }


def _population_setup(num_envs: int, rollout_steps: int, seed: int):
    """Tiny-net fused PPO iteration + per-member carry builder (shared by the
    population bench and the compile probe).  Small shapes on purpose: the
    population win IS the fixed-overhead amortization, measured where a single
    member underuses the chip."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns
    from sheeprl_tpu.engine.anakin import init_episode_stats, make_ppo_anakin_iteration, reset_envs

    cfg = compose(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            "algo.anakin=True",
            "algo.mlp_keys.encoder=[state]",
            f"env.num_envs={num_envs}",
            f"algo.rollout_steps={rollout_steps}",
            f"algo.per_rank_batch_size={max(rollout_steps * num_envs // 4, 1)}",
            "algo.update_epochs=1",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.encoder.mlp_features_dim=8",
            "env.capture_video=False",
            "buffer.memmap=False",
        ]
    )
    ctx = MeshContext(mesh=build_mesh(devices=jax.devices()[:1]), precision="fp32", seed=seed)
    env = make_jax_env("cartpole")
    env_params = env.default_params()
    obs_space = gym.spaces.Dict({"state": env.observation_space(env_params)})
    agent, params = build_agent(ctx, env.action_space(env_params), obs_space, cfg)
    fns = PPOTrainFns(ctx, agent, cfg, ["state"], 8)
    iteration = make_ppo_anakin_iteration(env, env_params, agent, fns, cfg, "state")

    def member_carry(m: int):
        p = jax.tree.map(jnp.copy, params)
        env_state, obs0 = reset_envs(env, env_params, num_envs, jax.random.fold_in(jax.random.PRNGKey(seed), m))
        return {
            "params": p,
            "opt_state": fns.opt.init(p),
            "env_state": env_state,
            "obs": obs0,
            "key": jax.random.fold_in(jax.random.PRNGKey(seed + 1), m),
            "episode_stats": init_episode_stats(num_envs),
        }

    return iteration, member_carry


def _time_dispatch(dispatch, carry, args, iters: int) -> float:
    carry, metrics = dispatch(carry, *args)  # warmup/compile
    jax.device_get(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, metrics = dispatch(carry, *args)
    jax.device_get(metrics)
    return (time.perf_counter() - t0) / iters


def bench_anakin_population(
    members: int, num_envs: int, rollout_steps: int, iters: int, seed: int = 0
) -> Dict[str, float]:
    """Env-steps/s of the K-member population PPO dispatch vs K × the
    single-member rate (per-member efficiency), both over the default
    bit-exact ``lax.map`` member axis."""
    from sheeprl_tpu.engine.population import population_transform, stack_members

    iteration, member_carry = _population_setup(num_envs, rollout_steps, seed)
    steps = rollout_steps * num_envs

    single = jax.jit(iteration, donate_argnums=(0,))
    t_single = _time_dispatch(single, member_carry(0), (0.2, 0.0), iters)

    stacked = stack_members([member_carry(m) for m in range(members)])
    pop = jax.jit(population_transform(iteration, vectorize=False, n_args=2), donate_argnums=(0,))
    coefs = (jnp.full((members,), 0.2, jnp.float32), jnp.zeros((members,), jnp.float32))
    t_pop = _time_dispatch(pop, stacked, coefs, iters)

    single_sps = steps / t_single
    pop_sps = members * steps / t_pop
    return {
        "pop_steps_per_sec": pop_sps,
        "single_steps_per_sec": single_sps,
        "per_member_efficiency": pop_sps / (members * single_sps),
    }


def _compile_probe(num_envs: int, rollout_steps: int, cache_dir: str) -> None:
    """Child-process half of the compile bench: enable the persistent cache
    through the shared resolver, then time the FIRST dispatch (trace + compile +
    execute) of the fused PPO program and print one JSON line naming the device
    this process measured on."""
    from sheeprl_tpu.parallel.mesh import device_identity
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache({"enabled": True, "dir": cache_dir})
    iteration, member_carry = _population_setup(num_envs, rollout_steps, seed=0)
    dispatch = jax.jit(iteration, donate_argnums=(0,))
    t0 = time.perf_counter()
    carry, metrics = dispatch(member_carry(0), 0.2, 0.0)
    jax.block_until_ready(metrics)
    print(json.dumps({"first_dispatch_seconds": time.perf_counter() - t0, **device_identity()}))


def bench_compile_cache(num_envs: int, rollout_steps: int) -> Dict[str, float]:
    """Cold-vs-warm first-dispatch seconds across two fresh subprocesses, one at
    a time, sharing one persistent XLA compilation cache directory: a fixed
    sub-directory of the resolved cache, emptied first.  The children hold the
    accelerator, so this must run BEFORE the calling process initialises a JAX
    backend (``main`` runs it first)."""
    import subprocess

    from sheeprl_tpu.distributed import chips
    from sheeprl_tpu.utils.compile_cache import empty_cold_start_dir

    cache_dir = empty_cold_start_dir("anakin_compile")
    env = {**chips.accelerator_env(os.environ), "SHEEPRL_TPU_QUIET": "1"}
    rows = []
    for _ in range(2):
        proc = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--compile-probe",
                "--compile-cache-dir", cache_dir,
                "--pop-envs", str(num_envs),
                "--pop-rollout", str(rollout_steps),
            ],
            capture_output=True,
            text=True,
            env=env,
            timeout=600,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"compile probe failed: {proc.stderr[-500:]}")
        rows.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    cold, warm = (float(r.pop("first_dispatch_seconds")) for r in rows)
    return {"cold_seconds": cold, "warm_seconds": warm, "speedup": cold / max(warm, 1e-9), "device": rows[1]}


def main(argv: Optional[List[str]] = None) -> Dict[str, float]:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--num-envs", type=int, default=int(os.environ.get("BENCH_ANAKIN_ENVS", "1024")))
    parser.add_argument("--steps", type=int, default=int(os.environ.get("BENCH_ANAKIN_STEPS", "2048")))
    parser.add_argument("--host-steps", type=int, default=int(os.environ.get("BENCH_ANAKIN_HOST_STEPS", "512")))
    parser.add_argument("--rollout-steps", type=int, default=128)
    parser.add_argument("--ppo-envs", type=int, default=int(os.environ.get("BENCH_ANAKIN_PPO_ENVS", "64")))
    parser.add_argument("--iters", type=int, default=int(os.environ.get("BENCH_ANAKIN_ITERS", "8")))
    parser.add_argument(
        "--host-envs",
        type=int,
        default=4,
        help="env count for the 'current training config' host baseline (the env/default.yaml num_envs)",
    )
    parser.add_argument(
        "--members", type=int, default=int(os.environ.get("BENCH_ANAKIN_MEMBERS", "16")),
        help="population size K for the anakin_population_steps_per_sec row",
    )
    parser.add_argument("--pop-envs", type=int, default=int(os.environ.get("BENCH_ANAKIN_POP_ENVS", "16")))
    parser.add_argument("--pop-rollout", type=int, default=32)
    parser.add_argument("--pop-iters", type=int, default=int(os.environ.get("BENCH_ANAKIN_POP_ITERS", "6")))
    parser.add_argument("--skip-population", action="store_true", help="skip the population row")
    parser.add_argument(
        "--compile-bench", type=int, default=int(os.environ.get("BENCH_ANAKIN_COMPILE", "1")),
        help="1 = emit the anakin_compile_seconds cold-vs-warm row (2 subprocesses); 0 = skip",
    )
    parser.add_argument("--compile-probe", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--compile-cache-dir", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    if args.compile_probe:  # child-process mode of bench_compile_cache
        _compile_probe(args.pop_envs, args.pop_rollout, args.compile_cache_dir)
        return {}

    # First, while this process has not initialised a backend: the two probe
    # children need the chip to themselves.
    cc = bench_compile_cache(args.pop_envs, args.pop_rollout) if args.compile_bench else None

    from sheeprl_tpu.parallel.mesh import device_identity

    identity = device_identity()
    host_sps = bench_host_sync_vector(args.host_envs, args.host_steps)
    raw_envs = min(args.num_envs, 64)  # the python loop saturates long before 64
    host_raw = bench_host_raw_gym(raw_envs, max(args.host_steps // 2, 16))
    anakin_sps = bench_anakin_env_steps(args.num_envs, args.steps)
    rows = [
        {
            "metric": "anakin_cartpole_steps_per_sec",
            "value": round(anakin_sps, 1),
            "unit": f"env_steps/s ({args.num_envs} vmapped jax CartPole in one jitted scan, 1 chip)",
            "host_sync_vector_steps_per_sec": round(host_sps, 1),
            "host_envs": args.host_envs,
            "speedup_vs_host": round(anakin_sps / host_sps, 1),
            "host_raw_gym_saturated_steps_per_sec": round(host_raw, 1),
            "host_raw_gym_envs": raw_envs,
            "speedup_vs_raw_gym_saturated": round(anakin_sps / host_raw, 1),
        }
    ]
    ppo = bench_anakin_ppo(args.ppo_envs, args.rollout_steps, args.iters)
    rows.append(
        {
            "metric": "anakin_ppo_grad_steps_per_sec",
            "value": round(ppo["grad_steps_per_sec"], 1),
            "unit": (
                f"grad_steps/s (fused collect+GAE+update dispatch, {args.ppo_envs} envs x "
                f"{args.rollout_steps} rollout, 1 chip)"
            ),
            "anakin_ppo_env_steps_per_sec": round(ppo["env_steps_per_sec"], 1),
        }
    )
    if not args.skip_population:
        pop = bench_anakin_population(args.members, args.pop_envs, args.pop_rollout, args.pop_iters)
        rows.append(
            {
                "metric": "anakin_population_steps_per_sec",
                "value": round(pop["pop_steps_per_sec"], 1),
                "unit": (
                    f"env_steps/s across all members ({args.members} members x {args.pop_envs} envs x "
                    f"{args.pop_rollout} rollout, fused population PPO dispatch, lax.map member axis, 1 chip)"
                ),
                "members": args.members,
                "single_member_steps_per_sec": round(pop["single_steps_per_sec"], 1),
                # K-member throughput / (K x single-member): 1.0 = K seeds ride free
                "per_member_efficiency": round(pop["per_member_efficiency"], 3),
            }
        )
    rows = [{**row, **identity} for row in rows]
    if cc is not None:
        rows.append(
            {
                "metric": "anakin_compile_seconds",
                "value": round(cc["warm_seconds"], 3),
                "unit": (
                    "seconds to first fused-PPO dispatch in a fresh process with a WARM persistent "
                    "XLA compilation cache (compile_cache.enabled; lower is better)"
                ),
                "cold_seconds": round(cc["cold_seconds"], 3),
                "warm_speedup": round(cc["speedup"], 2),
                **cc["device"],  # as the warm probe child saw it
            }
        )
    for row in rows:
        print(json.dumps(row))
    return {row["metric"]: row["value"] for row in rows}


if __name__ == "__main__":
    main()
