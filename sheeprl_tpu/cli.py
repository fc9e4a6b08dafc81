"""CLI entry points (reference: ``/root/reference/sheeprl/cli.py``).

``python -m sheeprl_tpu exp=dreamer_v3 env=atari algo.learning_rate=1e-4`` composes the
config tree, dispatches to the registered algorithm entrypoint and runs it under a
device-mesh context.  There is no process-per-device launch (the reference's
``fabric.launch``, ``cli.py:199``): JAX is single-controller, one process per *host*,
with all local devices driven through the mesh.
"""

from __future__ import annotations

import datetime
import importlib
import os
import sys
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional

from sheeprl_tpu.config.core import DotDict, compose, load_config, print_config, save_config
from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry, get_algorithm, get_evaluation
from sheeprl_tpu.utils.timer import timer


def _import_algorithms() -> None:
    """Populate the registries (reference imports every algo in ``sheeprl/__init__.py:18-47``)."""
    import sheeprl_tpu.algos  # noqa: F401  (registers everything on import)


def resume_from_checkpoint(cfg: DotDict) -> DotDict:
    """Merge the checkpoint run's config, protecting training-critical keys
    (reference ``cli.py:23-58``)."""
    ckpt_path = Path(cfg.checkpoint.resume_from)
    run_dir = ckpt_path.parent.parent if ckpt_path.is_dir() else ckpt_path.parent
    old_cfg_path = run_dir / "config.yaml"
    if not old_cfg_path.is_file():
        old_cfg_path = ckpt_path.parent / "config.yaml"
    if not old_cfg_path.is_file():
        raise FileNotFoundError(
            f"Cannot resume from {ckpt_path}: no config.yaml found alongside the checkpoint"
        )
    old_cfg = load_config(old_cfg_path)
    for key in ("env", "algo", "buffer", "distribution", "exp_name", "seed"):
        if key in old_cfg:
            cfg[key] = old_cfg[key]
    cfg.checkpoint.resume_from = str(ckpt_path)
    return cfg


def check_configs(cfg: DotDict) -> None:
    """Config validation (reference ``cli.py:271-345``)."""
    algo = cfg.get("algo", {})
    if not algo or "name" not in algo:
        raise ValueError("No algorithm selected: choose one with 'exp=<preset>' or 'algo=<name>'")
    entry = get_algorithm(algo["name"])
    decoupled = entry["decoupled"]
    if decoupled and cfg.env.get("sync_env", False) is False and cfg.env.num_envs <= 0:
        raise ValueError("Decoupled algorithms need at least one environment")
    cnn_keys = algo.get("cnn_keys", {}).get("encoder", [])
    mlp_keys = algo.get("mlp_keys", {}).get("encoder", [])
    if not isinstance(cnn_keys, list) or not isinstance(mlp_keys, list):
        raise ValueError("algo.cnn_keys.encoder and algo.mlp_keys.encoder must be lists")
    if cfg.metric.get("log_level", 1) not in (0, 1):
        raise ValueError(f"Invalid metric.log_level: {cfg.metric.log_level}")
    capture = cfg.get("obs", {}).get("capture_steps")
    if capture is not None:
        if not (isinstance(capture, (list, tuple)) and len(capture) == 2):
            raise ValueError(f"obs.capture_steps must be [start_update, end_update]; got {capture!r}")
        start, end = int(capture[0]), int(capture[1])
        if start < 1 or end < start:
            raise ValueError(
                f"obs.capture_steps window must satisfy 1 <= start <= end; got [{start}, {end}]"
            )
    # DV1/DV2 (and their P2E variants) pin the decoder geometry to 64×64 single-frame
    # (reference dreamer_v2.py:399-400).  Validate instead of silently overwriting the
    # user's config, so the saved config.yaml never contradicts the CLI.
    if str(algo.get("name", "")).startswith(("dreamer_v1", "dreamer_v2", "p2e_dv1", "p2e_dv2")) and cnn_keys:
        if int(cfg.env.get("screen_size") or 64) != 64 or int(cfg.env.get("frame_stack") or 1) > 1:
            raise ValueError(
                f"{algo['name']} pixel observations require env.screen_size=64 and "
                f"env.frame_stack<=1 (the decoder geometry is pinned to one 64x64 frame); "
                f"got screen_size={cfg.env.get('screen_size')}, "
                f"frame_stack={cfg.env.get('frame_stack')}."
            )
    # Sequence-sampling algorithms: the prefill must leave every env's sub-buffer with
    # at least one full sequence, or the first train iteration dies mid-run with a
    # sampling error.  Prefill iterations (= rows per env) are
    # learning_starts // (num_envs * world * action_repeat) — the loops' own divisor.
    # World size comes from the config, NOT jax.process_count(): touching jax here
    # would initialize the backend before jax.distributed.initialize() runs.
    seq_len = int(algo.get("per_rank_sequence_length", 0) or 0)
    learning_starts = int(algo.get("learning_starts", 0) or 0)
    buffer_prefilled = bool(cfg.checkpoint.get("resume_from")) or bool(
        cfg.get("buffer", {}).get("load_from_exploration", False)
    )
    if seq_len > 1 and learning_starts > 0 and not buffer_prefilled and not cfg.get("dry_run", False):
        dist = cfg.get("mesh", {}).get("distributed", {}) or {}
        # Multi-process launches configured through a cluster launcher leave
        # num_processes null and let jax.distributed auto-detect: fall back to the
        # launcher env vars so the guard doesn't underestimate world as 1.  Only
        # trust them when a coordinator_address shows this run IS distributed —
        # a single-process run inside a SLURM/MPI allocation must not be rejected.
        world = int(dist.get("num_processes") or 1)
        if dist.get("coordinator_address") and not dist.get("num_processes"):
            world = int(
                os.environ.get("SLURM_NTASKS") or os.environ.get("OMPI_COMM_WORLD_SIZE") or 1
            )
        steps_per_iter = max(cfg.env.num_envs * world * max(cfg.env.action_repeat, 1), 1)
        rows_per_env = learning_starts // steps_per_iter
        if rows_per_env < seq_len:
            raise ValueError(
                f"algo.learning_starts={learning_starts} prefills only ~{rows_per_env} steps per "
                f"environment ({cfg.env.num_envs} envs x {world} process(es) x action_repeat "
                f"{cfg.env.action_repeat}), but algo.per_rank_sequence_length={seq_len} needs at "
                f"least {seq_len} steps per env before the first gradient step. Raise "
                f"learning_starts to >= {seq_len * steps_per_iter} or lower the sequence "
                f"length / env count."
            )


def run_algorithm(cfg: DotDict) -> None:
    """Registry lookup + mesh-context construction + entrypoint call
    (reference ``cli.py:60-199``)."""
    from sheeprl_tpu.parallel.mesh import make_mesh_context, maybe_init_distributed
    from sheeprl_tpu.utils.metric import MetricAggregator

    entry = get_algorithm(cfg.algo.name)
    kwargs: Dict[str, Any] = {}
    if "finetuning" in cfg.algo.name and "p2e" in entry["module"]:
        # Load + merge the exploration run's env config (reference cli.py:117-148).
        from sheeprl_tpu.algos.p2e import load_exploration_config

        kwargs["exploration_cfg"] = load_exploration_config(cfg)
    precision = cfg.get("float32_matmul_precision")
    if precision:
        # reference: torch.set_float32_matmul_precision(cfg.float32_matmul_precision)
        import jax

        algo_precision = str(cfg.algo.get("precision", "mesh")).lower()
        if any(t in algo_precision for t in ("bf16", "fp16", "16-mixed", "16-true")):
            # jax_default_matmul_precision only governs f32 dots; with an
            # explicit 16-bit algo.precision the knob is dead weight and
            # silently proceeding hides that (howto/precision.md).
            warnings.warn(
                f"float32_matmul_precision={precision!r} has no effect: "
                f"algo.precision={algo_precision!r} runs the matmuls in 16-bit "
                "compute, so the f32 dot precision knob never applies — set "
                "algo.precision=f32 if you want full-precision matmuls",
                stacklevel=2,
            )
        jax.config.update("jax_default_matmul_precision", str(precision))
    # Persistent XLA compilation cache, shared with the serve startup; where it
    # lives is decided in utils/compile_cache.py and nowhere else.
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(cfg.get("compile_cache", {}) or {})
    if cache_dir:
        print(f"persistent compile cache: {cache_dir}", flush=True)
    # Fault layer (sheeprl_tpu/fault, howto/fault_tolerance.md): SIGTERM/SIGINT
    # become a sticky flag every training loop polls at its safe boundary (one
    # final checkpoint + PREEMPTED marker + exit 75), and any scheduled chaos
    # faults are parsed before EnvPool forks its workers so the worker-fault spec
    # rides the fork.
    from sheeprl_tpu.fault import chaos as fault_chaos
    from sheeprl_tpu.fault import install_signal_handlers
    from sheeprl_tpu.fault.preemption import Preempted

    install_signal_handlers(grace_seconds=cfg.get("fault", {}).get("grace_seconds", 0))
    fault_chaos.install(cfg)

    # Concurrency race detector (jaxlint-threads runtime half,
    # sheeprl_tpu/analysis/threads/runtime.py): opt-in lock instrumentation
    # installed at the same boundary as chaos/signals so every lock the run
    # creates afterwards is observed; its JSONL report lands in
    # <log_dir>/races/ at the exit/crash boundary below.
    from sheeprl_tpu.analysis.threads import runtime as race_runtime

    race_detector = race_runtime.maybe_install(cfg)

    maybe_init_distributed(cfg.get("mesh", {}))
    ctx = make_mesh_context(cfg)

    if cfg.metric.get("disable_timer", False):
        timer.disabled = True
    MetricAggregator.disabled = cfg.metric.get("log_level", 1) == 0

    # Flight-recorder crash boundary (sheeprl_tpu/obs/flight_recorder.py): any
    # exception escaping the algorithm — including strict-mode NonFiniteError/
    # SignatureDriftError/RecompileError and RolloutAbortError — dumps the black
    # box (<log_dir>/blackbox/) before propagating.  The recorder is installed by
    # the entry point's TrainingMonitor and cleared here so back-to-back runs in
    # one process never cross-contaminate.
    from sheeprl_tpu.obs import flight_recorder
    from sheeprl_tpu.obs import fleet as obs_fleet

    try:
        entry["entrypoint"](ctx, cfg, **kwargs)
    except Preempted:
        # Graceful preemption is not a crash: the boundary checkpoint and the
        # PREEMPTED marker are already on disk — no blackbox dump.
        raise
    except Exception as exc:
        dump = flight_recorder.dump_active("crash", exc)
        if dump:
            print(f"flight recorder: black box dumped to {dump}", file=sys.stderr)
        # A crashing process with a private in-process aggregator (obs.fleet.dir
        # mode) flags the crash in its final snapshot before the plane goes down.
        obs_fleet.close_active(error=exc)
        raise
    finally:
        # Race report first: its headline counts merge into the flight recorder
        # and the fleet exporter's final flush before those planes close.  The
        # run's log dir is only resolved inside the entry point (the logger owns
        # the version_N subdir), so the detector borrows the flight recorder's.
        if race_detector is not None:
            if race_detector.log_dir is None:
                recorder = flight_recorder.get_active()
                if recorder is not None:
                    race_detector.log_dir = recorder.log_dir
            race_runtime.dump_active("run-end")
            race_runtime.uninstall()
        flight_recorder.install(None)
        obs_fleet.close_active()
        # Cost-model registry is process-global: clear it between multirun jobs
        # so one job's lowered FLOPs never leak into the next job's MFU.
        from sheeprl_tpu.obs import perf as obs_perf

        obs_perf.reset()


def eval_algorithm(cfg: DotDict) -> None:
    """Evaluation dispatch (reference ``cli.py:202-268``).  ``cfg`` is the run's saved
    config with the user's CLI overrides already merged on top
    (``_load_checkpoint_cfg``), so structural keys (algorithm, model sizes, obs keys)
    match the checkpoint unless the user explicitly overrides them.  Evaluation always
    uses a single process with one environment."""
    from sheeprl_tpu.parallel.mesh import make_mesh_context

    ckpt_path = Path(cfg.checkpoint_path)
    if "capture_video" in cfg:  # top-level convenience alias for env.capture_video
        cfg.env.capture_video = bool(cfg.capture_video)  # jaxlint: disable=JL006
    cfg.env.num_envs = 1
    cfg.run_name = cfg.get("run_name") or _default_run_name(cfg)

    evaluate_fn = get_evaluation(cfg.algo.name)
    ctx = make_mesh_context(cfg)
    evaluate_fn(ctx, cfg, str(ckpt_path))


def _default_run_name(cfg: Dict[str, Any]) -> str:
    stamp = datetime.datetime.now().strftime("%Y-%m-%d_%H-%M-%S")
    return f"{stamp}_{cfg.get('exp_name', 'run')}_{cfg.get('seed', 0)}"


def expand_multirun(overrides: List[str]) -> List[List[str]]:
    """Hydra-multirun semantics (reference ``cli.py:358`` ``@hydra.main`` with ``-m``):
    every override whose value is a bare comma-separated list becomes a sweep axis,
    and the grid is their cartesian product, e.g. ``algo.lr=1e-4,3e-4 seed=1,2`` →
    4 jobs.  Bracketed/quoted values (``cnn_keys.encoder=[rgb,depth]``) are single
    values, never axes."""
    import itertools

    axes: List[List[str]] = []
    for ov in overrides:
        key, eq, val = ov.partition("=")
        if eq and "," in val and not val.lstrip().startswith(("[", "{", "(", "'", '"')):
            axes.append([f"{key}={v}" for v in val.split(",")])
        else:
            axes.append([ov])
    return [list(combo) for combo in itertools.product(*axes)]


def run(args: Optional[List[str]] = None) -> None:
    """Train entry: ``python -m sheeprl_tpu exp=... key=value ...``

    ``-m`` / ``--multirun`` sweeps comma-separated override values as a grid
    (sequential execution), mirroring the reference's Hydra multirun: each job's
    ``run_name`` gains a ``multirun_<stamp>/job<i>`` prefix so the sweep lands in
    one directory tree."""
    _import_algorithms()
    overrides = list(args if args is not None else sys.argv[1:])
    multirun = False
    for flag in ("-m", "--multirun"):
        if flag in overrides:
            multirun = True
            overrides = [ov for ov in overrides if ov != flag]
    jobs = expand_multirun(overrides) if multirun else [overrides]
    if multirun and len(jobs) > 1:
        stamp = datetime.datetime.now().strftime("%Y-%m-%d_%H-%M-%S")
        print(f"multirun: {len(jobs)} jobs")
    for i, job_overrides in enumerate(jobs):
        cfg = compose(overrides=job_overrides)
        if cfg.checkpoint.get("resume_from"):
            cfg = resume_from_checkpoint(cfg)
        if multirun and len(jobs) > 1:
            base = cfg.get("run_name") or _default_run_name(cfg)
            cfg.run_name = f"multirun_{stamp}/job{i}_{base}"
            print(f"multirun job {i}/{len(jobs) - 1}: {' '.join(job_overrides)}")
        elif not cfg.get("run_name"):
            cfg.run_name = _default_run_name(cfg)
        check_configs(cfg)
        if os.environ.get("SHEEPRL_TPU_QUIET", "0") != "1":
            print_config(cfg)
        _run_with_autoresume(cfg)


def _run_with_autoresume(cfg: DotDict) -> None:
    """Run one job under the fault policy (``fault`` config group).

    Without ``fault.autoresume``: a graceful preemption exits with the resumable
    code 75 (EX_TEMPFAIL) so fleet schedulers / ``sheeprl_tpu.supervise`` relaunch
    it; every other exception propagates as usual (after the blackbox dump).

    With ``fault.autoresume=True``: preemptions resume immediately from the
    boundary checkpoint and retryable crashes relaunch from the latest *valid*
    checkpoint with bounded exponential backoff — the in-process mirror of
    ``python -m sheeprl_tpu.supervise`` (which alone survives SIGKILL/OOM).
    """
    import time

    from sheeprl_tpu.fault import classify as fault_classify
    from sheeprl_tpu.fault import counters as fault_counters
    from sheeprl_tpu.fault import preemption as fault_preemption
    from sheeprl_tpu.fault.supervisor import (
        backoff_seconds,
        fault_cfg,
        find_resume_checkpoint,
        run_dir_for,
    )

    f_cfg = fault_cfg(cfg)
    autoresume = bool(f_cfg.get("autoresume", False))
    max_retries = int(f_cfg.get("max_retries", 3))
    retries = 0
    while True:
        try:
            run_algorithm(cfg)
            return
        except fault_preemption.Preempted as p:
            if not autoresume:
                print(
                    f"preempted at step {p.step}; resumable checkpoint: "
                    f"{p.ckpt_path or 'none'} (exit {fault_preemption.RESUMABLE_EXIT_CODE})",
                    file=sys.stderr,
                )
                raise SystemExit(fault_preemption.RESUMABLE_EXIT_CODE)
            fault_preemption.clear_preemption()
            fault_counters.bump("Fault/restarts")
            resume = p.ckpt_path or find_resume_checkpoint(run_dir_for(cfg))
            print(
                f"fault.autoresume: preempted at step {p.step}; resuming"
                + (f" from {resume}" if resume else " from scratch"),
                file=sys.stderr,
            )
        except Exception as exc:
            if not autoresume:
                raise
            if fault_classify.classify_exception(exc) == fault_classify.FATAL:
                print(
                    f"fault.autoresume: {type(exc).__name__} is deterministic — not retrying",
                    file=sys.stderr,
                )
                raise
            retries += 1
            if retries > max_retries:
                print(f"fault.autoresume: exceeded fault.max_retries={max_retries}", file=sys.stderr)
                raise
            fault_counters.bump("Fault/restarts")
            delay = backoff_seconds(
                retries, float(f_cfg.get("backoff_s", 2.0)), float(f_cfg.get("backoff_max_s", 60.0))
            )
            print(
                f"fault.autoresume: {type(exc).__name__}; retry {retries}/{max_retries} "
                f"in {delay:.1f}s",
                file=sys.stderr,
            )
            time.sleep(delay)
            resume = find_resume_checkpoint(run_dir_for(cfg))
        if resume:
            cfg.checkpoint.resume_from = str(resume)


def _load_checkpoint_cfg(overrides: List[str], path_key: str) -> tuple:
    """Extract ``<path_key>=...`` from the overrides, load the checkpoint run's
    config.yaml and apply the remaining overrides on top (reference ``cli.py:369-401``).

    The value may also be a registry spec ``name[:version|stage|latest]`` instead
    of a filesystem path: it resolves through the model registry
    (``model_manager.registry_dir`` override, or the default ``models_registry``)
    to the registered payload, whose dir carries its own ``config.yaml``."""
    ckpt = None
    rest = []
    for ov in overrides:
        if ov.startswith(f"{path_key}="):
            ckpt = ov.split("=", 1)[1]
        else:
            rest.append(ov)
    if ckpt is None:
        raise ValueError(f"this entry point requires {path_key}=<path>")
    ckpt_path = Path(ckpt)
    if not ckpt_path.exists() and not ckpt.startswith(("/", ".", "~")):
        from sheeprl_tpu.serve.router import resolve_registry_checkpoint

        name, version, ckpt_path = resolve_registry_checkpoint(ckpt, rest)
        print(f"resolved {ckpt!r} -> {name} v{version} ({ckpt_path})")
    run_dir = ckpt_path.parent.parent if ckpt_path.is_dir() else ckpt_path.parent
    cfg_path = run_dir / "config.yaml"
    if not cfg_path.is_file():
        cfg_path = ckpt_path.parent / "config.yaml"
    if not cfg_path.is_file() and ckpt_path.is_dir():
        # Registry payloads are self-contained: config.yaml lives INSIDE the dir.
        cfg_path = ckpt_path / "config.yaml"
    if not cfg_path.is_file():
        raise FileNotFoundError(f"No config.yaml found alongside checkpoint {ckpt}")
    cfg = load_config(cfg_path)
    from sheeprl_tpu.config.core import _parse_value, _set_dotted

    for ov in rest:
        if "=" not in ov:
            raise ValueError(f"Malformed override {ov!r}")
        key, _, val = ov.partition("=")
        _set_dotted(cfg, key.lstrip("+"), _parse_value(val))
    return DotDict.wrap(cfg), ckpt_path


def evaluate(args: Optional[List[str]] = None) -> None:
    """Eval entry: ``python -m sheeprl_tpu.eval checkpoint_path=... [overrides]``"""
    _import_algorithms()
    overrides = list(args if args is not None else sys.argv[1:])
    cfg, ckpt_path = _load_checkpoint_cfg(overrides, "checkpoint_path")
    cfg.checkpoint_path = str(ckpt_path)
    # Eval records a video by default regardless of the training run's setting
    # (reference cli.py:378); an explicit override still wins.
    overridden = {ov.partition("=")[0].lstrip("+") for ov in overrides}
    if not overridden & {"env.capture_video", "capture_video"}:
        cfg.env.capture_video = True
    eval_algorithm(cfg)


def registration(args: Optional[List[str]] = None) -> None:
    """Model-registration entry (reference ``cli.py:408`` / ``sheeprl-registration``):
    ``python -m sheeprl_tpu.registration checkpoint_path=<ckpt_dir> [model_manager.name=...]``
    registers a training checkpoint's models in the configured registry."""
    from sheeprl_tpu.utils.model_manager import build_model_manager

    overrides = list(args if args is not None else sys.argv[1:])
    cfg, ckpt_path = _load_checkpoint_cfg(overrides, "checkpoint_path")

    mm_cfg = cfg.get("model_manager", {}) or {}
    name = mm_cfg.get("name") or f"{cfg.algo.name}_{cfg.env.id}"
    manager = build_model_manager(cfg)
    version = manager.register_model(
        str(ckpt_path),
        name,
        model_keys=list(mm_cfg.get("models", {}) or []),
        metadata={"algo": cfg.algo.name, "env": cfg.env.id, "seed": cfg.seed},
    )
    print(f"Registered {name} version {version}")


def available_algorithms() -> List[str]:
    _import_algorithms()
    return sorted(algorithm_registry)


def agents(args: Optional[List[str]] = None) -> None:
    """List registered agents (reference ``sheeprl-agents`` /
    ``available_agents.py``): one row per entry point, with its module, whether it
    runs decoupled, and whether an evaluation entry is registered."""
    _import_algorithms()
    rows = []
    for name in sorted(algorithm_registry):
        entry = algorithm_registry[name]
        rows.append(
            (
                name,
                entry["module"],
                "yes" if entry.get("decoupled") else "no",
                "yes" if name in evaluation_registry else "no",
            )
        )
    headers = ("algorithm", "module", "decoupled", "evaluable")
    widths = [max((len(r[i]) for r in rows), default=0) for i in range(len(headers))]
    widths = [max(w, len(h)) for w, h in zip(widths, headers)]
    line = "  ".join(h.ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)))
