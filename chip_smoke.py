#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that DreamerV3 training still starts on the chip.

Drives the main path once through the entry point a user calls
(``sheeprl_tpu.cli.run``, i.e. ``python -m sheeprl_tpu``): DreamerV3-S at its
published training shape — batch 16 x sequence 64 x 64x64x3 pixels,
``mesh.precision=bf16-mixed`` — on the seeded dummy pixel env, random weights from
the seed: prefill, 48 gradient steps, metrics flushed, a checkpoint written.  It
does so on BOTH replay paths, because they are different programs: the default a
user gets (``buffer.device=False``: host sampling + prefetch) and
``buffer.device=True`` (HBM ring, in-jit gather).

Phases (any failure makes the exit code non-zero; later phases still run so one
chip call shows everything that is broken):

* ``device``       JAX must find a TPU.  Anything else: exit 2, a message on stderr
                   naming the backend found, nothing on stdout.  No CPU fallback.
* ``native``       the host gather library comes from the committed ``gather.cpp``
                   (built in this run, or loaded under its source-hash name).
* ``numerics``     the RSSM's LayerNorm-GRU gate chain (``ops/gru.py``), compiled for
                   the device, agrees with a float64 numpy reference at the shapes
                   the DV3 scan uses for S and XL (B=16; H=512, 4096; bf16, f32).
* ``train_host``   the CLI run on the host replay path.
* ``train_device`` the CLI run on the HBM replay path, with an XProf capture of
                   three updates that must contain a device plane.

The program holds no hand-written kernel on this path (PERF.md, PR 21), so there is
no interpret-vs-compiled decision to report.

Each train phase checks, by the repo's own artefacts: every ``Loss/*`` scalar in
the TensorBoard events is finite, ``Time/sps_train`` is present, device memory was
read from ``memory_stats()`` for every device of the mesh, the checkpoint loads
with finite parameters and the expected gradient-step count, and
``perf_report.json`` holds a cost model for ``dreamer_v3/train_block``.

This is the only process: it imports JAX itself and calls ``cli.run`` in-process,
so nothing else competes for the chip.  The persistent compile cache is on and
lives where ``sheeprl_tpu/utils/compile_cache.py`` resolves it
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.xla_cache``); the
directory and this process's hit/miss counts are printed.  Needs neither git nor
the network.

The last line of stdout is one JSON object with exactly two keys,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`` — the
device as JAX reports it; it is printed only when JAX found a TPU.  Everything
else the run learned is on the ``chip_smoke: summary`` line before it and in
``<out>/summary.json``.

``--cpu-tiny-for-tests`` is a TEST-ONLY switch: the same phases at a toy size on the
CPU backend (host-RSS memory branch), so the control flow can be
debugged without a chip.  It is refused on any accelerator backend, so it cannot
take effect on the chip by accident, and it never prints ``"platform": "tpu"``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

REPO = Path(__file__).resolve().parent

TRAIN_BLOCK = "dreamer_v3/train_block"

NUM_ENVS = 4


@dataclass(frozen=True)
class Size:
    """What a run is cut to; everything the checks expect follows from it."""

    label: str
    overrides: Tuple[str, ...]
    grad_steps: int  # train iterations x (replay_ratio 1 x NUM_ENVS policy steps)
    capture: Tuple[int, int]  # post-compile training updates to trace
    recurrent: int  # RSSM recurrent state width
    numerics_hidden: Tuple[int, ...]


# DreamerV3-S at its published training shape; only the run length is cut: 64
# prefill iterations (one full sequence per env), then 12 training iterations.
FULL = Size(
    label="DreamerV3-S 16x64x64x64x3 bf16-mixed",
    overrides=(
        "algo.per_rank_batch_size=16",
        "algo.per_rank_sequence_length=64",
        "algo.learning_starts=256",
        "algo.total_steps=300",
    ),
    grad_steps=48,
    capture=(69, 71),  # the first training update is 64
    recurrent=512,  # algo/dreamer_v3_S.yaml
    numerics_hidden=(512, 4096),  # S and XL
)
# Test switch only: same loop, toy widths.
TINY = Size(
    label="cpu-tiny-for-tests",
    overrides=(
        "algo.per_rank_batch_size=4",
        "algo.per_rank_sequence_length=8",
        "algo.horizon=4",
        "algo.learning_starts=32",
        "algo.total_steps=36",
        "algo.dense_units=16",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=32",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
    ),
    grad_steps=8,
    capture=(9, 9),
    recurrent=32,
    numerics_hidden=(32,),
)


def train_overrides(device_replay: bool, devices: int, size: Size, log_root: Path) -> List[str]:
    ovs = [
        "exp=dreamer_v3",
        "algo=dreamer_v3_S",
        "env=discrete_dummy",
        "env.screen_size=64",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        f"env.num_envs={NUM_ENVS}",
        "env.sync_env=True",
        "env.capture_video=False",
        "algo.replay_ratio=1",
        "algo.run_test=False",
        "mesh.precision=bf16-mixed",
        f"mesh.devices={devices}",
        "buffer.size=100000",
        "buffer.memmap=False",
        "buffer.checkpoint=False",
        f"buffer.device={device_replay}",
        "checkpoint.every=0",
        "checkpoint.save_last=True",
        "metric.log_every=16",
        "obs.enabled=True",
        "obs.telemetry_interval=0",
        "compile_cache.enabled=True",
        f"log_root={log_root}",
        "seed=5",
    ]
    if device_replay:
        ovs.append(f"obs.capture_steps=[{size.capture[0]},{size.capture[1]}]")
    return ovs + list(size.overrides)


# ------------------------------------------------------------------ artefact readers


def device_planes(xprof_dir: Path) -> Tuple[Optional[Path], List[str]]:
    import jax

    traces = sorted(xprof_dir.rglob("*.xplane.pb"))
    if not traces:
        return None, []
    data = jax.profiler.ProfileData.from_file(str(traces[-1]))
    return traces[-1], [plane.name for plane in data.planes]


# --------------------------------------------------------------------------- phases


class Failure(Exception):
    """A phase check that did not hold."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise Failure(message)


def phase_native() -> Dict[str, Any]:
    from sheeprl_tpu import native

    expected = native.library_path()
    check(native.load() is not None, f"native gather library unavailable (status={native.status})")
    check(native.status in ("built", "loaded"), f"unexpected native status {native.status!r}")
    check(expected.is_file(), f"{expected} missing after load()")
    return {"status": native.status, "library": expected.name}


def phase_numerics(size: Size) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.ops.gru import reference_layernorm_gru

    def numpy_reference(proj, h, gamma, beta, eps):
        proj, h = np.asarray(proj, np.float64), np.asarray(h, np.float64)
        hidden = h.shape[-1]
        mean = proj.mean(-1, keepdims=True)
        var = np.square(proj - mean).mean(-1, keepdims=True)
        n = (proj - mean) / np.sqrt(var + eps) * np.asarray(gamma, np.float64) + np.asarray(beta, np.float64)
        sigmoid = lambda x: 1.0 / (1.0 + np.exp(-x))  # noqa: E731
        reset = sigmoid(n[..., :hidden])
        cand = np.tanh(reset * n[..., hidden : 2 * hidden])
        update = sigmoid(n[..., 2 * hidden :] - 1.0)
        return update * cand + (1.0 - update) * h

    batch, eps = 16, 1e-3
    results = {}
    for hidden in size.numerics_hidden:
        for dtype in (jnp.bfloat16, jnp.float32):
            name = f"H{hidden}_{jnp.dtype(dtype).name}"
            rng = np.random.default_rng(hidden)
            proj = jnp.asarray(rng.normal(size=(batch, 3 * hidden)), dtype)
            h = jnp.asarray(rng.normal(size=(batch, hidden)), dtype)
            gamma = jnp.asarray(rng.normal(1.0, 0.1, size=(3 * hidden,)), jnp.float32)
            beta = jnp.asarray(rng.normal(0.0, 0.1, size=(3 * hidden,)), jnp.float32)
            out = jax.jit(reference_layernorm_gru, static_argnums=4)(proj, h, gamma, beta, eps)
            check(out.shape == (batch, hidden) and out.dtype == dtype, f"{name}: got {out.shape} {out.dtype}")
            got = np.asarray(out, np.float64)
            check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
            err = float(np.abs(got - numpy_reference(proj, h, gamma, beta, eps)).max())
            # f32 statistics, one rounding to the state dtype; |h'| stays O(1)
            tol = 2.0**-6 if dtype == jnp.bfloat16 else 1e-4
            check(err <= tol, f"{name}: max abs err {err:.3e} vs float64 reference (tolerance {tol:.1e})")
            results[name] = {"max_abs_err": err}
    return results


def phase_train(device_replay: bool, devices: int, size: Size, out_dir: Path) -> Dict[str, Any]:
    import jax
    import numpy as np

    from sheeprl_tpu import native
    from sheeprl_tpu.checkpoint.manager import CheckpointManager
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.utils.logger import read_scalars

    name = "train_device" if device_replay else "train_host"
    log_root = out_dir / name
    native_before = dict(native.calls)
    run(train_overrides(device_replay, devices, size, log_root))

    versions = sorted(log_root.glob("runs/**/version_*"))
    check(len(versions) == 1, f"expected one run dir under {log_root}, found {len(versions)}")
    version_dir = versions[0]
    scalars = read_scalars(version_dir)

    losses = {tag: vals for tag, vals in scalars.items() if tag.startswith("Loss/")}
    check(bool(losses), "no Loss/* scalars were flushed")
    for tag, vals in losses.items():
        check(all(math.isfinite(v) for v in vals), f"{tag} has a non-finite value: {vals}")
    sps = scalars.get("Time/sps_train", [])
    check(bool(sps) and all(math.isfinite(v) and v > 0 for v in sps), f"Time/sps_train missing or invalid: {sps}")

    on_tpu = jax.default_backend() == "tpu"
    memory = {}
    if on_tpu:
        for i in range(devices):
            vals = scalars.get(f"Memory/bytes_in_use/dev{i}", [])
            check(bool(vals) and vals[-1] > 0, f"Memory/bytes_in_use/dev{i} missing: device memory was not read from memory_stats()")
            memory[f"dev{i}"] = int(vals[-1])
        check(
            min(memory.values()) >= 0.5 * max(memory.values()),
            f"device memory is not spread evenly over the mesh: {memory}",
        )
    else:
        check("Memory/host_peak_rss_bytes" in scalars, "no Memory/* scalar at all")

    ckpt = CheckpointManager.latest_valid(version_dir / "checkpoints")
    check(ckpt is not None, "no valid checkpoint was written")
    state = CheckpointManager.load(ckpt, fallback=False)
    grad_steps = int(state["cumulative_grad_steps"])
    check(grad_steps == size.grad_steps, f"{grad_steps} gradient steps taken, expected {size.grad_steps}")
    leaves = jax.tree.leaves(state["params"])
    check(bool(leaves) and all(np.isfinite(np.asarray(x)).all() for x in leaves), "checkpointed parameters are not all finite")
    from flax.traverse_util import flatten_dict

    gru_scales = [np.shape(v) for k, v in flatten_dict(state["params"], sep="/").items() if k.endswith("ln_scale")]
    check(
        gru_scales == [(3 * size.recurrent,)],
        f"RSSM GRU LayerNorm scale has shape {gru_scales}, not the full width {3 * size.recurrent}",
    )

    report = json.loads((version_dir / "perf_report.json").read_text())
    model = report["cost_models"].get(TRAIN_BLOCK, {})
    check(not report["registration_failures"], f"perf plane registration failures: {report['registration_failures']}")
    check(model.get("flops", 0) > 0 and model.get("calls", 0) > 0, f"no cost model registered for {TRAIN_BLOCK}: {model}")

    result = {
        "log_dir": str(version_dir),
        "grad_steps": grad_steps,
        "sps_train_last": sps[-1],
        "loss_tags": len(losses),
        "world_model_loss_last": losses.get("Loss/world_model_loss", [float("nan")])[-1],
        "memory_bytes_in_use": memory,
        "train_block_flops": model["flops"],
        "compile_total": scalars.get("Compile/total_compiles", [0])[-1],
    }
    if device_replay:
        trace, planes = device_planes(version_dir / "xprof")
        check(trace is not None, "obs.capture_steps wrote no .xplane.pb")
        if on_tpu:
            check(any(p.startswith("/device:TPU:") for p in planes), f"no device plane in {trace.name}: {planes}")
        result["xplane"] = {"file": trace.name, "planes": planes}
    else:
        served = {k: native.calls[k] - native_before[k] for k in native.calls}
        check(served["native"] > 0 and served["numpy"] == 0, f"host sampler did not stay on the native gather: {served}")
        result["host_sampler"] = {"path": "native", **served}
    return result


# ----------------------------------------------------------------------------- main


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--devices", type=int, default=1, help="mesh.devices (data-parallel over N chips)")
    parser.add_argument("--out", type=Path, default=REPO / "chip_smoke_out", help="emptied first; holds the run dirs")
    parser.add_argument("--cpu-tiny-for-tests", action="store_true", help="TEST ONLY: toy size on the CPU backend")
    args = parser.parse_args(argv)
    tiny = args.cpu_tiny_for_tests
    size = TINY if tiny else FULL
    os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

    t_start = time.perf_counter()
    import sheeprl_tpu  # noqa: F401  (nothing to drive without the program: fail before any output)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}
    if tiny and platform != "cpu":
        print(f"chip_smoke: --cpu-tiny-for-tests is refused on backend {platform!r}", file=sys.stderr)
        return 2
    if not tiny and platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform={platform} device_kind={device['kind']} "
              f"count={device['count']}; there is no CPU fallback", file=sys.stderr)
        return 2
    if args.devices > len(devices):
        print(f"chip_smoke: --devices {args.devices} but JAX sees {len(devices)}", file=sys.stderr)
        return 2
    print(f"chip_smoke: platform={platform} device_kind={device['kind']} count={device['count']} "
          f"jax={jax.__version__}", flush=True)

    import logging

    logging.basicConfig(level=logging.WARNING, format="%(name)s: %(message)s")
    logging.getLogger("sheeprl_tpu").setLevel(logging.INFO)

    from sheeprl_tpu.utils.compile_cache import CacheStats, resolve_cache_dir

    cache = CacheStats()
    out_dir = args.out
    shutil.rmtree(out_dir, ignore_errors=True)  # checkpoints and traces of the last run
    out_dir.mkdir(parents=True)

    phases: List[Tuple[str, Callable[[], Dict[str, Any]]]] = [
        ("native", phase_native),
        ("numerics", lambda: phase_numerics(size)),
        ("train_host", lambda: phase_train(False, args.devices, size, out_dir)),
        ("train_device", lambda: phase_train(True, args.devices, size, out_dir)),
    ]
    results: Dict[str, Any] = {}
    failed: List[str] = []
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"chip_smoke: phase {name} ...", flush=True)
        try:
            results[name] = fn()
            status = "ok"
        except Exception as exc:  # keep going: one call should show every broken phase
            traceback.print_exc()
            results[name] = {"error": f"{type(exc).__name__}: {exc}"}
            failed.append(name)
            status = "FAILED"
        results[name]["seconds"] = round(time.perf_counter() - t0, 2)
        print(f"chip_smoke: phase {name} {status} in {results[name]['seconds']}s: "
              f"{json.dumps(results[name], default=str)}", flush=True)

    cache_stats = cache.snapshot()
    cache.close()
    print(f"chip_smoke: compile cache {resolve_cache_dir()} {cache_stats}", flush=True)
    summary = {
        "ok": not failed,
        "device": device,
        "failed": failed,
        "mesh_devices": args.devices,
        "size": size.label,
        "grad_steps": {k: results[k].get("grad_steps") for k in ("train_host", "train_device")},
        "compile_cache": {"dir": resolve_cache_dir(), **cache_stats},
        "native": results["native"].get("status"),
        "wall_seconds": round(time.perf_counter() - t_start, 1),
        "versions": {"jax": jax.__version__, "jaxlib": _version("jaxlib"), "libtpu": _version("libtpu")},
    }
    (out_dir / "summary.json").write_text(json.dumps({**summary, "phases": results}, indent=1, default=str))
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    print(result_line(not failed, device), flush=True)
    return 0 if not failed else 1


def result_line(ok: bool, device: Dict[str, Any]) -> str:
    """The contract's last line: these two keys and the device's three, nothing else."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(device["platform"]), "kind": str(device["kind"]), "count": int(device["count"])},
    })


def _version(package: str) -> Optional[str]:
    from importlib import metadata

    try:
        return metadata.version(package)
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
