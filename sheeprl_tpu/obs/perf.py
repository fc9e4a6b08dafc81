"""Performance attribution plane: cost-model MFU, goodput ledger, regression watchdog.

Three parts, surfaced through :class:`PerfPlane` (owned by ``TrainingMonitor``)
and a handful of free functions used from the lowering seams:

1. **Cost-model registry** — every jitted hot path registers its XLA
   ``cost_analysis()`` FLOPs + bytes once, at first call, via
   :func:`instrument` (training dispatches) or :func:`register_compiled`
   (serve batch buckets, which already hold ``Compiled`` objects).  The
   registration reads ``Compiled.cost_analysis()`` — the one form every
   backend implements (``Lowered.cost_analysis()`` is unimplemented on the TPU
   runtime) — from ``jitted.lower(*args).compile()`` just before the first
   call; that call then reuses the executable (JAX shares the lowering and
   executable caches between the two paths), so nothing compiles twice and no
   buffer is consumed.  A hot path whose cost model cannot be read is named
   once in the log and in ``perf_report.json``.  After that, the
   wrapper only bumps a per-name call counter: the existing step timers turn
   call deltas into zero-extra-sync ``Perf/{mfu,hbm_bw_util,
   achieved_flops_per_sec}`` gauges at every log flush.

2. **Goodput ledger** — classifies every second of wall clock from signals the
   monitor already drains (the ``Time/*`` timer registry, the recompile
   watchdog's compile seconds, checkpoint phases) into
   compute / env / transport / recompile / checkpoint / downtime / other.
   Fractions always sum to 1.0; ``Perf/goodput`` = compute + env (useful work).

3. **Regression watchdog** — an EWMA step-time detector that, on sustained
   post-warmup degradation beyond ``obs.perf.regress_pct``, fires ONE bounded
   auto-capture through the xprof window machinery, stamps a
   ``perf_regression`` flight-recorder event and exports a ``perf_anomalies``
   fleet gauge.

4. **Scope maps** — :func:`scope` names a part of a jitted hot path
   (``jax.named_scope`` + the name's declaration); :func:`register_compiled`
   writes, once per registered program that declares scopes,
   ``<log_dir>/scopes/<name>.json``: the HLO module's name and instruction name
   -> ``{"<scope> <fwd|bwd>": share}``, parsed from ``Compiled.as_text()``
   (:func:`scopes_tag` keeps the compile cache from handing back an executable
   compiled under other scopes).  A device capture
   names its op events by HLO instruction only (no ``op_name``), so this file is
   what groups a capture's device time by the program's layers
   (``perfbench/readers/spans.py``).

All state that outlives a run (the registry, the declared scopes, the scope
directory) is process-global and reset from ``cli.run_algorithm``'s ``finally``
block so multirun jobs do not bleed cost models into each other.
"""

from __future__ import annotations

import contextlib
import functools
import json
import logging
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, MutableMapping, Optional, Tuple

__all__ = [
    "PEAK_FLOPS",
    "PEAK_HBM_BW",
    "PERF_REPORT_ENV_VAR",
    "GoodputLedger",
    "PerfPlane",
    "StepTimeWatchdog",
    "UnknownDeviceError",
    "analyze_compiled",
    "instrument",
    "mfu_from_flops",
    "note",
    "peak_flops",
    "peak_hbm_bw",
    "perf_enabled",
    "register_compiled",
    "register_cost_model",
    "registered_cost_models",
    "report_path",
    "reset",
    "scope",
    "scope_map",
    "scopes_tag",
]

PERF_REPORT_ENV_VAR = "SHEEPRL_TPU_PERF_REPORT"

_log = logging.getLogger(__name__)

# Peak dense bf16 FLOP/s per chip (public figures).  bench.py imports this
# table — keep it the single source of truth for both offline and in-run MFU.
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12 / 2,  # per-chip figure is per 2 cores; one jax device = 1 chip
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e's device_kind
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e/Trillium's device_kind
    "TPU v6e": 918e12,
}
# A CPU backend has no published bf16 matrix peak; a nominal figure keeps the
# MFU gauge finite and nonzero in CI smokes without pretending to be accurate.
_CPU_PEAK_FLOPS = 5e11

# Peak HBM bandwidth, bytes/s per chip (public figures); CPUs get a nominal
# DDR-class figure for the same reason as above.
PEAK_HBM_BW = {
    "TPU v2": 700e9,
    "TPU v3": 900e9 / 2,
    "TPU v4": 1200e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}
_CPU_PEAK_HBM_BW = 50e9


class UnknownDeviceError(LookupError):
    """The accelerator's ``device_kind`` is in neither peak table.  A utilisation
    against a guessed peak is not a measurement: add the device's published
    figures to ``PEAK_FLOPS`` / ``PEAK_HBM_BW`` instead."""


def _lookup(table: Mapping[str, float], device: Any, cpu: float) -> float:
    kind = str(getattr(device, "device_kind", "") or "")
    for name, peak in table.items():
        if kind.startswith(name):
            return peak
    platform = str(getattr(device, "platform", "") or "")
    if platform == "cpu" or kind.lower() in ("cpu", "host"):
        return cpu
    raise UnknownDeviceError(
        f"no published peak for device_kind={kind!r} (platform={platform!r}); "
        f"known kinds: {sorted(table)}"
    )


def peak_flops(device: Any = None) -> float:
    """Peak dense bf16 FLOP/s for ``device`` (default: ``jax.devices()[0]``);
    raises :class:`UnknownDeviceError` for an accelerator not in the table."""
    if device is None:
        device = _default_device()
    return _lookup(PEAK_FLOPS, device, _CPU_PEAK_FLOPS)


def peak_hbm_bw(device: Any = None) -> float:
    """Peak HBM bytes/s for ``device`` (default: ``jax.devices()[0]``);
    raises :class:`UnknownDeviceError` for an accelerator not in the table."""
    if device is None:
        device = _default_device()
    return _lookup(PEAK_HBM_BW, device, _CPU_PEAK_HBM_BW)


def _default_device() -> Any:
    import jax

    return jax.devices()[0]


def mfu_from_flops(flops_per_step: float, steps_per_sec: float, device: Any = None) -> float:
    """Model FLOPs utilization: achieved FLOP/s over the chip's peak."""
    peak = peak_flops(device)
    if peak <= 0:
        return 0.0
    return float(flops_per_step) * float(steps_per_sec) / peak


# --------------------------------------------------------------------------- config


def perf_enabled(cfg: Any) -> bool:
    """``obs.perf.enabled`` (default True once an ``obs.perf`` section is
    composed — like the flight recorder, the attribution plane runs regardless
    of ``obs.enabled``).  A cfg with no ``obs.perf`` section at all leaves the
    plane off, so a bare hand-rolled monitor stays a true no-op."""
    perf_cfg = _perf_cfg(cfg)
    if not perf_cfg:
        return False
    try:
        return bool(perf_cfg.get("enabled", True))
    except Exception:
        return True


def _perf_cfg(cfg: Any) -> Mapping[str, Any]:
    if cfg is None:
        return {}
    try:
        obs = cfg.get("obs") if hasattr(cfg, "get") else getattr(cfg, "obs", None)
        if not obs:
            return {}
        perf = obs.get("perf") if hasattr(obs, "get") else getattr(obs, "perf", None)
        return perf or {}
    except Exception:
        return {}


# --------------------------------------------------------------------- cost registry


class _Entry:
    """One registered hot path: XLA cost model + a hot-path call counter.

    ``calls`` is bumped without a lock — CPython's GIL makes the int increment
    effectively atomic, and a rare lost increment only perturbs one flush
    window's MFU, never the registry itself.
    """

    __slots__ = ("name", "flops", "bytes_accessed", "info", "calls", "attempted", "error")

    def __init__(self, name: str) -> None:
        self.name = name
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.info: Dict[str, Any] = {}
        self.calls = 0
        self.attempted = False
        self.error: Optional[str] = None


_lock = threading.Lock()
_registry: Dict[str, _Entry] = {}


def _ensure_entry(name: str) -> _Entry:
    with _lock:
        entry = _registry.get(name)
        if entry is None:
            entry = _Entry(name)
            _registry[name] = entry
        return entry


def register_cost_model(name: str, flops: float, bytes_accessed: float = 0.0, **info: Any) -> None:
    """Record the XLA cost model for one jitted hot path (idempotent by name)."""
    entry = _ensure_entry(name)
    with _lock:
        entry.flops = float(flops or 0.0)
        entry.bytes_accessed = float(bytes_accessed or 0.0)
        entry.info.update(info)
        entry.attempted = True


def record_registration_failure(name: str, exc: BaseException) -> None:
    """A hot path whose cost model could not be read: said once in the log by
    name, and carried into ``perf_report.json`` (``registration_failures``) so
    an absent ``Perf/mfu`` is explained instead of silent."""
    entry = _ensure_entry(name)
    with _lock:
        entry.attempted = True
        entry.error = f"{type(exc).__name__}: {exc}"
    _log.warning("perf: no cost model for %s (%s); Perf/mfu will not count it", name, entry.error)


def registration_failures() -> Dict[str, str]:
    with _lock:
        return {name: e.error for name, e in _registry.items() if e.error}


def record_call(name: str, n: int = 1) -> None:
    """Bump the call counter for ``name`` (for paths not wrapped by instrument)."""
    _ensure_entry(name).calls += n


def registered_cost_models() -> Dict[str, Dict[str, Any]]:
    """Snapshot of the registry: ``{name: {flops, bytes_accessed, calls, ...}}``."""
    with _lock:
        return {
            name: {
                "flops": e.flops,
                "bytes_accessed": e.bytes_accessed,
                "calls": e.calls,
                **({"info": dict(e.info)} if e.info else {}),
            }
            for name, e in _registry.items()
        }


def reset() -> None:
    """Clear the process-global registry (between multirun jobs / in tests)."""
    global _scope_dir
    with _lock:
        _registry.clear()
        _scope_names.clear()
        _notes.clear()
        _scope_dir = None


# ------------------------------------------------------------------- cost analysis


def _cost_dict(cost: Any) -> Dict[str, Any]:
    # Compiled.cost_analysis() returns a dict or (older form) a list of
    # per-executable dicts — normalize both shapes.
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def analyze_compiled(compiled: Any) -> Tuple[float, float]:
    """``(flops, bytes_accessed)`` from a ``jax.stages.Compiled``."""
    cost = _cost_dict(compiled.cost_analysis())
    return float(cost.get("flops", 0.0) or 0.0), float(cost.get("bytes accessed", 0.0) or 0.0)


def _memory_info(compiled: Any) -> Dict[str, float]:
    try:
        stats = compiled.memory_analysis()
    except Exception:
        return {}
    info: Dict[str, float] = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        value = getattr(stats, attr, None)
        if value is not None:
            info[attr] = float(value)
    return info


def register_compiled(name: str, compiled: Any) -> None:
    """Register a cost model straight from a ``Compiled``."""
    try:
        flops, bytes_accessed = analyze_compiled(compiled)
    except Exception as exc:  # attribution must not kill the hot path it measures
        record_registration_failure(name, exc)
        return
    if flops <= 0.0:
        record_registration_failure(name, ValueError("cost_analysis() reported no flops on this backend"))
        return
    register_cost_model(name, flops, bytes_accessed, **_memory_info(compiled))
    if _notes:
        _log.info("perf: %s traced with %s", name, json.dumps(_notes, sort_keys=True))
    if _scope_dir is not None:
        _write_scope_map(name, compiled)


# ---------------------------------------------------------------------- scope maps

_scope_names: set = set()
_notes: Dict[str, Any] = {}  # what a program's trace said of itself (``note``)
_scope_dir: Optional[str] = None  # <log_dir>/scopes once a PerfPlane with a log dir exists

# ``  %fusion.12 = bf16[..] fusion(..), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/.."}``
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_HLO_MODULE = re.compile(r"HloModule\s+([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_HLO_REFERENCE = re.compile(r"%([\w.\-]+)")
_JIT_COMPONENT = re.compile(r"jit\([^()]*\)/?")
_TRANSFORM_OPEN = re.compile(r"\b\w+\(")


def scope(name: str):
    """``with scope("world_model/rssm"):`` inside a jitted hot path: a
    ``jax.named_scope`` whose name is also declared here, so that the program's
    scope map can tell it from the Flax modules and primitives after it in an
    ``op_name`` path.  Metadata only: the compiled program holds the same ops."""
    import jax

    _scope_names.add(name)
    return jax.named_scope(name)


def note(key: str, value: Any) -> None:
    """Called while a jitted hot path is traced: a static fact about the program
    (which mechanism its trace engaged, on how much), logged once at the program's
    registration and written as a top-level key of its scope map.  A dict joins the dict
    noted under the key before (each layer of a model its own entry)."""
    before = _notes.get(key)
    _notes[key] = {**before, **value} if isinstance(before, dict) and isinstance(value, dict) else value


def scopes_tag():
    """``with scopes_tag():`` around a few small ops of a program that declares scopes,
    traced after them: the ops carry the declared names as a frontend attribute.

    JAX's persistent compile cache keys a program without its metadata, so a cache
    that a source with other scopes (or none) filled would hand back that source's
    executable, with its ``op_name`` paths.  A frontend attribute is part of the
    program and of the key: a program compiled under other scope names is another
    entry.  (A scope moved under the same names is not; clear the entry then.)  A
    program that declares none is left as it is."""
    from jax.experimental.xla_metadata import set_xla_metadata

    if not _scope_names:
        return contextlib.nullcontext()
    return set_xla_metadata(scopes=",".join(sorted(_scope_names)))


def _scope_of(op_name: str) -> str:
    """``jit(block)/jit(main)/while/body/transpose(jvp(world_model/rssm))/while/body/mul``
    -> ``"world_model/rssm bwd"``: the leftmost declared scope of the path (the
    transformations' wrappers dropped), the declared scopes nested directly under
    it, and the direction; ``""`` where the path holds no declared scope."""
    parts = _TRANSFORM_OPEN.sub("", _JIT_COMPONENT.sub("", op_name)).replace(")", "").split("/")
    found: List[str] = []
    i = 0
    while i < len(parts):
        hits = [n for n in _scope_names if parts[i : i + n.count("/") + 1] == n.split("/")]
        if hits:
            found.append(max(hits, key=len))
            i += found[-1].count("/") + 1
        elif found:
            break
        else:
            i += 1
    if not found:
        return ""
    return "/".join(found) + (" bwd" if "transpose(" in op_name else " fwd")


def scope_map(hlo_text: str) -> Tuple[Dict[str, Dict[str, float]], List[str]]:
    """``(ops, inherited)``: HLO instruction name -> ``{"<scope> <fwd|bwd>": share}``
    for every instruction a device capture can show (those of a fused computation
    run inside their fusion), the shares of one instruction summing to 1 (``{}``:
    unscoped); and the names of those that took their shares from a neighbour.

    An instruction that calls a computation (a fusion, mostly) is split over the
    scopes of the instructions inside it, by their count: XLA fuses a ``Health/*``
    norm's reduction into the Adam update that reads the same tensor and roots the
    fusion at the reduction, so the fusion's own ``op_name`` (its root's) would book
    the optimizer's pass under ``health``.  Any other instruction takes its own
    ``op_name``, or that of the computation it calls (its root's, else the first one
    inside it that has one).  One whose path holds no declared scope (the compiler's
    own copies and slices, which carry no metadata; loop-invariant work that JAX
    hoists out of a scan without the outer name stack) takes the shares of the first
    instruction that reads it, else of the first it reads: data movement is charged
    to the scope it serves."""
    own: Dict[str, str] = {}  # instruction -> op_name
    calls: Dict[str, str] = {}  # instruction -> the computation it calls
    home: Dict[str, str] = {}  # instruction -> the computation it is in
    reads: Dict[str, List[str]] = {}  # instruction -> the instructions it reads
    stands_for: Dict[str, str] = {}  # computation -> op_name of its root, else of its first named instruction
    inside: Dict[str, Dict[str, int]] = {}  # computation -> how many instructions of each scope
    scope_of = functools.lru_cache(maxsize=None)(_scope_of)  # a layer's ops share their path up to the primitive
    computation = ""
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        home[name] = computation
        reads[name] = _HLO_REFERENCE.findall(line[m.end() :])
        op = _HLO_OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
            if m.group(1) or computation not in stands_for:
                stands_for[computation] = op.group(1)
            found = scope_of(op.group(1))
            if found:
                counts = inside.setdefault(computation, {})
                counts[found] = counts.get(found, 0) + 1
        called = _HLO_CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    inner = set(calls.values())
    ops: Dict[str, Dict[str, float]] = {}
    for name, comp in home.items():
        if comp in inner:
            continue
        counts = inside.get(calls.get(name, ""), {})
        found = scope_of(own.get(name) or stands_for.get(calls.get(name, ""), ""))
        total = sum(counts.values())
        ops[name] = {key: n / total for key, n in counts.items()} if counts else {found: 1.0} if found else {}
    read_by: Dict[str, List[str]] = {}
    for name in ops:
        for other in reads[name]:
            read_by.setdefault(other, []).append(name)
    inherited: List[str] = []
    for _ in range(4):  # copy-start -> copy-done -> tuple -> the loop that reads it
        for name, shares in ops.items():
            if not shares:
                near = [ops[n] for n in read_by.get(name, []) + reads[name] if ops.get(n)]
                if near:
                    ops[name] = dict(near[0])
                    inherited.append(name)
    return ops, inherited


def _write_scope_map(name: str, compiled: Any) -> None:
    """``<log_dir>/scopes/<name>.json`` for one registered program that declares
    scopes; best-effort."""
    if not _scope_names:
        return
    try:
        t0 = time.perf_counter()
        text = compiled.as_text()
        module = _HLO_MODULE.match(text)  # the name a capture's module events carry
        ops, inherited = scope_map(text)
        if not any(ops.values()):
            # another program's scopes; or the compile cache handed back an executable that a
            # source without these scopes compiled (its entry has to go before a map can be had)
            _log.info("perf: no scope map for %s: its compiled text holds none of the declared scopes", name)
            return
        doc = {"program": name, "module": module.group(1) if module else "", "ops": ops, "inherited": inherited, **_notes}
        _dump_json(os.path.join(_scope_dir, f"{name}.json"), doc)
        _log.info("perf: scope map of %s (%d ops) written in %.2fs", name, len(ops), time.perf_counter() - t0)
    except Exception as exc:  # attribution must not kill the hot path it measures
        _log.warning("perf: no scope map for %s (%s: %s)", name, type(exc).__name__, exc)


def _unwrap_jit(fn: Any) -> Optional[Any]:
    """Follow ``__wrapped__`` (strict_guard et al.) down to a jitted callable."""
    target, hops = fn, 0
    while target is not None and hops < 8:
        if hasattr(target, "lower"):
            return target
        target = getattr(target, "__wrapped__", None)
        hops += 1
    return None


def instrument(cfg: Any, name: str, fn: Callable[..., Any]) -> Callable[..., Any]:
    """Wrap a jitted hot path: register its cost model once, count every call.

    Identity when ``obs.perf.enabled`` is off.  The first call compiles the
    underlying jitted function ahead of time with the live arguments (the
    executable the call itself then reuses) and records XLA's FLOPs/bytes
    estimate under ``name``.  Every call bumps the per-name counter the
    :class:`PerfPlane` turns into MFU at flush time.
    """
    if not perf_enabled(cfg):
        return fn
    entry = _ensure_entry(name)

    def instrumented(*args: Any, **kwargs: Any) -> Any:
        if not entry.attempted:
            entry.attempted = True
            target = _unwrap_jit(fn)
            if target is not None:
                try:
                    compiled = target.lower(*args, **kwargs).compile()
                except Exception as exc:  # let the call below raise the real error
                    record_registration_failure(name, exc)
                else:
                    register_compiled(name, compiled)
        entry.calls += 1
        return fn(*args, **kwargs)

    instrumented.__name__ = f"perf_instrument[{name}]"
    instrumented.__qualname__ = instrumented.__name__
    instrumented.__wrapped__ = fn
    return instrumented


# ------------------------------------------------------------------ goodput ledger

# First-present candidate lists: Anakin times its dispatch block with BOTH
# ``Time/train_time`` and ``Time/phase_dispatch`` (same with-block), so only
# the first present key counts — summing them would double-book compute.
_COMPUTE_KEYS = ("Time/phase_dispatch", "Time/train_time", "Time/phase_train")
_ENV_KEYS = ("Time/phase_env_step", "Time/env_interaction_time", "Time/env_interaction", "Time/env_time")
_TRANSPORT_KEYS = ("Time/block_send", "Time/block_recv", "Time/queue_wait", "Time/phase_transport")
_CHECKPOINT_KEYS = ("Time/phase_checkpoint", "Time/checkpoint_time", "Time/phase_ckpt")

GOODPUT_CATEGORIES = ("compute", "env", "transport", "recompile", "checkpoint", "downtime", "other")


def _first_present(timers: Mapping[str, float], keys: Tuple[str, ...]) -> float:
    for key in keys:
        if key in timers:
            try:
                return max(0.0, float(timers[key]))
            except (TypeError, ValueError):
                return 0.0
    return 0.0


class GoodputLedger:
    """Classify wall clock into the goodput taxonomy; fractions sum to 1.0.

    ``classify`` takes one flush window's drained timers plus out-of-band
    seconds (recompiles from the compile-event watchdog, downtime from the
    supervisor) and returns per-category fractions of ``elapsed_s``.  When the
    classified seconds exceed the wall clock (overlapping timers), every
    category is scaled down proportionally so the sum stays exactly 1.0.
    Cumulative seconds accumulate for the end-of-run report.
    """

    def __init__(self) -> None:
        self.totals: Dict[str, float] = {c: 0.0 for c in GOODPUT_CATEGORIES}
        self.elapsed_total = 0.0

    def classify(
        self,
        timers: Mapping[str, float],
        elapsed_s: float,
        recompile_s: float = 0.0,
        downtime_s: float = 0.0,
    ) -> Dict[str, float]:
        seconds = {
            "compute": _first_present(timers, _COMPUTE_KEYS),
            "env": _first_present(timers, _ENV_KEYS),
            "transport": sum(_first_present(timers, (k,)) for k in _TRANSPORT_KEYS),
            "recompile": max(0.0, float(recompile_s or 0.0)),
            "checkpoint": _first_present(timers, _CHECKPOINT_KEYS),
            "downtime": max(0.0, float(downtime_s or 0.0)),
        }
        classified = sum(seconds.values())
        elapsed = float(elapsed_s or 0.0)
        if elapsed <= 0.0:
            elapsed = classified
        if elapsed <= 0.0:
            # Nothing happened this window: call it all "other" so fractions
            # still sum to 1.0 and downstream means stay well-defined.
            fractions = {c: 0.0 for c in GOODPUT_CATEGORIES}
            fractions["other"] = 1.0
            return fractions
        if classified > elapsed:
            scale = elapsed / classified
            seconds = {c: s * scale for c, s in seconds.items()}
            classified = elapsed
        seconds["other"] = elapsed - classified
        for category, value in seconds.items():
            self.totals[category] += value
        self.elapsed_total += elapsed
        return {c: seconds[c] / elapsed for c in GOODPUT_CATEGORIES}

    def fractions(self) -> Dict[str, float]:
        """Cumulative fractions over every classified window (sum to 1.0)."""
        if self.elapsed_total <= 0.0:
            out = {c: 0.0 for c in GOODPUT_CATEGORIES}
            out["other"] = 1.0
            return out
        return {c: self.totals[c] / self.elapsed_total for c in GOODPUT_CATEGORIES}

    def goodput(self) -> float:
        """Useful-work fraction: device compute + env stepping."""
        fractions = self.fractions()
        return fractions["compute"] + fractions["env"]


# -------------------------------------------------------------- regression watchdog


class StepTimeWatchdog:
    """EWMA step-time regression detector with a bounded capture budget.

    ``observe(dt)`` returns an event dict exactly once per *sustained*
    degradation episode (EWMA above ``baseline * (1 + regress_pct)`` for
    ``sustain_steps`` consecutive observations), then stays silent until the
    EWMA recovers below the threshold — no retrigger flapping.  The event's
    ``capture`` flag is True at most ``max_captures`` times per run.
    """

    def __init__(
        self,
        regress_pct: float = 0.25,
        warmup_steps: int = 20,
        sustain_steps: int = 5,
        alpha: float = 0.2,
        max_captures: int = 1,
    ) -> None:
        self.regress_pct = float(regress_pct)
        self.warmup_steps = max(1, int(warmup_steps))
        self.sustain_steps = max(1, int(sustain_steps))
        self.alpha = float(alpha)
        self.baseline: Optional[float] = None
        self.ewma: Optional[float] = None
        self.anomalies = 0
        self._observed = 0
        self._degraded_run = 0
        self._in_episode = False
        self._captures_left = max(0, int(max_captures))

    def observe(self, dt: float) -> Optional[Dict[str, float]]:
        dt = float(dt)
        if dt < 0.0:
            return None
        self._observed += 1
        if self.ewma is None:
            self.ewma = dt
        else:
            self.ewma = self.alpha * dt + (1.0 - self.alpha) * self.ewma
        if self._observed <= self.warmup_steps:
            self.baseline = self.ewma
            return None
        assert self.baseline is not None
        threshold = self.baseline * (1.0 + self.regress_pct)
        if self.ewma > threshold:
            self._degraded_run += 1
            if self._degraded_run >= self.sustain_steps and not self._in_episode:
                self._in_episode = True
                self.anomalies += 1
                capture = self._captures_left > 0
                if capture:
                    self._captures_left -= 1
                return {
                    "baseline_s": self.baseline,
                    "ewma_s": self.ewma,
                    "regress_pct": self.regress_pct,
                    "degradation": self.ewma / self.baseline - 1.0,
                    "capture": capture,
                }
        else:
            self._degraded_run = 0
            self._in_episode = False  # recovered: re-arm for the next episode
        return None


# ----------------------------------------------------------------------- PerfPlane


class PerfPlane:
    """Per-process attribution plane owned by the training monitor.

    ``observe_step()`` per update feeds the regression watchdog;
    ``flush(metrics)`` at every log flush folds ``Perf/*`` gauges into the
    outgoing metric dict (reading the ``Time/*`` timers that were just drained
    into it) and pushes MFU/goodput to the active fleet exporter;
    ``write_report(path)`` emits ``perf_report.json`` at close.
    """

    def __init__(self, cfg: Any = None, role: str = "learner", log_dir: Optional[str] = None) -> None:
        global _scope_dir
        perf_cfg = _perf_cfg(cfg)
        self.enabled = perf_enabled(cfg)
        if self.enabled and log_dir:
            _scope_dir = os.path.join(str(log_dir), "scopes")
        self.role = role
        self.regress_pct = float(perf_cfg.get("regress_pct", 0.25) or 0.25)
        self.capture_updates = int(perf_cfg.get("capture_updates", 3) or 3)
        self.watchdog = StepTimeWatchdog(
            regress_pct=self.regress_pct,
            warmup_steps=int(perf_cfg.get("warmup_steps", 20) or 20),
            sustain_steps=int(perf_cfg.get("sustain_steps", 5) or 5),
            alpha=float(perf_cfg.get("ewma_alpha", 0.2) or 0.2),
            max_captures=int(perf_cfg.get("max_captures", 1) or 1),
        )
        self.ledger = GoodputLedger()
        self._start = time.monotonic()
        self._last_flush = self._start
        self._last_step: Optional[float] = None
        self._last_calls: Dict[str, int] = {}
        self._flops_total = 0.0
        self._bytes_total = 0.0
        self._device = None
        self.anomaly_events: List[Dict[str, float]] = []

    # ------------------------------------------------------------------ stepping

    def observe_step(self) -> Optional[Dict[str, float]]:
        """Per-update heartbeat; returns a regression event when one fires."""
        if not self.enabled:
            return None
        now = time.monotonic()
        if self._last_step is None:
            self._last_step = now
            return None
        dt, self._last_step = now - self._last_step, now
        event = self.watchdog.observe(dt)
        if event is not None:
            self.anomaly_events.append(event)
        return event

    # ------------------------------------------------------------------- flushing

    def device(self) -> Any:
        if self._device is None:
            self._device = _default_device()
        return self._device

    def flush(
        self,
        metrics: MutableMapping[str, Any],
        recompile_s: float = 0.0,
        downtime_s: float = 0.0,
    ) -> None:
        """Fold ``Perf/*`` gauges into ``metrics`` (already holding the drained
        ``Time/*`` timers) and push them to the active fleet exporter."""
        if not self.enabled:
            return
        now = time.monotonic()
        elapsed, self._last_flush = now - self._last_flush, now
        snapshot = registered_cost_models()
        delta_flops = delta_bytes = 0.0
        for name, entry in snapshot.items():
            delta_calls = entry["calls"] - self._last_calls.get(name, 0)
            self._last_calls[name] = entry["calls"]
            if delta_calls > 0:
                delta_flops += delta_calls * entry["flops"]
                delta_bytes += delta_calls * entry["bytes_accessed"]
        self._flops_total += delta_flops
        self._bytes_total += delta_bytes
        if elapsed > 0.0 and delta_flops > 0.0:
            achieved = delta_flops / elapsed
            metrics["Perf/achieved_flops_per_sec"] = achieved
            metrics["Perf/mfu"] = achieved / peak_flops(self.device())
            bw = peak_hbm_bw(self.device())
            if bw > 0.0:
                metrics["Perf/hbm_bw_util"] = (delta_bytes / elapsed) / bw
        fractions = self.ledger.classify(
            metrics, elapsed, recompile_s=recompile_s, downtime_s=downtime_s
        )
        metrics["Perf/goodput"] = fractions["compute"] + fractions["env"]
        for category, fraction in fractions.items():
            metrics[f"Perf/goodput_{category}"] = fraction
        metrics["Perf/anomalies"] = float(self.watchdog.anomalies)
        self._push_fleet(metrics)

    def _push_fleet(self, metrics: Mapping[str, Any]) -> None:
        try:
            from sheeprl_tpu.obs import fleet as obs_fleet

            exporter = obs_fleet.get_active()
        except Exception:
            return
        if exporter is None:
            return
        for key in ("Perf/mfu", "Perf/goodput", "Perf/hbm_bw_util"):
            if key in metrics:
                exporter.gauge(key, float(metrics[key]))
        exporter.gauge("perf_anomalies", float(self.watchdog.anomalies))

    # -------------------------------------------------------------------- report

    def report(self) -> Dict[str, Any]:
        # Fold any call deltas since the last flush so the exit report is
        # complete even when the run ends mid-window.
        for name, entry in registered_cost_models().items():
            delta_calls = entry["calls"] - self._last_calls.get(name, 0)
            self._last_calls[name] = entry["calls"]
            if delta_calls > 0:
                self._flops_total += delta_calls * entry["flops"]
                self._bytes_total += delta_calls * entry["bytes_accessed"]
        elapsed = max(1e-9, time.monotonic() - self._start)
        device = self.device()
        peak = peak_flops(device)
        achieved = self._flops_total / elapsed
        fractions = self.ledger.fractions()
        return {
            "role": self.role,
            "device_kind": str(getattr(device, "device_kind", "") or ""),
            "peak_flops": peak,
            "peak_hbm_bw": peak_hbm_bw(device),
            "elapsed_s": elapsed,
            "total_flops": self._flops_total,
            "total_bytes_accessed": self._bytes_total,
            "achieved_flops_per_sec": achieved,
            "mfu": achieved / peak if peak > 0 else 0.0,
            "hbm_bw_util": (self._bytes_total / elapsed) / peak_hbm_bw(device)
            if peak_hbm_bw(device) > 0
            else 0.0,
            "goodput": fractions["compute"] + fractions["env"],
            "goodput_fractions": fractions,
            "anomalies": self.watchdog.anomalies,
            "anomaly_events": list(self.anomaly_events),
            "cost_models": registered_cost_models(),
            "registration_failures": registration_failures(),
        }

    def write_report(self, path: str) -> Optional[str]:
        """Atomically write ``perf_report.json``; best-effort, returns the path.

        Skipped when no cost model ever registered and no anomaly fired — a
        process with no instrumented hot path has nothing to attribute, and a
        fully disabled monitor must leave its log dir untouched."""
        if not self.enabled or not path:
            return None
        if not registered_cost_models() and not self.watchdog.anomalies:
            return None
        try:
            _dump_json(path, self.report(), indent=1, sort_keys=True)
            return path
        except OSError:
            return None


def _dump_json(path: str, doc: Any, **kwargs: Any) -> None:
    """Write ``doc`` to ``path`` whole or not at all (a reader never sees half a file)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, **kwargs)
    os.replace(tmp, path)


def report_path(log_dir: Optional[str] = None) -> Optional[str]:
    """Resolve where ``perf_report.json`` goes: env override, then the run dir."""
    env = os.environ.get(PERF_REPORT_ENV_VAR)
    if env:
        return env
    if log_dir:
        return os.path.join(str(log_dir), "perf_report.json")
    return None
