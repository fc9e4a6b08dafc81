"""The benchmark's harness: one cell, one process, one JSON line.

Everything that belongs to one configuration, one traffic mix or one metric lives in
a file of its own that this module finds by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``, ``workloads/<cell>.json``,
``metrics/<metric>.json`` (+ the reader module the metric's file names).  Adding a
cell, a configuration or a metric needs no edit here, and neither does a cell of
another algorithm family: what belongs to a family (which overrides a traffic mix
becomes, which environment generates it, what the first three steps are compared on)
sits behind the configuration's ``adapter``, ``reference``, ``traffic_overrides`` and
generator; ``adapters/base.py`` says what this module calls.

A run: compose the cell's overrides, put the family's weights and recorder in place
(the adapter), call the program's normal entry ``sheeprl_tpu.cli.run`` in this
process, and let the loop prefill, compile and warm up.  The environment's clock
(``envs/clock.py``) opens the window once warm-up is over and closes it ``--seconds``
later, each time after draining the device; the run ends there.  Then: peak memory,
the trace's reduction (``--trace 1``), the plain reference's three steps and the
comparison that decides ``correct``.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from perfbench.envs import clock

ROOT = Path(__file__).resolve().parents[1]
#: everything a run writes (compile cache, logs, traces) goes under here, inside the checkout
OUT = ROOT / ".perfbench"
WARM_GRAD_STEPS = 4  # the three compared steps and one more
TRACE_SECONDS = 3.0
ANCHOR = "perfbench_anchor"  # the host annotation that ties perf_counter to the capture
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class WindowClosed(BaseException):
    """Raised from the environment's ``step()`` when the window has closed: the user's
    simulator ends the run.  Not an ``Exception``, so nothing in the program treats it
    as a crash to be retried or dumped."""


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def resolve(spec: str):
    """``"package.module:attr"`` -> the attribute; ``"package.module"`` -> the module."""
    module, _, attr = spec.partition(":")
    mod = importlib.import_module(module)
    return getattr(mod, attr) if attr else mod


# --------------------------------------------------------------------------- data files
class Cell:
    def __init__(self, name: str, root: Path = ROOT, bench: Optional[Path] = None):
        self.root = root
        self.bench = bench or root / "perfbench"
        self.benchmark = load_json(root / "BENCHMARK.json")
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in entries:
            raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json (have {sorted(entries)})")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.workload = load_json(self.bench / "workloads" / f"{name}.json")
        self.traffic_file = self.bench / "traffic" / f"{self.entry['traffic']}.json"
        self.traffic = load_json(self.traffic_file)
        cfg_entry = {c["name"]: c for c in self.benchmark["configs"]}[self.entry["config"]]
        self.config_file = root / cfg_entry["file"]
        self.config = load_json(self.config_file)

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``, each with its file."""
        out = []
        for m in self.benchmark[group]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            spec = load_json(self.bench / "metrics" / f"{m['name']}.json")
            out.append({**spec, **m})
        return out

    def sizes(self, rehearsal: bool) -> Dict[str, Any]:
        sizes = dict(self.config["sizes"])
        if rehearsal:
            sizes.update(self.config["rehearsal"]["sizes"])
        return sizes

    def limits(self, rehearsal: bool) -> Dict[str, float]:
        """The limits of ``correct``: the cell's own (its workload file; read on the chip
        in that cell, PERF.md), or the configuration's rehearsal limits on the CPU."""
        return self.config["rehearsal"]["limits"] if rehearsal else self.workload["limits"]

    def traffic_overrides(self, rehearsal: bool) -> List[str]:
        """The configuration's ``traffic_overrides`` filled from the traffic file's keys
        (``{num_envs}``) and from the sizes as run (``{sizes.<key>}``)."""
        sizes = types.SimpleNamespace(**self.sizes(rehearsal))
        out = []
        for template in self.config["traffic_overrides"]:
            try:
                out.append(template.format(sizes=sizes, **self.traffic))
            except (KeyError, AttributeError) as e:
                raise SystemExit(
                    f"perfbench: {self.config_file} asks for {template!r} in traffic_overrides; "
                    f"neither {self.traffic_file} nor the configuration's sizes fill it ({e!r})"
                )
        return out

    def overrides(self, seed: int, rehearsal: bool, cache_dir: Path, log_root: Path) -> List[str]:
        out = list(self.config["overrides"]) + self.traffic_overrides(rehearsal)
        out += [
            f"mesh.devices={self.chips}",
            f"seed={seed}",
            f"compile_cache.dir={cache_dir}",
            f"log_root={log_root}",
            "run_name=run",
        ]
        if rehearsal:
            out += list(self.config["rehearsal"]["overrides"])
        return out


class FullCollections:
    """Python's generation-2 garbage collections in this process, each with its start and
    its seconds: the cause of the rare far-off run (PERF.md, PR 24: a full collection
    stalls the host loop for as long as it scans the heap).  Printed, never a metric."""

    def __init__(self) -> None:
        self.events: List[List[float]] = []  # [start, seconds]
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.events.append([self._t0, time.perf_counter() - self._t0])

    def between(self, t0: float, t1: float) -> List[float]:
        return [round(1e3 * d, 1) for t, d in self.events if t0 <= t <= t1]

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


class CompileCounter:
    """The benchmark's own count of JAX's persistent-cache events in this process."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self) -> None:
        from jax import monitoring

        self.requests = self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: Any) -> None:
        field = self.EVENTS.get(event)
        if field:
            setattr(self, field, getattr(self, field) + 1)

    def snapshot(self) -> Dict[str, int]:
        return {"requests": self.requests, "hits": self.hits, "misses": self.misses}

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_listener(self._on_event)


# --------------------------------------------------------------------------- the clock
class Controller:
    """Env 0 calls :meth:`on_step` at the start of every ``step()``: the iteration
    clock as the environment sees it."""

    def __init__(self, adapter, compiles: CompileCounter, seconds: float, trace_dir: Optional[Path], t_process: float):
        self.adapter = adapter
        self.compiles = compiles
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.t_process = t_process
        self.state = "warmup"
        self.iteration = 0
        self.block_iters: List[int] = []
        self._blocks_seen = 0
        self._requests_seen = 0
        self.last_compile_iter = 0
        self._stop_logging = False
        self.stamps: List[float] = []
        self.open: Dict[str, Any] = {}
        self.close: Dict[str, Any] = {}
        self.player = _ResettingTimer("Time/phase_player")
        self.trace = {"state": "off" if trace_dir is None else "armed"}

    def _snapshot(self, now: float) -> Dict[str, Any]:
        return {
            "t": now,
            "grad_steps": self.adapter.grad_steps,
            "blocks": self.adapter.blocks,
            "compiles": self.compiles.snapshot(),
            "spans": {k: s.snapshot() for k, s in self.adapter.spans.items()},
            "player_s": self.player.total(),
            "env_s": sum(e.seconds for e in clock.ENVS),
            "env_steps": sum(e.steps for e in clock.ENVS),
        }

    def on_step(self, env) -> None:
        self.iteration += 1
        self.player.sample()
        if self._stop_logging:
            clock.LOG_ROWS = False
        elif self.adapter.captured():
            self._stop_logging = True  # this vector step still commits rows the third batch may hold
        if self.state == "warmup":
            if self.adapter.blocks != self._blocks_seen:
                self._blocks_seen = self.adapter.blocks
                self.block_iters.append(self.iteration)
            if self.compiles.requests != self._requests_seen:
                self._requests_seen = self.compiles.requests
                self.last_compile_iter = self.iteration
            if not self._warm():
                return
            if self.trace["state"] == "armed":
                self._start_capture()
                self.state = "capture"
                return
            self._open_window()
            return
        if self.state == "capture":
            if time.perf_counter() - self.trace["t_started"] >= TRACE_SECONDS:
                self._stop_capture()
                self._open_window()
            return
        now = time.perf_counter()
        if now - self.open["t"] >= self.seconds:
            self.adapter.drain()
            now = time.perf_counter()
            self.stamps.append(now)
            self.close = self._snapshot(now)
            self.close["xla_cost"] = _program_cost_models()
            self.state = "closed"
            raise WindowClosed()
        self.stamps.append(now)

    def _open_window(self) -> None:
        self.adapter.drain()
        now = time.perf_counter()
        self.open = self._snapshot(now)
        self.stamps = [now]
        self.state = "window"
        log(
            f"window opens at iteration {self.iteration}, {self.open['grad_steps']} gradient steps in, "
            f"setup {now - self.t_process:.1f}s, compile cache {self.open['compiles']}"
        )

    def _warm(self) -> bool:
        if self.adapter.grad_steps < WARM_GRAD_STEPS or len(self.block_iters) < 2 or not self.adapter.captured():
            return False
        period = self.block_iters[-1] - self.block_iters[-2]
        return self.iteration - self.last_compile_iter > period

    # The capture is a few seconds of the steady loop taken after warm-up and BEFORE the
    # window opens, so that starting and stopping the profiler (seconds, for a capture of
    # this size) stalls no iteration of the window: the traced run's host-clock metrics
    # then read what an untraced run's would.  Python's own tracer is off (it slows the
    # host loop it is meant to watch); the host's spans come from the benchmark's clock,
    # tied to the capture by one annotation.
    def _start_capture(self) -> None:
        import jax

        tr = self.trace
        self.adapter.drain()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation(ANCHOR):
            tr["t_started"] = time.perf_counter()
        tr["grad_steps0"] = self.adapter.grad_steps
        self.adapter.keep_intervals = clock.KEEP_INTERVALS = True
        tr["state"] = "on"

    def _stop_capture(self) -> None:
        import jax

        tr = self.trace
        self.adapter.drain()
        tr["grad_steps"] = self.adapter.grad_steps - tr["grad_steps0"]
        tr["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
        tr["stop_s"] = time.perf_counter() - tr["t1"]
        self.adapter.keep_intervals = clock.KEEP_INTERVALS = False
        tr["state"] = "done"
        log(f"captured {tr['t1'] - tr['t_started']:.2f}s, {tr['grad_steps']} gradient steps; stopping the profiler took {tr['stop_s']:.1f}s")


def _program_cost_models() -> Dict[str, Any]:
    """XLA's own ``cost_analysis()`` of the program's instrumented blocks, as the
    program registered it (printed beside the shape count, never used for a metric)."""
    try:
        from sheeprl_tpu.obs import perf

        return {k: {f: v.get(f) for f in ("flops", "bytes_accessed")} for k, v in perf.registered_cost_models().items()}
    except Exception as e:  # the print is a courtesy; a program without the registry still runs
        return {"unavailable": repr(e)}


class _ResettingTimer:
    """Follows one of the program's named timers across its resets at log flushes.
    Sampled once an iteration at a point where the timer is not running, after its
    increment of that iteration, so nothing is lost at a reset."""

    def __init__(self, name: str):
        from sheeprl_tpu.utils.timer import timer

        self.name = name
        self._registry = timer._registry  # cleared in place at a flush, never rebound
        self._acc = 0.0
        self._last = 0.0

    def sample(self) -> None:
        cur = float(self._registry.get(self.name, 0.0))
        if cur < self._last:
            self._acc += self._last
        self._last = cur

    def total(self) -> float:
        return self._acc + self._last


# --------------------------------------------------------------------------- a run
def device_report(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs), "memory_peak_bytes": peak}


def drive(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    rehearsal: bool = False,
    adapter_cls=None,
    t_process: Optional[float] = None,
    bench: Optional[Path] = None,
    root: Path = ROOT,
    extra_overrides: Sequence[str] = (),
) -> Dict[str, Any]:
    """Set the cell up, let the program run through warm-up and the window, and return
    the run's record: the window's counts and stamps, the device as JAX reports it, the
    capture's reduction (``trace``), and what the program produced in its first three
    gradient steps.  The program's state is gone when this returns.  ``adapter_cls``
    stands in for the configuration's adapter (the tests' planted faults)."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = Cell(workload, root=root, bench=bench)
    sizes = cell.sizes(rehearsal)

    cache_dir = OUT / "xla_cache"
    log_root = OUT / "logs" / workload
    trace_dir = OUT / "trace" / workload
    for d in (log_root, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    cache_dir.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("SHEEPRL_TPU_QUIET", "1")

    import jax

    platform = jax.devices()[0].platform
    if rehearsal:
        if platform != "cpu":
            log("a rehearsal runs on the CPU backend only. No result.")
            raise SystemExit(2)
    elif platform != "tpu" or len(jax.devices()) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(jax.devices())} x {platform!r}. No result.")
        raise SystemExit(2)

    from sheeprl_tpu import cli

    clock.reset_registry()
    compiles, collections = CompileCounter(), FullCollections()
    reference = resolve(cell.config["reference"])
    adapter = (adapter_cls or resolve(cell.config["adapter"]))(sizes, seed, reference)
    adapter.install()
    controller = Controller(adapter, compiles, seconds, trace_dir if trace else None, t_process)
    clock.HOOK = controller.on_step
    overrides = cell.overrides(seed, rehearsal, cache_dir, log_root) + list(extra_overrides)
    log(f"{workload} seed {seed}: " + " ".join(overrides))
    try:
        cli.run(overrides)
        raise RuntimeError("the program returned before the window closed")
    except WindowClosed:
        pass
    finally:
        adapter.uninstall()
        compiles.close()
        collections.close()
        clock.HOOK = None
        if controller.trace["state"] == "on":
            jax.profiler.stop_trace()

    program = adapter.program_readings()
    rows = adapter.rows()
    gc.collect()
    device = device_report(cell.chips)
    window = _window(controller, t_process)
    window["full_collections_ms"] = collections.between(controller.open["t"], controller.close["t"])
    run: Dict[str, Any] = {
        "cell": cell,
        "sizes": sizes,
        "seed": seed,
        "window": window,
        "device": device,
        "peaks": load_json(cell.bench / "peaks.json"),
        "trace": None,
        "adapter": adapter,
        "program": program,
        "rows": rows,
        "rehearsal": rehearsal,
        "traced": bool(trace),
    }
    log(
        f"window {window['seconds']:.3f}s: {window['iterations']} iterations, {window['grad_steps']} gradient steps "
        f"in {window['blocks']} blocks, compile requests inside the window {window['compile_requests']} "
        f"(misses {window['cache_misses']}); the three longest iterations "
        f"{[round(1e3 * g, 1) for g in sorted(window['gaps_s'])[-3:]]} ms, Python's full collections in the window "
        f"{window['full_collections_ms']} ms; the env's own step cost {window['env_step_ms']:.4f} ms; "
        f"cache at window open {window['compiles_at_open']}; rows written {window['rows_written_at_open']} at window open and "
        f"{window['rows_written_at_close']} at its close (a row a policy step); "
        + "".join(f"{k} {v}; " for k, v in adapter.facts().items())
        + f"memory peak {device['memory_peak_bytes'] / 2**30:.3f} GiB"
    )
    log(
        f"one gradient step by shapes ({cell.config['flops']}): {resolve(cell.config['flops'])(sizes)['total']:.4e} flops; "
        f"XLA's cost_analysis as the program registered it: {json.dumps(window['xla_cost'])}"
    )
    if trace:
        from perfbench.readers import xplane

        try:
            run["trace"] = xplane.summarize(trace_dir, controller.trace, adapter.intervals + clock.INTERVALS)
        except xplane.NoDevicePlane:
            if not rehearsal:
                raise
            log("rehearsal: the CPU capture holds no device plane; trace metrics are left out")
        if run["trace"] is not None:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            log("trace: " + json.dumps({k: v for k, v in run["trace"].items() if k != "breakdown"}))
    return run


def judge(run: Dict[str, Any]) -> Dict[str, Any]:
    """Follow the program's three steps with the plain reference and hold each number
    compared against its limit."""
    from perfbench import check

    t0 = time.perf_counter()
    adapter, program = run["adapter"], run["program"]
    ref_out = adapter.reference_readings(run["rows"], program)
    prog_out = {
        "loss": [s["loss"] for s in program["steps"]],
        "grad_norms": program["grad_norms"],
        "change_norms": program["change_norms"],
    }
    numbers = check.compare(prog_out, ref_out, **adapter.compared())
    judged = check.verdict(numbers, run["cell"].limits(run["rehearsal"]))
    log(f"reference followed three steps in {time.perf_counter() - t0:.1f}s; all numbers: {json.dumps(numbers)}")
    log(f"what the three steps exercised: {json.dumps(adapter.coverage(ref_out))}")
    for i, (p, r) in enumerate(zip(prog_out["loss"], ref_out["loss"])):
        log(f"step {i + 1} loss: program {json.dumps(p, sort_keys=True)} reference {json.dumps(r, sort_keys=True)}")
    judged["numbers"] = numbers
    judged["reference"] = ref_out
    # the raw readings of every run are kept beside the logs: limits are set from them
    out = OUT / "readings"
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{run['cell'].name}.{run['seed']}.json", "w") as f:
        reported = [s["reported"] for s in program["steps"]]
        json.dump({"seed": run["seed"], "numbers": numbers, "program": plain(prog_out), "reference": plain(ref_out), "reported": reported}, f)
    return judged


def plain(readings: Dict[str, Any]) -> Dict[str, Any]:
    return {k: (v.tolist() if hasattr(v, "tolist") else v) for k, v in readings.items()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, rehearsal: bool = False, **kwargs) -> Dict[str, Any]:
    """Run one cell; returns the result object (the caller prints it as the last line)."""
    return report(drive(workload, seed, seconds, trace, rehearsal=rehearsal, **kwargs))


def report(run: Dict[str, Any]) -> Dict[str, Any]:
    """The result object of a run: its metrics through their readers, and the verdict."""
    cell, device, rehearsal, trace = run["cell"], run["device"], run["rehearsal"], run["traced"]
    metrics: Dict[str, Any] = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        value = resolve(m["reader"])(run)
        if value is None:
            continue
        metrics[("rehearsal." if rehearsal else "") + m["name"]] = {"value": value, "unit": m["unit"]}
    judged = run["judged"] = judge(run)
    result = {
        "correct": judged["correct"],
        "attempted": run["window"]["grad_steps"],
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if run["trace"] is not None:
        result["breakdown"] = run["trace"]["breakdown"]
    result["compared"] = judged["compared"]
    for name, c in judged["compared"].items():
        print(f"perfbench: compared {name} = {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    return result


def _window(c: Controller, t_process: float) -> Dict[str, Any]:
    o, z = c.open, c.close
    gaps = [b - a for a, b in zip(c.stamps[:-1], c.stamps[1:])]
    iters = len(gaps)
    env_steps = z["env_steps"] - o["env_steps"]
    spans = {
        k: {f: z["spans"][k][f] - o["spans"][k][f] for f in ("seconds", "calls")} for k in z["spans"]
    }
    return {
        "seconds": z["t"] - o["t"],
        "setup_s": o["t"] - t_process,
        "iterations": iters,
        "gaps_s": gaps,
        "env_steps": env_steps,
        "rows_written_at_open": o["env_steps"],
        "rows_written_at_close": z["env_steps"],
        "grad_steps": z["grad_steps"] - o["grad_steps"],
        "blocks": z["blocks"] - o["blocks"],
        "compile_requests": z["compiles"]["requests"] - o["compiles"]["requests"],
        "cache_misses": z["compiles"]["misses"] - o["compiles"]["misses"],
        "compiles_at_open": o["compiles"],
        "spans": spans,
        "player_s": z["player_s"] - o["player_s"],
        "env_step_ms": 1e3 * (z["env_s"] - o["env_s"]) / max(env_steps, 1),
        "xla_cost": z.get("xla_cost", {}),
    }


def emit(result: Dict[str, Any]) -> None:
    """The contract's one JSON line, last on standard output."""
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
