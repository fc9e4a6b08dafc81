"""Reduction of a capture by the program's own names: its spans on the host plane, its
scopes on the device plane, and the device's idle gaps labelled by the spans.

The program writes every ``timer`` / ``span`` block into the profiler's trace as an
annotation under the span's name (``sheeprl_tpu/obs/tracer.py``), so the host's spans
and the device's ops lie on one clock and no offset is needed.  A device op event is
named by its HLO instruction only; ``<log_dir>/scopes/<program>.json``, which the
program writes when it registers a compiled block (``sheeprl_tpu/obs/perf.py``), maps
the instruction to the program's scope (``world_model/rssm``, ``health``, ...).

* host: whole events named ``Time/...`` or ``Rollout/...`` on any host thread, by
  name: seconds, calls, nesting depth.  The window is the hull of those events;
* device: op events inside module executions that lie whole inside the window and
  whose module has a scope map.  Every nanosecond of busy time goes to one event (the
  one that started last; a loop's time that its body does not cover is the loop's
  own), and each event's time to its instruction's scopes and directions by the map's
  shares (a fusion that holds instructions of two scopes is split between them), or
  to ``unscoped`` (the map lists the instructions without a scope of their own that
  inherited from the instruction they feed; the table says how much time came that
  way);
* idle: the gaps of the device's busy union inside the window, each labelled by the
  deepest span that covers more than half of it.

A program without the spans or the map (an older commit) gives empty tables, and every
metric reader here then returns ``None``.
"""

from __future__ import annotations

import bisect
import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from perfbench.readers import xplane

SPAN_PREFIXES = ("Time/", "Rollout/")
UNSCOPED = "unscoped"
Event = Tuple[str, float, float]  # name, start s, end s

_CACHE: Dict[Any, Dict[str, Any]] = {}


# --------------------------------------------------------------------------- pieces
def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def self_seconds(events: Sequence[Event]) -> List[float]:
    """For each event, the seconds in which it is the innermost one running: at any
    moment the time goes to the running event that started last."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [0.0] * len(events)
    stack: List[int] = []
    cursor = float("-inf")
    for i in order + [None]:
        until = float("inf") if i is None else events[i][1]
        while stack:
            top = stack[-1]
            end = events[top][2]
            if end <= cursor:
                stack.pop()
                continue
            if cursor >= until:
                break
            step = min(end, until)
            own[top] += step - cursor
            cursor = step
        if i is not None:
            cursor = max(cursor, until)
            stack.append(i)
    return own


def host_spans(pd) -> List[Tuple[str, float, float, int]]:
    """``(name, start, end, depth)`` of the program's spans on every host thread."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            events = sorted(
                ((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events if e.name.startswith(SPAN_PREFIXES)),
                key=lambda e: (e[1], -e[2]),
            )
            open_ends: List[float] = []
            for name, a, b in events:
                while open_ends and open_ends[-1] <= a:
                    open_ends.pop()
                out.append((name, a, b, len(open_ends)))
                open_ends.append(b)
    return out


def label_gap(gap: xplane.Interval, spans: Sequence[Tuple[str, float, float, int]]) -> Tuple[str, Dict[str, float]]:
    """The deepest span that covers more than half of the gap (``"no span"`` where none
    does), and the seconds of the gap under each span that touches it."""
    cover: Dict[str, float] = {}
    depth: Dict[str, int] = {}
    for name, a, b, d in spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > 0:
            cover[name] = cover.get(name, 0.0) + c
            depth[name] = max(depth.get(name, 0), d)
    most = [n for n, c in cover.items() if c > 0.5 * (gap[1] - gap[0])]
    label = max(most, key=lambda n: (depth[n], cover[n])) if most else "no span"
    return label, cover


ScopeMap = Dict[str, Any]  # {"ops": {instruction: {"<scope> <fwd|bwd>": share}}, "inherited": {instruction, ...}}


def load_scope_maps(root: Optional[Path]) -> Dict[str, ScopeMap]:
    """HLO module name -> its scope map, from every ``scopes/**/*.json`` under ``root``
    (or from ``root`` itself, if it is such a file or a ``scopes`` directory)."""
    if root is None:
        return {}
    root = Path(root)
    files = [root] if root.is_file() else sorted(root.glob("**/*.json" if root.name == "scopes" else "**/scopes/**/*.json"))
    maps: Dict[str, ScopeMap] = {}
    for path in files:
        with open(path) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and "module" in doc and "ops" in doc:
            m = maps.setdefault(doc["module"], {"ops": {}, "inherited": set()})
            m["ops"].update(doc["ops"])
            m["inherited"].update(doc.get("inherited", ()))
    return maps


# --------------------------------------------------------------------------- the reduction
def reduce_capture(pd, maps: Dict[str, ScopeMap]) -> Dict[str, Any]:
    spans = host_spans(pd)
    by_name: Dict[str, Dict[str, float]] = {}
    for name, a, b, d in spans:
        s = by_name.setdefault(name, {"seconds": 0.0, "calls": 0, "depth": d})
        s["seconds"] += b - a
        s["calls"] += 1
        s["depth"] = min(s["depth"], d)

    planes = [p for p in pd.planes if xplane.is_device_plane(p.name)]
    ops = xplane.plane_events(planes[0], "XLA Ops") if planes else []
    modules = xplane.plane_events(planes[0], "XLA Modules") if planes else []
    if spans:
        window = (min(a for _, a, _, _ in spans), max(b for _, _, b, _ in spans))
    elif ops:
        window = (min(a for _, a, _ in ops), max(b for _, _, b in ops))
    else:
        window = (0.0, 0.0)
    lo, hi = window

    # device time by module and scope, inside whole executions of mapped modules
    ops.sort(key=lambda e: e[1])
    starts = [a for _, a, _ in ops]
    device: Dict[str, Dict[str, Any]] = {}
    for name, a, b in modules:
        kind = xplane.op_kind(name)
        if kind not in maps or a < lo or b > hi:
            continue
        inside = [e for e in ops[bisect.bisect_left(starts, a) : bisect.bisect_right(starts, b)] if e[2] <= b]
        m = device.setdefault(kind, {"executions": 0, "module_s": 0.0, "busy_s": 0.0, "inherited_s": 0.0, "op_events": 0, "fewest_op_events": len(inside), "scopes": {}})
        m["executions"] += 1
        m["module_s"] += b - a
        m["op_events"] += len(inside)
        m["fewest_op_events"] = min(m["fewest_op_events"], len(inside))
        shares_of, inherited = maps[kind].get("ops", {}), set(maps[kind].get("inherited", ()))
        for (op_name, _, _), own in zip(inside, self_seconds(inside)):
            name = instruction(op_name)
            for key, share in (shares_of.get(name) or {UNSCOPED: 1.0}).items():
                m["scopes"][key] = m["scopes"].get(key, 0.0) + own * share
            m["busy_s"] += own
            if name in inherited:  # no scope of its own: the map gave it that of the instruction it feeds
                m["inherited_s"] += own

    # idle gaps of the whole device inside the window, by span
    busy = xplane.union(xplane.clip([(a, b) for _, a, b in ops], lo, hi))
    gaps, edge = [], lo
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if hi > edge:
        gaps.append((edge, hi))
    idle_by_span: Dict[str, float] = {}
    labelled = []
    by_start = sorted(spans, key=lambda s: s[1])
    touching: List[Tuple[str, float, float, int]] = []  # the spans that can touch the gap at hand
    upcoming = 0
    for g in gaps:
        while upcoming < len(by_start) and by_start[upcoming][1] < g[1]:
            touching.append(by_start[upcoming])
            upcoming += 1
        touching = [s for s in touching if s[2] > g[0]]
        label, cover = label_gap(g, touching)
        idle_by_span[label] = idle_by_span.get(label, 0.0) + (g[1] - g[0])
        labelled.append((g[1] - g[0], label, cover))
    labelled.sort(key=lambda x: -x[0])
    return {
        "window_s": hi - lo,
        "spans": by_name,
        "device": device,
        "busy_s": xplane.total(busy),
        "idle_s": sum(g[1] - g[0] for g in gaps),
        "idle_by_span": idle_by_span,
        "longest_gaps": [{"seconds": s, "span": label, "under": cover} for s, label, cover in labelled[:10]],
    }


def tables(red: Dict[str, Any], steps_per_execution: float = 1.0) -> List[str]:
    """The reduction as lines of text: scope x direction in ms a gradient step; span,
    ms a call, calls; idle seconds by span; the ten longest gaps."""
    out = [f"window {red['window_s']:.4f}s, device busy {red['busy_s']:.4f}s, idle {red['idle_s']:.4f}s"]
    for module, m in sorted(red["device"].items()):
        steps = m["executions"] * steps_per_execution
        out.append(
            f"device, {module}: {m['executions']} whole executions ({steps:g} gradient steps), {m['op_events']} op events, "
            f"module {1e3 * m['module_s'] / steps:.3f} ms a step, attributed {1e3 * m['busy_s'] / steps:.3f} ms a step "
            f"(of which to a scope inherited from a neighbour {1e3 * m['inherited_s'] / steps:.3f})"
        )
        if m["fewest_op_events"] * m["executions"] != m["op_events"]:
            # seen once on the chip (PERF.md, PR 25): 4.5 steps' device events missing from a capture, the module events around them long
            out.append(f"  executions differ in their op events (fewest {m['fewest_op_events']}): the capture lost device events, or the module ran more than one program; read no time off this capture")
        for key, s in sorted(m["scopes"].items(), key=lambda kv: -kv[1]):
            out.append(f"  {key:<32s} {1e3 * s / steps:9.3f} ms a step  {100 * s / max(m['busy_s'], 1e-30):6.2f}%")
    out.append("host spans (name, ms a call, calls, depth):")
    for name, s in sorted(red["spans"].items(), key=lambda kv: (kv[1]["depth"], -kv[1]["seconds"])):
        out.append(f"  {name:<32s} {1e3 * s['seconds'] / s['calls']:9.3f} ms  {s['calls']:6d}  {s['depth']}")
    out.append("device idle by the deepest span covering most of each gap (s): " + json.dumps({k: round(v, 6) for k, v in sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])}))
    for g in red["longest_gaps"]:
        under = {k: round(1e3 * v, 3) for k, v in sorted(g["under"].items(), key=lambda kv: -kv[1])}
        out.append(f"  gap {1e3 * g['seconds']:8.3f} ms under {g['span']}: {json.dumps(under)}")
    return out


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of this run's capture (parsed once in a process, and printed), or
    ``None`` where the run kept none."""
    from perfbench import harness

    if not run.get("traced"):
        return None
    cell = run["cell"].name
    try:
        path = xplane.find_xplane(harness.OUT / "trace" / cell)
    except FileNotFoundError:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        red = reduce_capture(xplane.load(path), load_scope_maps(harness.OUT / "logs" / cell))
        w = run["window"]
        red["steps_per_execution"] = w["grad_steps"] / w["blocks"] if w.get("blocks") else 1.0
        for line in tables(red, red["steps_per_execution"]):
            harness.log("spans " + line)
        _beside(run, red)
        _CACHE[key] = red
    return _CACHE[key]


def _beside(run: Dict[str, Any], red: Dict[str, Any]) -> None:
    """The program's own spans at the two places where the benchmark times from outside:
    over the same calls (the benchmark keeps its intervals while the capture runs), and
    beside the window's metric, which an untraced stretch of the loop gives."""
    from perfbench import harness
    from perfbench.readers import basic

    kept = getattr(run.get("adapter"), "intervals", [])
    for span, label, outside in (("Time/phase_dispatch", "dispatch", basic.dispatch_ms), ("Time/phase_buffer_add", "buffer_add", basic.buffer_add_ms)):
        s = red["spans"].get(span)
        if s:
            same = [t1 - t0 for name, t0, t1 in kept if name == label]
            harness.log(
                f"spans {span} {1e3 * s['seconds'] / s['calls']:.3f} ms a call over {s['calls']} calls in the capture; the benchmark's own "
                f"pair around the same place {1e3 * sum(same) / len(same) if same else float('nan'):.3f} ms over {len(same)} calls in the capture, "
                f"{outside.__name__} over the window {outside(run)}"
            )


# --------------------------------------------------------------------------- metric readers
def _span_ms(run: Dict[str, Any], name: str, per: Optional[str] = None) -> Optional[float]:
    """Seconds of the span ``name`` over the calls of the span ``per`` (default: its own), in ms."""
    red = of_run(run)
    if red is None:
        return None
    s, p = red["spans"].get(name), red["spans"].get(per or name)
    if not s or not p:
        return None
    return 1e3 * s["seconds"] / p["calls"]


def action_wait_ms(run):
    """The player's wait for its action (which waits for the block before it), an iteration."""
    return _span_ms(run, "Rollout/action_fetch", per="Time/phase_player")


def dispatch_sample_ms(run):
    return _span_ms(run, "Time/dispatch_sample")


def dispatch_stage_ms(run):
    return _span_ms(run, "Time/dispatch_stage", per="Time/dispatch_sample")


def dispatch_call_ms(run):
    """The jitted call(s) of a block, summed over its chunks."""
    return _span_ms(run, "Time/dispatch_call", per="Time/dispatch_sample")


def _block_scopes(run: Dict[str, Any]) -> Optional[Tuple[Dict[str, float], float, float]]:
    """Seconds by ``scope direction`` over the train block's whole executions, their sum,
    and the gradient steps they held."""
    red = of_run(run)
    if red is None:
        return None
    scopes: Dict[str, float] = {}
    steps = 0.0
    for module, m in red["device"].items():
        if "block" in module:
            steps += m["executions"] * red["steps_per_execution"]
            for key, s in m["scopes"].items():
                scopes[key] = scopes.get(key, 0.0) + s
    total = sum(scopes.values())
    return (scopes, total, steps) if steps and total > 0 else None


def _scope_ms(run: Dict[str, Any], *names: str) -> Optional[float]:
    found = _block_scopes(run)
    if found is None:
        return None
    scopes, _, steps = found
    seconds = sum(s for key, s in scopes.items() if any(f"{key.split(' ')[0]}/".startswith(f"{n}/") for n in names))
    return 1e3 * seconds / steps


def rssm_device_ms(run):
    return _scope_ms(run, "world_model/rssm")


def imagination_device_ms(run):
    return _scope_ms(run, "imagination")


def optimizer_device_ms(run):
    return _scope_ms(run, "wm_optimizer", "actor_optimizer", "critic_optimizer")


def health_device_ms(run):
    return _scope_ms(run, "health")


def unscoped_device_share(run):
    found = _block_scopes(run)
    if found is None:
        return None
    scopes, total, _ = found
    return 100.0 * scopes.get(UNSCOPED, 0.0) / total
