"""Reduction of an XProf capture (``.xplane.pb``) to device metrics, with nothing but JAX
(``jax.profiler.ProfileData``).

* busy: the union of the intervals in which an operation ran on the device (the
  plane's "XLA Ops" line), clipped to the captured span; idle share = 1 - busy/span;
* per-kind sums: op events grouped by their name with the numeric suffix dropped
  (``fusion.123`` -> ``fusion``), so the table survives a recompile;
* module events (the plane's "XLA Modules" line): one per executed program, by name;
* the longest idle gaps, each labelled by the host span that covers most of it.

Event times in the file are nanoseconds from the start of the profiling session.  The
host's spans are on ``perf_counter``'s clock; the two are tied together by the host
plane's own ``start_trace`` event (its end is the moment ``start_trace`` returned).
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


class NoDevicePlane(RuntimeError):
    """The capture holds no ``/device:`` plane (a CPU run)."""

_SUFFIX = re.compile(r"(\(\d+\)|[.\-_]?\d+)$")


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def op_kind(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion``."""
    head = name.split(" = ")[0].strip().lstrip("%")
    prev = None
    while prev != head:
        prev, head = head, _SUFFIX.sub("", head)
    return head or name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def plane_events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    """``(name, start_s, end_s)`` of every event on the plane's line of that name."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for e in line.events:
            out.append((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return out


def device_summary(plane, span: Optional[Interval] = None) -> Dict[str, Any]:
    """Busy union, idle share, per-kind sums, module sums and idle gaps of one device."""
    ops = plane_events(plane, "XLA Ops")
    modules = plane_events(plane, "XLA Modules")
    if span is None:
        starts = [a for _, a, _ in ops + modules]
        ends = [b for _, _, b in ops + modules]
        span = (min(starts), max(ends)) if starts else (0.0, 0.0)
    lo, hi = span
    busy = union(clip([(a, b) for _, a, b in ops], lo, hi))
    # The ops of a loop's body lie inside the loop's own event on the same line: only
    # top-level events are summed, so the kinds add up to the busy time.  A loop is named
    # as the trace prints it (``while.232``): there are few, and each is a scan of the model.
    kinds: Dict[str, float] = {}
    edge = float("-inf")
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        if b <= edge:
            continue  # nested in the previous top-level event
        edge = max(edge, b)
        a, b = max(a, lo), min(b, hi)
        if b > a:
            k = op_kind(name)
            if k == "while":
                k = name.split(" = ")[0].strip().lstrip("%")
            kinds[k] = kinds.get(k, 0.0) + (b - a)
    mods: Dict[str, Dict[str, float]] = {}
    for name, a, b in modules:
        if a >= lo and b <= hi:  # whole executions only
            m = mods.setdefault(op_kind(name), {"seconds": 0.0, "count": 0})
            m["seconds"] += b - a
            m["count"] += 1
    gaps, edge = [], lo
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = b
    if hi > edge:
        gaps.append((edge, hi))
    window = hi - lo
    busy_s = total(busy)
    return {
        "span": span,
        "window_s": window,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window) if window > 0 else None,
        "kinds": kinds,
        "modules": mods,
        "gaps": gaps,
        "op_events": len(ops),
    }


def host_anchor(pd, name: str = "perfbench_anchor") -> Optional[float]:
    """The session time (s) at which the harness's anchor annotation began."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns * 1e-9
    return None


def label_gap(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    best, best_cover = "other host work", 0.0
    cover: Dict[str, float] = {}
    for label, a, b in host:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > 0:
            cover[label] = cover.get(label, 0.0) + c
    for label, c in cover.items():
        if c > best_cover:
            best, best_cover = label, c
    if best_cover < 0.5 * (gap[1] - gap[0]) and cover:
        return f"{best}+other"
    return best


def summarize(trace_dir: Path, trace_info: Dict[str, Any], host_intervals: Sequence[Tuple[str, float, float]]) -> Dict[str, Any]:
    """What the harness and the metric readers take from a traced run.

    ``trace_info``: the controller's record of the capture (``t_started``/``t1`` on
    ``perf_counter``'s clock).  ``host_intervals``: ``(label, t0, t1)`` host spans on
    the same clock."""
    pd = load(find_xplane(trace_dir))
    planes = [p for p in pd.planes if is_device_plane(p.name)]
    if not planes:
        raise NoDevicePlane(f"the capture holds no device plane: {[p.name for p in pd.planes]}")
    anchor = host_anchor(pd)
    t_started = trace_info.get("t_started", trace_info.get("t0"))
    span = None
    offset = None
    if anchor is not None and t_started is not None and "t1" in trace_info:
        offset = anchor - t_started  # session time = perf_counter time + offset
        span = (anchor, trace_info["t1"] + offset)
    per_device = [device_summary(p, span) for p in planes]
    n = len(per_device)
    busy_s = sum(d["busy_s"] for d in per_device) / n
    window_s = sum(d["window_s"] for d in per_device) / n
    first = per_device[0]
    host = []
    if offset is not None:
        host = [(label, a + offset, b + offset) for label, a, b in host_intervals]
    kinds = sorted(first["kinds"].items(), key=lambda kv: -kv[1])
    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:10]
    by_label: Dict[str, float] = {}
    for g in first["gaps"]:
        lab = label_gap(g, host)
        by_label[lab] = by_label.get(lab, 0.0) + (g[1] - g[0])
    return {
        "planes": [p.name for p in pd.planes],
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "kinds": dict(kinds),
        "modules": first["modules"],
        "idle_by_host_phase": by_label,
        "grad_steps": trace_info.get("grad_steps"),
        "anchored": offset is not None,
        "breakdown": {
            "device_ops": [[k, v] for k, v in kinds[:10]],
            "idle_gaps": [[label_gap(g, host), g[1] - g[0]] for g in gaps],
        },
    }
