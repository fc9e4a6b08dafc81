"""Per-layer metrics of recurrent PPO's host loop (``sheeprl_tpu/algos/ppo_recurrent/
ppo_recurrent.py::main``), read from a capture by the program's own spans beside the
device's events, and the share of the device's idle time that no span names (any cell).

An acting iteration, inside ``Time/env_interaction_time``: ``Rollout/act_call`` (the step's
host arrays in, the launch), the device's execution of ``jit_act``, ``Rollout/action_fetch``
(which waits for it and brings its result back), then the host's ``Rollout/env_step``,
``Rollout/truncation_value`` (only where an episode was cut) and ``Rollout/store``.  Between
two rollouts, the update boundary: ``Time/rollout_prep``, ``Time/update_prep``,
``Time/update_call`` and ``Time/update_fetch`` (both inside ``Time/train_time``) and
``Time/update_after``.

Host spans and device events lie on one clock (``readers/spans.py``).  What
``spans.of_run``'s reduction holds (seconds and calls by span, idle by span) is read from it;
pairing a call with its execution, the rollouts as wholes and the idle between them need the
events themselves, which this module takes from the capture once in a process and reads in
time-ordered sweeps (a bisection into the device's busy time where an interval asks for its
idle seconds): linear in the events, never a gap against a span.  A reader gives ``None``
where the capture lacks the spans it reads: an older commit has the acting call and its
fetch, so the round trip reads there, and none of the host's parts or the boundary's spans.
"""

from __future__ import annotations

import bisect
import time
from typing import Any, Dict, List, Optional, Tuple

from perfbench.readers import spans, xplane

ACTING = "jit_act"  # the HLO module of the jitted acting step
ROLLOUT, CALL, FETCH = "Time/env_interaction_time", "Rollout/act_call", "Rollout/action_fetch"
HOST_PARTS = ("Rollout/env_step", "Rollout/truncation_value", "Rollout/store")
BOUNDARY = ("Time/rollout_prep", "Time/update_prep", "Time/update_call", "Time/update_fetch", "Time/update_after")

_CACHE: Dict[Any, Dict[str, Any]] = {}


def _log(msg: str) -> None:
    from perfbench import harness

    harness.log("rollout " + msg)


# --------------------------------------------------------------------------- the capture's events
def events(pd) -> Dict[str, Any]:
    """The program's spans ``(name, start, end, depth)`` in start order, the ``jit_act``
    executions ``(start, end)`` and the device's busy union with its running sum."""
    host = sorted(spans.host_spans(pd), key=lambda s: (s[1], -s[2]))
    planes = [p for p in pd.planes if xplane.is_device_plane(p.name)]
    acts: List[Tuple[float, float]] = []
    busy: List[Tuple[float, float]] = []
    if planes:
        acts = sorted((a, b) for name, a, b in xplane.plane_events(planes[0], "XLA Modules") if xplane.op_kind(name) == ACTING)
        busy = xplane.union((a, b) for _, a, b in xplane.plane_events(planes[0], "XLA Ops"))
    before = [0.0]
    for a, b in busy:
        before.append(before[-1] + (b - a))
    return {"host": host, "acts": acts, "busy": busy, "busy_starts": [a for a, _ in busy], "busy_before": before}


def _capture(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """:func:`events` of this run's capture, read once in a process; ``None`` where the run
    kept none."""
    from perfbench import harness

    if not run.get("traced"):
        return None
    try:
        path = xplane.find_xplane(harness.OUT / "trace" / run["cell"].name)
    except FileNotFoundError:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        t0 = time.perf_counter()
        _CACHE[key] = events(xplane.load(path))
        ev = _CACHE[key]
        _log(f"capture read in {time.perf_counter() - t0:.2f}s: {len(ev['host'])} spans, {len(ev['acts'])} {ACTING} executions, {len(ev['busy'])} busy intervals")
    return _CACHE[key]


def idle_seconds(ev: Dict[str, Any], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which no operation ran on the device."""

    def busy_until(t: float) -> float:
        i = bisect.bisect_right(ev["busy_starts"], t)
        if i == 0:
            return 0.0
        a, b = ev["busy"][i - 1]
        return ev["busy_before"][i - 1] + (min(b, t) - a)

    return max(hi - lo, 0.0) - (busy_until(hi) - busy_until(lo))


def acting_steps(ev: Dict[str, Any]) -> List[Tuple[float, float, float, float]]:
    """``(call end, execution start, execution end, fetch end)`` of each acting step: each
    ``Rollout/act_call`` with the first ``jit_act`` execution that starts after the call
    began and the first ``Rollout/action_fetch`` that starts after it ended, both before
    the next call begins (a step the capture holds in part is left out)."""
    calls = [(a, b) for name, a, b, _ in ev["host"] if name == CALL]
    fetches = [(a, b) for name, a, b, _ in ev["host"] if name == FETCH]
    acts = ev["acts"]
    out = []
    j = k = 0
    for i, (a, b) in enumerate(calls):
        limit = calls[i + 1][0] if i + 1 < len(calls) else float("inf")
        while j < len(acts) and acts[j][0] < a:
            j += 1
        while k < len(fetches) and fetches[k][0] < b:
            k += 1
        if j < len(acts) and k < len(fetches) and acts[j][0] < limit and fetches[k][0] < limit:
            out.append((b, acts[j][0], acts[j][1], fetches[k][1]))
            j += 1
            k += 1
    return out


def rollouts(ev: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each whole ``Time/env_interaction_time`` in the capture: its start, end, and the
    seconds and calls of each span directly inside it."""
    out: List[Dict[str, Any]] = []
    current = None
    for name, a, b, d in ev["host"]:
        if name == ROLLOUT:
            current = {"start": a, "end": b, "depth": d, "parts": {}}
            out.append(current)
        elif current is not None and a >= current["start"] and b <= current["end"] and d == current["depth"] + 1:
            part = current["parts"].setdefault(name, [0.0, 0])
            part[0] += b - a
            part[1] += 1
    return out


# --------------------------------------------------------------------------- metric readers
def _steps(run: Dict[str, Any]) -> Optional[List[Tuple[float, float, float, float]]]:
    ev = _capture(run)
    if ev is None:
        return None
    return acting_steps(ev) or None


def act_launch_ms(run):
    """From the end of ``Rollout/act_call`` to the start of its ``jit_act`` execution, a
    step; negative where the device began before the call returned."""
    steps = _steps(run)
    if steps is None:
        return None
    launch = sorted(x - b for b, x, _, _ in steps)
    _log(f"{len(steps)} acting steps paired; launch median {1e3 * launch[len(launch) // 2]:.4f} ms, {sum(v < 0 for v in launch)} negative")
    return 1e3 * sum(launch) / len(launch)


def act_return_ms(run):
    """From the end of the ``jit_act`` execution to the end of the ``Rollout/action_fetch``
    that follows its call, a step."""
    steps = _steps(run)
    if steps is None:
        return None
    return 1e3 * sum(f - y for _, _, y, f in steps) / len(steps)


def rollout_host_ms(run):
    """``Rollout/env_step`` + ``Rollout/truncation_value`` + ``Rollout/store`` seconds over
    the ``Rollout/act_call`` calls, in ms: the host's work of an acting iteration after the
    fetch.  Logged beside it: each part, the bootstrap's calls a rollout, what of a whole
    rollout no child names, and the iteration as its parts add it up."""
    red = spans.of_run(run)
    if red is None or CALL not in red["spans"] or not any(p in red["spans"] for p in HOST_PARTS):
        return None
    calls = red["spans"][CALL]["calls"]
    parts = {p: 1e3 * red["spans"][p]["seconds"] / calls for p in HOST_PARTS if p in red["spans"]}
    value = sum(parts.values())
    _log("host parts of an acting iteration (ms): " + ", ".join(f"{p} {v:.4f}" for p, v in parts.items()))
    ev = _capture(run)
    whole = rollouts(ev) if ev is not None else []
    whole = [r for r in whole if r["parts"].get(CALL)]
    if whole:
        seconds = sum(r["end"] - r["start"] for r in whole)
        n = sum(r["parts"][CALL][1] for r in whole)
        named = sum(s for r in whole for s, _ in r["parts"].values())
        bootstraps = [r["parts"].get("Rollout/truncation_value", [0.0, 0])[1] for r in whole]
        iteration = 1e3 * seconds / n
        _log(
            f"{len(whole)} whole rollouts, {n} acting calls: an iteration {iteration:.4f} ms, of which no child span {1e3 * (seconds - named) / n:.4f} ms; "
            f"bootstrap calls a rollout {sum(bootstraps) / len(whole):.3f} (at most {max(bootstraps)})"
        )
        steps = acting_steps(ev)
        if steps:
            call = 1e3 * red["spans"][CALL]["seconds"] / calls
            launch = 1e3 * sum(x - b for b, x, _, _ in steps) / len(steps)
            device = 1e3 * sum(y - x for _, x, y, _ in steps) / len(steps)
            back = 1e3 * sum(f - y for _, _, y, f in steps) / len(steps)
            total = call + launch + device + back + value
            _log(
                f"the iteration closes: act_call {call:.4f} + launch {launch:.4f} + {ACTING} {device:.4f} + return {back:.4f} + host {value:.4f} "
                f"= {total:.4f} ms against {iteration:.4f} ({100 * (total / iteration - 1):+.2f} %)"
            )
    return value


def update_boundary_idle_ms(run):
    """Device idle between the end of one whole ``Time/env_interaction_time`` and the start
    of the next, in ms, over such pairs; logged by the five boundary spans."""
    ev = _capture(run)
    if ev is None or not ev["busy"] or not any(name in BOUNDARY for name, _, _, _ in ev["host"]):
        return None
    whole = rollouts(ev)
    pairs = [(r["end"], s["start"]) for r, s in zip(whole, whole[1:])]
    if not pairs:
        return None
    idle = [idle_seconds(ev, lo, hi) for lo, hi in pairs]
    under = dict.fromkeys(BOUNDARY, 0.0)
    p = 0
    for name, a, b, _ in ev["host"]:  # start order, as the pairs are
        if name not in under:
            continue
        while p < len(pairs) and pairs[p][1] <= a:
            p += 1
        if p < len(pairs) and a >= pairs[p][0]:
            under[name] += idle_seconds(ev, a, min(b, pairs[p][1]))
    n = len(pairs)
    rest = sum(idle) - sum(under.values())
    _log(
        f"{n} update boundaries of {1e3 * sum(hi - lo for lo, hi in pairs) / n:.3f} ms each, device idle (ms a boundary): "
        + ", ".join(f"{k} {1e3 * v / n:.4f}" for k, v in under.items())
        + f", under none of them {1e3 * rest / n:.4f}"
    )
    return 1e3 * sum(idle) / n


def idle_unspanned_share(run):
    """% of the capture's device idle seconds in gaps that no span covers more than half of
    (``spans.reduce_capture``'s ``no span``)."""
    red = spans.of_run(run)
    if red is None or not red["spans"] or red["busy_s"] <= 0 or red["idle_s"] <= 0:
        return None
    return 100.0 * red["idle_by_span"].get("no span", 0.0) / red["idle_s"]
