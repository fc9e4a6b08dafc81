"""One small reader per metric: each takes the run's record and returns the metric's
value, or ``None`` where it finds nothing to read (the harness then leaves the metric
out of the line; a share of a peak is never reported as 0).

``run`` holds: ``window`` (seconds, iterations, gaps_s, env_steps, grad_steps, blocks,
spans, player_s, setup_s, compiles_at_open), ``device`` (as JAX reports it, with
``memory_peak_bytes``), ``trace`` (the capture's reduction, ``--trace 1`` only),
``sizes`` (the configuration as run), ``peaks`` and ``cell``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _percentile(values, q: float) -> Optional[float]:
    import numpy as np

    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---- end to end (host clock; all the work of the window over all its seconds)
def grad_steps_per_s(run: Dict[str, Any]) -> Optional[float]:
    w = run["window"]
    return w["grad_steps"] / w["seconds"] if w["grad_steps"] else None


def env_steps_per_s(run: Dict[str, Any]) -> Optional[float]:
    w = run["window"]
    return w["env_steps"] / w["seconds"] if w["env_steps"] else None


def iter_ms_p95(run: Dict[str, Any]) -> Optional[float]:
    p = _percentile(run["window"]["gaps_s"], 95.0)
    return None if p is None else 1e3 * p


def setup_s(run: Dict[str, Any]) -> Optional[float]:
    return run["window"]["setup_s"]


# ---- per layer
def cache_misses_warm(run: Dict[str, Any]) -> Optional[float]:
    return float(run["window"]["compiles_at_open"]["misses"])


def player_ms(run: Dict[str, Any]) -> Optional[float]:
    """The program's ``Time/phase_player`` less the ring append nested in it, an iteration."""
    w = run["window"]
    acting = w["player_s"] - w["spans"]["buffer_add"]["seconds"]
    if w["player_s"] <= 0 or not w["iterations"]:
        return None
    return 1e3 * acting / w["iterations"]


def buffer_add_ms(run: Dict[str, Any]) -> Optional[float]:
    w = run["window"]
    s = w["spans"]["buffer_add"]
    return 1e3 * s["seconds"] / w["iterations"] if s["calls"] and w["iterations"] else None


def dispatch_ms(run: Dict[str, Any]) -> Optional[float]:
    s = run["window"]["spans"]["dispatch"]
    return 1e3 * s["seconds"] / s["calls"] if s["calls"] else None


def train_step_device_ms(run: Dict[str, Any]) -> Optional[float]:
    """Device time of the train block's executions in the capture, a gradient step."""
    tr, w = run["trace"], run["window"]
    if tr is None or not w["blocks"]:
        return None
    seconds = count = 0.0
    for name, m in tr["modules"].items():
        if "block" in name:
            seconds += m["seconds"]
            count += m["count"]
    if not count:
        return None
    return 1e3 * seconds / (count * w["grad_steps"] / w["blocks"])


def step_mfu(run: Dict[str, Any]) -> Optional[float]:
    from perfbench.harness import resolve

    w, dev = run["window"], run["device"]
    if not w["grad_steps"] or run.get("rehearsal"):
        return None  # a share of the chip's peak is read on the chip or not at all
    kind = dev["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json: add it with its source")
    peak = run["peaks"][kind]["flops_per_s_bf16"] * dev["count"]
    return 100.0 * resolve(run["cell"].config["flops"])(run["sizes"])["total"] * w["grad_steps"] / (w["seconds"] * peak)


def device_idle_share(run: Dict[str, Any]) -> Optional[float]:
    tr = run["trace"]
    if tr is None or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]


def hbm_peak_gib(run: Dict[str, Any]) -> Optional[float]:
    peak = run["device"].get("memory_peak_bytes")
    return peak / 2**30 if peak else None
