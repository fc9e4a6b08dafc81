"""Per-layer metrics of the decoder policy under recurrent PPO, read from the capture by
the program's own names: the scopes inside its jitted update (``ppo_recurrent/train_fn``:
``policy/embed``, ``policy/router``, ``policy/experts``, ``policy/attention_full``,
``policy/attention_window``, ``policy/head``, ``policy_optimizer``, ``health``, and what lies
under none) and its acting step (``ppo_recurrent/act_fn``), the host span around the acting
call (``Rollout/act_call``), and the update's own counters (``MoE/*``,
``Health/ratio_first_epoch``).

The reduction is ``readers/spans.py``'s; a program without the scopes, the span or the
counter (an older commit, another family) gives every reader here ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from perfbench.readers import spans

UPDATE, ACTING = "jit_train_fn", "jit_act"  # the HLO modules of the two jitted functions


def _module(run: Dict[str, Any], part: str) -> Optional[Tuple[Dict[str, float], float, float]]:
    """Seconds by ``scope direction`` over the whole executions of the program's modules
    of that name, their module seconds, and their count."""
    red = spans.of_run(run)
    if red is None:
        return None
    scopes: Dict[str, float] = {}
    seconds = count = 0.0
    for module, m in red["device"].items():
        if module == part:
            count += m["executions"]
            seconds += m["module_s"]
            for key, s in m["scopes"].items():
                scopes[key] = scopes.get(key, 0.0) + s
    return (scopes, seconds, count) if count else None


def _update_scope_ms(run: Dict[str, Any], *names: str) -> Optional[float]:
    found = _module(run, UPDATE)
    if found is None:
        return None
    scopes, _, executions = found
    steps = executions * spans.of_run(run)["steps_per_execution"]
    seconds = sum(s for key, s in scopes.items() if key.split(" ")[0] in names)
    return 1e3 * seconds / steps if seconds > 0 else None


def _unscoped_share(run: Dict[str, Any], part: str) -> Optional[float]:
    found = _module(run, part)
    if found is None:
        return None
    scopes = found[0]
    total = sum(scopes.values())
    return 100.0 * scopes.get(spans.UNSCOPED, 0.0) / total if total > 0 else None


def _reported(run: Dict[str, Any], name: str) -> list:
    """The counter ``name`` as each of the compared updates reported it."""
    steps = (run.get("program") or {}).get("steps") or []
    return [s["reported"][name] for s in steps if name in s.get("reported", {})]


def update_step_device_ms(run):
    """The whole jitted update on the device, a gradient step: what the scopes are parts of."""
    found = _module(run, UPDATE)
    if found is None:
        return None
    _, seconds, executions = found
    return 1e3 * seconds / (executions * spans.of_run(run)["steps_per_execution"])


def embed_device_ms(run):
    return _update_scope_ms(run, "policy/embed")


def router_device_ms(run):
    return _update_scope_ms(run, "policy/router")


def policy_health_device_ms(run):
    return _update_scope_ms(run, "health")


def update_unscoped_share(run):
    return _unscoped_share(run, UPDATE)


def act_unscoped_share(run):
    return _unscoped_share(run, ACTING)


def experts_device_ms(run):
    return _update_scope_ms(run, "policy/experts")


def attention_device_ms(run):
    """Both kinds of attention layer, a gradient step; each kind is printed apart."""
    from perfbench import harness

    full, window = _update_scope_ms(run, "policy/attention_full"), _update_scope_ms(run, "policy/attention_window")
    if full is None and window is None:
        return None
    harness.log(f"decoder attention a gradient step: full layers {full} ms, window layers {window} ms")
    return (full or 0.0) + (window or 0.0)


def head_device_ms(run):
    return _update_scope_ms(run, "policy/head")


def policy_optimizer_device_ms(run):
    return _update_scope_ms(run, "policy_optimizer")


def act_step_device_ms(run):
    """Device time of one execution of the jitted acting step."""
    found = _module(run, ACTING)
    if found is None:
        return None
    _, seconds, executions = found
    return 1e3 * seconds / executions


def act_call_ms(run):
    """Host time of the acting call (dispatch; the fetch that waits for it is ``Rollout/action_fetch``)."""
    return spans._span_ms(run, "Rollout/act_call")


def expert_load_max_over_mean(run):
    """The fullest held expert's tokens over the mean, as the first updates reported it."""
    values = _reported(run, "MoE/load_max_over_mean")
    return sum(values) / len(values) if values else None


def first_epoch_ratio_gap(run):
    """How far from 1 the new over the old probabilities lie before an update's first step,
    the farthest of the compared updates: the chunk through the carried cache against the
    acting steps that wrote it."""
    values = _reported(run, "Health/ratio_first_epoch")
    return max(abs(v - 1.0) for v in values) if values else None


def moe_dropped(run):
    """Assignments to held experts that no grouped product computed, over the compared updates."""
    values = _reported(run, "MoE/dropped")
    return sum(values) if values else None
