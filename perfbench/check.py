"""The comparison that decides ``correct`` for a training cell.

The program's first three gradient steps (through the timed path's own block, at the
timed sizes) against the plain reference following the same three: same weights from
the seed, batches gathered from the environment's own rows at the indices the program
drew, the program's key schedule.

Numbers compared (each has a limit of its own in the cell's workload file; how each
limit was read is in PERF.md):

* ``loss_gap.<tree>``: the widest relative gap of that tree's loss over the three steps;
  ``loss_gap.kl``: the same for the batch's mean KL between posterior and prior;
* ``grad_gap``: by the worst leaf, the gap between the program's norm of the first
  gradient (as Adam received it, read from its first moment after step 1) and the
  reference's, against the reference's norm of that leaf or of the median leaf,
  whichever is larger; ``grad_gap.median``: the same by the median leaf;
  ``grad_gap.<group>``: over a group of leaves that the reference names, either by its
  worst leaf (``transition``: the prior's layers, reached by the dynamic KL term only, so
  a wrong KL weight or a stop-gradient on the wrong side shows there and nowhere in a
  loss) or pooled (``world_model``: the root of the summed squares of the leaves' gaps
  of norms, against the reference's norm of the group's whole gradient; the large
  leaves, whose norms a flipped draw hardly moves and a lower precision does, carry it);
* ``change_gap``: the same for the norm of the parameters' change after three steps,
  over the leaves whose reference gradient is at least a thousandth of the median
  leaf's (a leaf below that moves under Adam by round-off alone).

The worst leaf swings by its nature (one small leaf, a few flipped categorical draws),
and so does the median leaf where many draws flip (PERF.md, PR 24); the pooled gap is
the steady reading beside them.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

GRAD_FLOOR = 1e-3  # of the median leaf's gradient norm


def gather_batch(rows: List[Dict[str, np.ndarray]], envs: np.ndarray, starts: np.ndarray, T: int) -> Dict[str, np.ndarray]:
    """``[T, B, ...]`` sequences from the environment's own rows: batch element ``b``
    is rows ``starts[b] .. starts[b]+T-1`` of env ``envs[b]``."""
    out: Dict[str, list] = {}
    for e, s in zip(envs.tolist(), starts.tolist()):
        have = len(rows[e]["rewards"])
        if s + T > have:
            raise RuntimeError(f"the program sampled rows {s}..{s + T - 1} of env {e}; the environment kept {have}")
        for k, v in rows[e].items():
            out.setdefault(k, []).append(v[s : s + T])
    return {k: np.stack(v, axis=1) for k, v in out.items()}


def leaf_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> Dict[str, Any]:
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    median = float(np.median(ref[keep])) if keep.any() else 0.0
    denom = np.maximum(ref, median)
    gap = np.where(keep, np.abs(prog - ref) / np.where(denom > 0, denom, 1.0), 0.0)
    kept = gap[keep]
    return {
        "value": float(gap.max()),
        "gaps": gap,
        "median_gap": float(np.median(kept)) if kept.size else float("nan"),
        "median": median,
    }


def compare(program: Dict[str, Any], reference: Dict[str, Any], groups: Optional[Dict[str, Dict[str, Any]]] = None) -> Dict[str, float]:
    """``program``/``reference``: ``{"loss": [{tree: x} * 3], "grad_norms": [leaves],
    "change_norms": [leaves]}``; ``groups``: ``{name: {"leaves": [indices], "by": "worst"
    | "pooled"}}``.  Returns the numbers to hold against the limits."""
    numbers: Dict[str, float] = {}
    for tree in ("world_model", "actor", "critic", "kl"):
        gaps = []
        for p, r in zip(program["loss"], reference["loss"]):
            gaps.append(abs(p[tree] - r[tree]) / max(abs(r[tree]), 1e-12))
        numbers[f"loss_gap.{tree}"] = float(max(gaps))
    ref_g = np.asarray(reference["grad_norms"], np.float64)
    every = np.ones_like(ref_g, dtype=bool)
    g = leaf_gap(program["grad_norms"], ref_g, every)
    numbers["grad_gap"] = g["value"]
    numbers["grad_gap.median"] = g["median_gap"]
    prog_g = np.asarray(program["grad_norms"], np.float64)
    for name, group in (groups or {}).items():
        leaves = group["leaves"]
        if group["by"] == "pooled":
            pooled = np.linalg.norm(prog_g[leaves] - ref_g[leaves]) / np.linalg.norm(ref_g[leaves])
            numbers[f"grad_gap.{name}"] = float(pooled)
        else:
            numbers[f"grad_gap.{name}"] = float(g["gaps"][leaves].max())
    moved = ref_g >= GRAD_FLOOR * g["median"]
    c = leaf_gap(program["change_norms"], reference["change_norms"], moved)
    numbers["change_gap"] = c["value"]
    return numbers


def coverage(reference: Dict[str, Any], free_nats: float) -> Dict[str, Any]:
    """What the compared steps exercised: the KL of the batch's states against the free
    nats (under them the KL terms are constants and the transition model gets no
    gradient), and how many leaves the gradient floor leaves out of the change."""
    ref_g = np.asarray(reference["grad_norms"], np.float64)
    return {
        "free_nats": free_nats,
        "kl_mean": [step["kl"] for step in reference["loss"]],
        "kl_min": [step["kl_min"] for step in reference["loss"]],
        "leaves": int(ref_g.size),
        "leaves_under_grad_floor": int((ref_g < GRAD_FLOOR * np.median(ref_g)).sum()),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Any]:
    """Every number that has a limit, beside it; ``correct`` is all of them held."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        held = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(held)
        compared[name] = {"value": value, "limit": limit}
    return {"correct": bool(ok and compared), "compared": compared}
