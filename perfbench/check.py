"""The comparison that decides ``correct`` for a training cell, of any family.

The program's first three steps (through the timed path's own call, at the timed
sizes) against the family's plain reference following the same three: same weights
from the seed, the environment's own rows, the program's key schedule.  Which losses
are compared by name and which groups of leaves are the family's to say
(``Adapter.compared``, ``adapters/base.py``); what each means for a family is written
beside its adapter and its reference.

Numbers compared (each has a limit of its own in the cell's workload file; how each
limit was read is in PERF.md):

* ``loss_gap.<name>``: the widest relative gap of that loss over the three steps;
* ``grad_gap``: by the worst leaf, the gap between the program's norm of the first
  gradient (as the optimizer received it) and the reference's, against the
  reference's norm of that leaf or of the median leaf, whichever is larger;
  ``grad_gap.median``: the same by the median leaf; ``grad_gap.<group>``: over a group
  of leaves that the family names, either by its worst leaf or pooled (the root of the
  summed squares of the leaves' gaps of norms, against the reference's norm of the
  group's whole gradient; the large leaves carry it);
* ``change_gap``: the same for the norm of the parameters' change after three steps,
  over the leaves whose reference gradient is at least a thousandth of the median
  leaf's (a leaf below that moves under Adam by round-off alone).

The worst leaf swings by its nature (one small leaf), and so can the median leaf
(PERF.md, PR 24); a pooled gap is the steady reading beside them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

GRAD_FLOOR = 1e-3  # of the median leaf's gradient norm


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


def compare(
    program: Dict[str, Any], reference: Dict[str, Any], losses: Sequence[str], groups: Optional[Dict[str, Dict[str, Any]]] = None
) -> Dict[str, float]:
    """``program``/``reference``: ``{"loss": [{name: x} * 3], "grad_norms": [leaves],
    "change_norms": [leaves]}``; ``losses``: the names to compare; ``groups``: ``{name:
    {"leaves": [indices], "by": "worst" | "pooled"}}``.  Returns the numbers to hold
    against the limits."""
    numbers: Dict[str, float] = {}
    for name in losses:
        gaps = []
        for p, r in zip(program["loss"], reference["loss"]):
            gaps.append(abs(p[name] - r[name]) / max(abs(r[name]), 1e-12))
        numbers[f"loss_gap.{name}"] = float(max(gaps))
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


def grad_floor_coverage(reference: Dict[str, Any]) -> Dict[str, int]:
    """How many leaves the gradient floor leaves out of the change."""
    ref_g = np.asarray(reference["grad_norms"], np.float64)
    return {"leaves": int(ref_g.size), "leaves_under_grad_floor": int((ref_g < GRAD_FLOOR * np.median(ref_g)).sum())}


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
