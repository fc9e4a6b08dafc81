#!/usr/bin/env python
"""Diff two ``BENCH_*.json`` reports and flag per-metric regressions.

Usage:
    python benchmarks/bench_compare.py OLD_REPORT.json NEW_REPORT.json [--threshold 0.10] [--json]
    python benchmarks/bench_compare.py --latest 2 [--strict]

A BENCH report is the collector's dict whose ``tail`` embeds one JSON object per
benchmark metric (``{"metric": ..., "value": ..., "unit": ...}``); bare
JSON/JSONL files of such rows are accepted too.  For each metric present in both
reports the relative change is computed and classified:

* throughput-like metrics (the default) regress when the value DROPS by more
  than ``--threshold``;
* latency-like metrics (name/unit contains ``ms``, ``time``, ``latency`` or
  ``seconds``) regress when the value RISES by more than ``--threshold``.

A metric present in the baseline but missing from the latest report is a
DROPPED metric — reported loudly (a silently-vanished benchmark is not a pass),
and treated like a regression under ``--strict``.

Exit code is 0 unless ``--strict`` is given and regressions (or dropped metrics)
were found — CI wires this as a non-blocking warning step
(``continue-on-error``), so a slow metric shows up in the job log without
failing the build.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

_LOWER_BETTER_HINTS = ("ms", "latency", "time", "seconds")
# Explicit direction pins beat the unit-text heuristic: every anakin_* row
# (benchmarks/anakin_bench.py), sebulba_* row (benchmarks/sebulba_bench.py),
# serve_* row (benchmarks/serve_bench.py) and precision_* row
# (benchmarks/precision_bench.py — parity/agreement fractions AND the bf16/int8
# throughputs ride the anakin_/serve_ prefixes) and fleet_* row
# (benchmarks/fleet_bench.py) is higher-better regardless of what its unit
# string mentions...
_HIGHER_BETTER_PREFIXES = ("anakin_", "sebulba_", "serve_", "precision_", "fleet_")
# ...EXCEPT the wall-clock/latency rows, which are durations: exact-name pins
# win over the prefix pins (serve_p99_ms / fleet_p99_ms are latency SLOs,
# serve_startup_seconds is the cold/warm replica start time — all regress when
# they RISE).
_LOWER_BETTER_METRICS = (
    "anakin_compile_seconds",
    "checkpoint_save_seconds",
    "fleet_p99_ms",
    "obs_fleet_overhead_pct",
    "perf_overhead_pct",
    "race_detect_overhead_pct",
    "resume_restore_seconds",
    "serve_p99_ms",
    "serve_startup_seconds",
)
# Exact-name higher-better pins (beat the unit-hint heuristic, whose "time"/
# "wall clock" words would otherwise misread these): the perf-attribution
# plane's own figures regress when they DROP — a fall in perf_mfu or
# goodput_fraction means lost utilization or lost useful-work share.
_HIGHER_BETTER_METRICS = (
    "goodput_fraction",
    "perf_mfu",
)


def extract_metrics(path: str) -> Dict[str, Tuple[float, str]]:
    """``{metric: (value, unit)}`` from a BENCH report (or bare JSON/JSONL rows)."""
    with open(path) as f:
        text = f.read()
    rows: List[dict] = []
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and "metric" in doc:
        rows = [doc]
    elif isinstance(doc, list):
        rows = [r for r in doc if isinstance(r, dict) and "metric" in r]
    elif isinstance(doc, dict):
        text = doc.get("tail", "") or ""
    if not rows:
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict) and "metric" in row and "value" in row:
                rows.append(row)
    out: Dict[str, Tuple[float, str]] = {}
    for row in rows:
        try:
            out[str(row["metric"])] = (float(row["value"]), str(row.get("unit", "")))
        except (TypeError, ValueError):
            continue
    return out


def lower_is_better(metric: str, unit: str) -> bool:
    if str(metric).lower() in _LOWER_BETTER_METRICS:
        return True
    if str(metric).lower() in _HIGHER_BETTER_METRICS:
        return False
    if str(metric).lower().startswith(_HIGHER_BETTER_PREFIXES):
        return False
    blob = f"{metric} {unit}".lower()
    return any(hint in blob for hint in _LOWER_BETTER_HINTS)


def compare(base_path: str, new_path: str, threshold: float = 0.10) -> dict:
    base = extract_metrics(base_path)
    new = extract_metrics(new_path)
    rows = []
    for name in sorted(set(base) & set(new)):
        b, unit = base[name]
        n, _ = new[name]
        change = (n - b) / abs(b) if b else float("inf") if n else 0.0
        lower = lower_is_better(name, unit)
        regressed = (change > threshold) if lower else (change < -threshold)
        rows.append(
            {
                "metric": name,
                "base": b,
                "new": n,
                "change": change,
                "direction": "lower-better" if lower else "higher-better",
                "regressed": regressed,
            }
        )
    dropped = sorted(set(base) - set(new))
    return {
        "base": base_path,
        "new": new_path,
        "threshold": threshold,
        # A metric present in the baseline but ABSENT from the latest report is
        # not a pass — it means the benchmark silently stopped being measured
        # (renamed row, crashed collector, skipped env gate).  Surface it as
        # loudly as a regression; --strict fails on it.
        "only_in_base": dropped,
        "dropped_metrics": dropped,
        "only_in_new": sorted(set(new) - set(base)),
        "rows": rows,
        "regressions": [r["metric"] for r in rows if r["regressed"]],
    }


def format_table(report: dict) -> str:
    lines = [
        f"bench_compare: {os.path.basename(report['base'])} -> "
        f"{os.path.basename(report['new'])} (threshold {report['threshold'] * 100:.0f}%)"
    ]
    if not report["rows"]:
        lines.append("no common metrics found")
        return "\n".join(lines)
    headers = ("metric", "base", "new", "change", "verdict")
    table = [
        (
            r["metric"],
            f"{r['base']:.4g}",
            f"{r['new']:.4g}",
            f"{r['change'] * 100:+.1f}%",
            "REGRESSED" if r["regressed"] else "ok",
        )
        for r in report["rows"]
    ]
    widths = [max(len(h), *(len(t[i]) for t in table)) for i, h in enumerate(headers)]
    lines.append("  ".join(h.ljust(w) for h, w in zip(headers, widths)))
    lines.append("-" * (sum(widths) + 2 * (len(widths) - 1)))
    for t in table:
        lines.append("  ".join(c.ljust(w) for c, w in zip(t, widths)))
    dropped = report.get("dropped_metrics", report["only_in_base"])
    if dropped:
        lines.append(
            f"WARNING: {len(dropped)} metric(s) present in the baseline DISAPPEARED "
            "from the latest report — a silently-dropped benchmark is not a pass:"
        )
        for name in dropped:
            lines.append(f"  DROPPED: {name}")
    for name in report["only_in_new"]:
        lines.append(f"(new metric: {name})")
    if report["regressions"]:
        lines.append(f"{len(report['regressions'])} regression(s): {', '.join(report['regressions'])}")
    else:
        lines.append("no regressions")
    return "\n".join(lines)


def _latest_bench_files(n: int, root: str = ".") -> List[str]:
    files = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    return files[-n:]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("base", nargs="?", help="baseline BENCH_*.json")
    parser.add_argument("new", nargs="?", help="candidate BENCH_*.json")
    parser.add_argument("--latest", type=int, metavar="N", help="compare the two newest of the N latest BENCH_*.json in the CWD")
    parser.add_argument("--threshold", type=float, default=0.10, help="relative regression threshold (default 0.10)")
    parser.add_argument("--json", action="store_true", help="emit the JSON report")
    parser.add_argument(
        "--strict", action="store_true", help="exit 1 when regressions or dropped metrics are found"
    )
    args = parser.parse_args(argv)

    if args.latest:
        files = _latest_bench_files(args.latest)
        if len(files) < 2:
            print(f"bench_compare: need at least two BENCH_*.json files, found {files}")
            return 0
        base_path, new_path = files[-2], files[-1]
    elif args.base and args.new:
        base_path, new_path = args.base, args.new
    else:
        parser.error("provide two BENCH files or --latest N")

    report = compare(base_path, new_path, threshold=args.threshold)
    print(json.dumps(report, indent=1) if args.json else format_table(report))
    if report["dropped_metrics"]:
        print(
            f"bench_compare: WARNING — dropped metric(s): {', '.join(report['dropped_metrics'])}",
            file=sys.stderr,
        )
    return 1 if args.strict and (report["regressions"] or report["dropped_metrics"]) else 0


if __name__ == "__main__":
    sys.exit(main())
