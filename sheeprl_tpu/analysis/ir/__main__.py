"""``python -m sheeprl_tpu.analysis.ir`` / ``jaxlint-ir`` — the IR audit CLI.

Exit status: 0 when no findings survive the baseline, 1 otherwise, 2 on usage
errors.

    jaxlint-ir                         # audit everything vs irbudgets.json
    jaxlint-ir --entry sac --entry droq  # one or two registry units only
    jaxlint-ir --write-budgets         # accept current compile-memory budgets
    jaxlint-ir --json report.json      # full machine-readable report (CI artifact)
    jaxlint-ir --list                  # registry units + covered entry points

The audit forces the CPU backend (platform-independent IR properties are what
the rules check) and must stay importable before jax initialises a backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

DEFAULT_BASELINE = "jaxlint-ir.baseline"


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="jaxlint-ir",
        description="jaxlint-IR: jaxpr/HLO audit of every entry point's jitted update (rules IR000-IR006).",
    )
    parser.add_argument(
        "--entry",
        action="append",
        default=None,
        metavar="NAME",
        help="registry unit(s) to audit (default: all); repeatable",
    )
    parser.add_argument("--budgets", default=None, help="irbudgets.json path (default: ./irbudgets.json)")
    parser.add_argument(
        "--write-budgets",
        action="store_true",
        help="write the measured compile-memory budgets to the budgets file and exit 0",
    )
    parser.add_argument(
        "--tolerance", type=float, default=None, help="override the baseline's relative budget tolerance"
    )
    parser.add_argument(
        "--max-const-kb", type=int, default=128, help="IR005 threshold for baked-in constants (KiB)"
    )
    parser.add_argument(
        "--baseline",
        default=DEFAULT_BASELINE,
        help="fingerprint baseline for intentional IR violations (optional file)",
    )
    parser.add_argument("--no-baseline", action="store_true", help="ignore the fingerprint baseline")
    parser.add_argument("--json", default=None, metavar="PATH", help="write the full JSON report here")
    parser.add_argument("--list", action="store_true", help="list registry units and covered entry points")
    parser.add_argument("-q", "--quiet", action="store_true", help="suppress progress/summary lines")
    args = parser.parse_args(argv)

    # The audit is a CPU tool (dev boxes, CI runners; irbudgets.json holds CPU
    # compile-memory numbers and the IR properties checked are backend-
    # independent), so unless told otherwise it places itself on the CPU backend
    # — BEFORE jax initialises one — and says so: it must never take a chip a
    # training process needs.
    if not os.environ.get("JAX_PLATFORMS"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        if not args.quiet:
            print("jaxlint-ir: JAX_PLATFORMS unset, auditing on the CPU backend", file=sys.stderr)

    from sheeprl_tpu.analysis.core import filter_baseline, load_baseline
    from sheeprl_tpu.analysis.ir import (
        build_entries,
        check_budgets,
        coverage_findings,
        load_budgets,
        lower_entry,
        measured_budget,
        run_ir_rules,
        write_budgets,
    )
    from sheeprl_tpu.analysis.ir.budgets import DEFAULT_BUDGETS_FILE

    budgets_path = args.budgets or DEFAULT_BUDGETS_FILE
    full_run = not args.entry
    t0 = time.perf_counter()

    try:
        entry_iter = build_entries(args.entry)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    findings = []
    measurements: Dict[str, Dict[str, int]] = {}
    entries = []
    report_entries = []
    for entry in entry_iter:
        if args.list:
            entries.append(entry)
            continue
        t_entry = time.perf_counter()
        art = lower_entry(entry)
        entry_findings = run_ir_rules(art, max_const_bytes=args.max_const_kb * 1024)
        budget = measured_budget(art)
        measurements[entry.name] = budget
        findings.extend(entry_findings)
        entries.append(entry)
        elapsed = time.perf_counter() - t_entry
        if not args.quiet:
            status = "ok" if not entry_findings else f"{len(entry_findings)} finding(s)"
            print(
                f"jaxlint-ir: {entry.name}: {status} "
                f"(donated {art.donated_count} arg(s), {budget['total_bytes']} B, {elapsed:.1f}s)",
                file=sys.stderr,
            )
        report_entries.append(
            {
                "name": entry.name,
                "covers": list(entry.covers),
                "precision": entry.precision,
                "donated_args": art.donated_count,
                "budget": budget,
                "findings": [f.render() for f in entry_findings],
                "seconds": round(elapsed, 2),
            }
        )

    if args.list:
        for e in entries:
            print(f"{e.name}  covers: {', '.join(e.covers) or '-'}")
        return 0

    if args.write_budgets:
        if not full_run:
            print(
                "error: --write-budgets needs a full (unfiltered) audit so the "
                "baseline stays complete",
                file=sys.stderr,
            )
            return 2
        write_budgets(measurements, budgets_path)
        if not args.quiet:
            print(f"jaxlint-ir: wrote {len(measurements)} budget(s) to {budgets_path}")
        return 0

    findings.extend(coverage_findings(entries, full_run))
    baseline_doc = load_budgets(budgets_path)
    budget_findings = check_budgets(measurements, baseline_doc, tolerance=args.tolerance)
    if not full_run:
        # A filtered run audits a subset: entries absent from the run are not
        # stale, and coverage cannot be judged.
        budget_findings = [f for f in budget_findings if f.detail != "stale-budget-row"]
    findings.extend(budget_findings)

    baseline = None if args.no_baseline else load_baseline(args.baseline)
    if baseline:
        findings = filter_baseline(findings, baseline)

    for f in findings:
        print(f.render())
    if args.json:
        report = {
            "elapsed_seconds": round(time.perf_counter() - t0, 2),
            "entries": report_entries,
            "budgets_file": budgets_path,
            "findings": [
                {"rule": f.rule, "entry": f.path, "message": f.message, "detail": f.detail}
                for f in findings
            ],
        }
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1)
    if not args.quiet:
        status = "ok" if not findings else f"{len(findings)} finding(s)"
        print(
            f"jaxlint-ir: {status} over {len(entries)} audit entr{'y' if len(entries) == 1 else 'ies'} "
            f"({time.perf_counter() - t0:.1f}s)",
            file=sys.stderr,
        )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
