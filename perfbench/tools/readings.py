"""Readings that the limits of ``correct`` are set from (PERF.md records them).

Lower readings are the program's own: every benchmark run leaves its raw numbers in
``.perfbench/readings/<cell>.<seed>.json`` (losses of the three steps, per-leaf norms of
the first gradient and of the parameters' change, program and reference).

This tool makes the *upper* readings at the cell's own size, on the chip:

``python3 -m perfbench.tools.readings --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

It is a run of the cell like any other (the program through its window, then the plain
float32 reference over the program's own rows, indices and keys; its result line comes
first), and then, over those same rows, indices and keys, the reference put in the
program's place:

* ``control_fp8``: every matmul and convolution input rounded to fp8 (e4m3), the nearest
  precision below the bf16 the configuration states;
* ``fault_half_batch``: half of every batch left out, the mean taken over the rest.

Each is held against the cell's limits by the same ``check.verdict`` that judges the
program, and its ``correct`` is printed: it has to read false.  The fault "a step that
returns its state unchanged" needs no run: the parameters' change reads 0 against the
reference's norm, a gap of 1 on every leaf.  The benchmark's own runs do none of this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
VARIANTS = {"control_fp8": {"quant": "fp8"}, "fault_half_batch": {"fault": "half_batch"}}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from perfbench import check, harness

    run = harness.drive(args.workload, args.seed, args.seconds, bool(args.trace), rehearsal=bool(args.rehearsal))
    result = harness.report(run)
    harness.emit(result)
    limits = run["cell"].limits(run["rehearsal"])
    reference = run["judged"]["reference"]
    entry = {"seed": args.seed, "program": {"correct": result["correct"], "compared": result["compared"]}}
    for name, how in VARIANTS.items():
        got = run["adapter"].reference_readings(run["rows"], run["program"], **how)
        numbers = check.compare(got, reference, **run["adapter"].compared())
        judged = check.verdict(numbers, limits)
        entry[name] = {"correct": judged["correct"], "numbers": numbers, "readings": harness.plain(got)}
        print(f"readings: {args.workload} seed {args.seed} {name} correct={judged['correct']} " + json.dumps(numbers), flush=True)
    out = harness.OUT / "readings"
    with open(out / f"upper.{args.workload}.{args.seed}.json", "w") as f:
        json.dump(entry, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
