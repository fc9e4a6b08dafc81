"""``python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``

One process, which owns the chip.  Prints the contract's one JSON object as the last
line of standard output; exits non-zero and prints no result where JAX finds no TPU
or fewer chips than the cell asks for.  It never falls back to the CPU: a rehearsal on
the CPU backend has to be asked for (``--rehearsal 1``, used by the tests), runs the
configuration's tiny sizes, and names its metrics ``rehearsal.<name>``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


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
    os.chdir(ROOT)
    if not (ROOT / "sheeprl_tpu").is_dir():
        print("perfbench: the program (sheeprl_tpu/) is not in this checkout. No result.", file=sys.stderr)
        return 2
    from perfbench import harness

    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), rehearsal=bool(args.rehearsal), t_process=T_PROCESS
    )
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
