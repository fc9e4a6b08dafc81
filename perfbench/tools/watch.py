"""A run of a cell with a watcher thread beside the host loop: for a far-off run.

``python3 -m perfbench.tools.watch --workload <cell> --seed <n> --seconds <s>``

A diagnosis, never a measurement (the thread shares the host's cores and the
interpreter lock with the loop it watches).  The watcher sleeps 2 ms and notes every
tick that comes more than 30 ms late.  For each iteration of the window longer than
``--long-ms`` the tool prints the watcher's late ticks inside it: where the watcher
stood still too, the process as a whole did (the machine, or a call that held the
interpreter lock); where it kept ticking, only the host loop waited (on the device,
or in a call that releases the lock).  PERF.md, PR 27, says what it was built for:
a first window on a fresh machine that held iterations of 0.8-2.8 s with no full
collection in it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
TICK_S, LATE_S = 0.002, 0.03


class Watcher(threading.Thread):
    def __init__(self) -> None:
        super().__init__(daemon=True)
        self.late = []  # (when the tick came, how long after the one before), perf_counter's clock
        self.stop = threading.Event()

    def run(self) -> None:
        last = time.perf_counter()
        while not self.stop.is_set():
            time.sleep(TICK_S)
            now = time.perf_counter()
            if now - last > LATE_S:
                self.late.append((now, now - last))
            last = now


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--long-ms", type=float, default=300.0)
    parser.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from perfbench import harness

    watcher = Watcher()
    watcher.start()
    try:
        run = harness.drive(args.workload, args.seed, args.seconds, False, rehearsal=bool(args.rehearsal), t_process=T_PROCESS)
    finally:
        watcher.stop.set()
        watcher.join(timeout=1.0)
    window = run["window"]
    t_open = T_PROCESS + window["setup_s"]
    inside = [(t - t_open, gap) for t, gap in watcher.late if t_open <= t <= t_open + window["seconds"]]
    print(f"watch: {args.workload} seed {args.seed}: {window['grad_steps'] / window['seconds']:.4f} steps/s; the watcher's late ticks in the window (s into it, ms): {[(round(t, 2), round(1e3 * g)) for t, g in inside]}")
    t = 0.0
    for gap in window["gaps_s"]:
        if 1e3 * gap > args.long_ms:
            ticks = [(round(at - t, 3), round(1e3 * g)) for at, g in inside if t - 0.05 <= at <= t + gap + 0.05]
            print(f"watch: iteration of {1e3 * gap:.0f} ms, {t:.2f}s into the window; late ticks inside it (s after its start, ms): {ticks}")
        t += gap
    return 0


if __name__ == "__main__":
    sys.exit(main())
