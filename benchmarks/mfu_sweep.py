"""Train-only MFU sweep over DreamerV3 model sizes (VERDICT r3 #5).

Round 3 left MFU at ~0.17 for size S with the unmeasured claim that the T=64 RSSM /
H=15 imagination scans are latency-bound at S and that larger models lift arithmetic
intensity.  This probe measures grad-steps/s + MFU for sizes S/M/L (same batch 16 ×
seq 64 × 64×64×3 config) and prints one JSON line per size, each naming the device
it was measured on.

Usage: ``python benchmarks/mfu_sweep.py [S M L S:64]`` — ``SIZE:BATCH`` entries
override the batch size (default 16), probing the arithmetic-intensity lever.

FLOPs and peak figures come from the perf attribution plane
(``sheeprl_tpu/obs/perf.py``) via ``bench.bench_train_only`` — one MFU
definition shared with the in-run ``Perf/mfu`` gauge.
"""

import json
import sys

sys.path.insert(0, ".")

from bench import bench_train_only  # noqa: E402


def main() -> None:
    from sheeprl_tpu.parallel.mesh import device_identity

    entries = sys.argv[1:] or ["S", "M", "L"]
    for entry in entries:
        size, _, batch = entry.partition(":")
        batch = int(batch) if batch else 16
        gsps, mfu = bench_train_only(size, batch=batch)
        print(
            json.dumps(
                {
                    "size": size,
                    "batch": batch,
                    "grad_steps_per_sec": round(gsps, 4),
                    "mfu": round(mfu, 4),
                    **device_identity(),
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
