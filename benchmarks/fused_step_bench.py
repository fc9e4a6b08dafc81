"""Microbench: the RSSM recurrent step at DreamerV3 size-S shapes (VERDICT r4 #4).

Compares two implementations of the 64-step training-shape scan
(forward + backward, B=16, K=1024, H=512 — the T=64 world-model unroll's exact
per-step shapes) on the current backend:

  a. ``xla``        — plain XLA step (matmul + LN + gates, ``reference_gru_step``);
  b. ``full_fused`` — one VMEM-resident Pallas kernel incl. the matmul
                      (``ops/rssm_step.py``).

Prints one JSON line with ms/scan and steps/s for each, plus the implied ceiling:
the per-step latency floor x 64 steps is the minimum wall-clock of the world-model
scan regardless of what the rest of the train step does.

Usage: ``python benchmarks/fused_step_bench.py [T] [B]`` (defaults 64, 16).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo root, after site pkgs resolve

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    T = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    B = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    K_IN, H = 512, 512  # size S: input projection width and recurrent size
    K = K_IN + H

    from sheeprl_tpu.ops.rssm_step import fused_gru_step, reference_gru_step

    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    xs = jnp.asarray(rng.normal(size=(T, B, K_IN)).astype(np.float32), dtype)
    w = jnp.asarray(rng.normal(size=(K, 3 * H)).astype(np.float32) * 0.02, dtype)
    gamma = jnp.ones((3 * H,), jnp.float32)
    beta = jnp.zeros((3 * H,), jnp.float32)

    def scan_loss(step_fn):
        def run(w_):
            def step(h, x):
                h2 = step_fn(jnp.concatenate([x, h.astype(dtype)], -1), h, w_, gamma, beta)
                return h2.astype(jnp.float32), h2

            _, hs = jax.lax.scan(step, jnp.zeros((B, H)), xs)
            return jnp.sum(hs.astype(jnp.float32) ** 2)

        return jax.jit(jax.grad(run))

    results = {}
    for name, fn in (
        ("xla", reference_gru_step),
        ("full_fused", fused_gru_step),
    ):
        f = scan_loss(fn)
        g = f(w)
        jax.block_until_ready(g)  # warm-up: compile + one run
        n = 20
        t0 = time.perf_counter()
        for _ in range(n):
            g = f(w)
        jax.block_until_ready(g)
        ms = (time.perf_counter() - t0) / n * 1000.0
        results[name] = {"ms_per_scan": round(ms, 3), "us_per_step": round(ms * 1000.0 / T, 1)}

    base = results["xla"]["ms_per_scan"]
    for name in results:
        results[name]["speedup_vs_xla"] = round(base / results[name]["ms_per_scan"], 3)
    print(
        json.dumps(
            {
                "bench": "rssm_step_scan_fwd_bwd",
                "backend": jax.default_backend(),
                "shape": {"T": T, "B": B, "K": K, "H": H, "dtype": str(dtype.__name__)},
                **results,
            }
        )
    )


if __name__ == "__main__":
    main()
