"""Per-layer metrics of a decoder policy with Mamba-2 state-space blocks
(``nemotron3nano30b_1of16``), read by the program's own names: the scopes ``policy/mamba``
(in-projection, convolution, gated group norm, out-projection) and ``policy/ssd_scan`` (the
scan itself) inside its jitted update and its acting step.  The scopes' reduction is
``readers/decoder.py``'s; a program without them (an older commit, another model) gives
every reader here ``None``.
"""

from __future__ import annotations

from perfbench.readers import decoder, spans

MAMBA, SCAN = "policy/mamba", "policy/ssd_scan"


def ssm_device_ms(run):
    """The Mamba blocks' mixers, the scan included, forward and backward, a gradient step."""
    return decoder._update_scope_ms(run, MAMBA, SCAN)


def act_ssm_device_ms(run):
    """The Mamba blocks' part of one acting step on the device: the projections, the
    convolution over the carried tail and the one-token recurrence, which reads and writes
    every row's state."""
    found = decoder._module(run, decoder.ACTING)
    if found is None:
        return None
    scopes, _, executions = found
    seconds = sum(s for key, s in scopes.items() if key.split(" ")[0] in (MAMBA, SCAN))
    return 1e3 * seconds / executions if seconds > 0 else None


def ssd_scan_roofline(run):
    """The chunked scan's share of the chip's roofline over the captured updates, in %: the
    least time the chip could take for the scan's operations and bytes
    (``flops_ssm_decoder.scan_costs`` a Mamba block, the forward pass three times over: once,
    once recomputed in the backward pass, and twice for the backward pass itself), the larger
    of operations over the bf16 peak and bytes over the memory bandwidth, over the device
    seconds of ``policy/ssd_scan`` in the captured updates."""
    from perfbench import harness
    from perfbench.flops_ssm_decoder import scan_costs

    found = decoder._module(run, decoder.UPDATE)
    if found is None:
        return None
    scopes, _, executions = found
    seconds = sum(s for key, s in scopes.items() if key.split(" ")[0] == SCAN)
    if seconds <= 0:
        return None
    S, dev = run["sizes"], run["device"]
    steps = executions * spans.of_run(run)["steps_per_execution"]
    blocks = S["pattern"][: S["layers"]].count("M")
    one = scan_costs(S["num_envs"] // S["num_batches"], S["rollout_steps"], S, compute_bytes=2 if S["precision"].startswith("bf16") else 4)
    flops, moved = 4.0 * steps * blocks * one["flops"], 4.0 * steps * blocks * one["bytes"]
    peaks = run["peaks"][dev["kind"]]
    least = max(flops / (peaks["flops_per_s_bf16"] * dev["count"]), moved / (peaks["hbm_bytes_per_s"] * dev["count"]))
    harness.log(
        f"ssd scan: {1e3 * seconds / steps:.3f} ms a gradient step on the device, {flops / seconds / 1e12:.2f} TFLOP/s, "
        f"{moved / seconds / 1e9:.1f} GB/s ({'bytes' if moved / peaks['hbm_bytes_per_s'] > flops / peaks['flops_per_s_bf16'] else 'operations'} bound)"
    )
    return 100.0 * least / seconds
