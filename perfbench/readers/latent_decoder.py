"""Per-layer metrics of a decoder policy with latent attention (MLA) and a shared expert
(``moonlight16b_1of8``), read by the program's own names: the scopes
``policy/attention_latent`` and ``policy/shared_expert`` inside its jitted update and its
acting step, the two kernels of ``ops/blockwise_attention.py`` as the capture shows them
inside the update, and the update's counter ``Attn/key_blocks_visited_share``.  The scopes'
reduction is ``readers/decoder.py``'s; a program without the scope, the kernels or the
counter (an older commit, another model) gives every reader here ``None``.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Tuple

from perfbench.readers import decoder, spans, xplane

LATENT = "policy/attention_latent"
VISITED = "Attn/key_blocks_visited_share"


def latent_attention_device_ms(run):
    """Latent attention (projections, the latent's norm, rotation, the folded products, the
    kernels, the chunk's own keys and the merge), a gradient step."""
    return decoder._update_scope_ms(run, LATENT)


def shared_expert_device_ms(run):
    """The shared expert's three products, a gradient step."""
    return decoder._update_scope_ms(run, "policy/shared_expert")


def act_latent_attention_device_ms(run):
    """Latent attention's part of one acting step on the device: the read of every layer's cache."""
    found = decoder._module(run, decoder.ACTING)
    if found is None:
        return None
    scopes, _, executions = found
    seconds = sum(s for key, s in scopes.items() if key.split(" ")[0] == LATENT)
    return 1e3 * seconds / executions if seconds > 0 else None


def kernel_events(run: Dict[str, Any]) -> Optional[List[List[Tuple[str, float]]]]:
    """For each whole execution of the update in the capture, its kernels under the latent
    scope as ``(kind, seconds)``: the custom calls whose instruction the update's scope map
    books whole under ``policy/attention_latent`` by the instruction's own name path (the
    compiled program names them after the scope, ``attention_latent.<n>``).  ``kind`` is ``"forward"`` for the kernel
    whose result is a pair (the output and the rows' statistics) and ``"backward"`` for the
    one whose result is one array (``dq``).  ``None`` without a capture, a map or a kernel."""
    from perfbench import harness

    if not run.get("traced"):
        return None
    cell = run["cell"].name
    try:
        path = xplane.find_xplane(harness.OUT / "trace" / cell)
    except FileNotFoundError:
        return None
    scope_map = spans.load_scope_maps(harness.OUT / "logs" / cell).get(decoder.UPDATE, {"ops": {}, "inherited": ()})
    latent = {name for name, shares in scope_map["ops"].items() if shares and all(key.split(" ")[0] == LATENT for key in shares)}
    latent -= set(scope_map["inherited"])  # the compiler's own custom calls (buffer allocations, bitcasts of concatenations) carry no name and took a neighbour's scope
    if not latent:
        return None
    pd = xplane.load(path)
    planes = [p for p in pd.planes if xplane.is_device_plane(p.name)]
    host = spans.host_spans(pd)
    if not planes or not host:
        return None
    lo, hi = min(a for _, a, _, _ in host), max(b for _, _, b, _ in host)
    ops = sorted(xplane.plane_events(planes[0], "XLA Ops"), key=lambda e: e[1])
    starts = [a for _, a, _ in ops]
    out = []
    for name, a, b in xplane.plane_events(planes[0], "XLA Modules"):
        if xplane.op_kind(name) != decoder.UPDATE or a < lo or b > hi:
            continue
        inside = [e for e in ops[bisect.bisect_left(starts, a) : bisect.bisect_right(starts, b)] if e[2] <= b]
        kernels = []
        for text, t0, t1 in inside:
            if " custom-call(" in text and spans.instruction(text) in latent:
                result = text.split(" = ", 1)[1].lstrip()
                kernels.append(("forward" if result.startswith("(") else "backward", t1 - t0))
        out.append(kernels)
    return out if any(out) else None


def latent_attention_roofline(run):
    """The two attention kernels' share of the chip's peak over the captured updates, in %:
    the operations of the key blocks they visited (``flops_latent_decoder.latent_block_flops``
    a visited flag and kernel call: a recomputed forward pass counts as the work it is, a
    skipped block as none) over the kernels' device seconds times the peak.  The flags are
    the positions', so every latent layer has the same; the visited share is the one each
    captured update reported (kept by the cell's adapter while the capture ran)."""
    import jax

    from perfbench import harness
    from perfbench.flops_latent_decoder import latent_block_flops

    reports = getattr(run.get("adapter"), "capture_reports", None)
    executions = kernel_events(run)
    if not reports or not executions:
        return None
    shares = [float(r[VISITED]) for r in jax.device_get(reports) if VISITED in r]
    if not shares:
        return None
    if len(shares) != len(executions):  # an update cut by the capture's edge: every whole execution is taken as the mean update
        shares = [sum(shares) / len(shares)] * len(executions)
    S, dev = run["sizes"], run["device"]
    rows = S["rollout_steps"] * S["heads_held"]  # query rows of the one key head, a sequence
    whole = latent_block_flops(rows, S["num_envs"] * S["cache_capacity"], S)  # every slot of a latent layer visited, a kernel call
    flops = seconds = 0.0
    for share, kernels in zip(shares, executions):
        for kind, s in kernels:
            flops += share * whole[kind]
            seconds += s
    if seconds <= 0:
        return None
    peak = run["peaks"][dev["kind"]]["flops_per_s_bf16"] * dev["count"]
    calls = sum(len(k) for k in executions)
    harness.log(
        f"latent attention kernels: {calls} calls in {len(executions)} updates ({sum(1 for k in executions for kind, _ in k if kind == 'forward')} forward), "
        f"{1e3 * seconds / len(executions):.3f} ms an update, visited share {min(shares):.4f}-{max(shares):.4f}, {flops / seconds / 1e12:.2f} TFLOP/s"
    )
    return 100.0 * flops / (seconds * peak)
