"""``ops/scan_wgrad.py``: a scan whose dense kernels get their gradient after the
backward loop.  Parity with JAX's own transposition of the same scan (the RSSM unroll
of ``dreamer_v3.py`` at tiny sizes, and a scan without Flax), and the structure the
mechanism is for: no loop of the compiled train block updates a kernel-shaped array."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, build_agent
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
from sheeprl_tpu.analysis.ir.synth import (
    DREAMER_DISCRETE_OVERRIDES,
    DREAMER_TINY_OVERRIDES,
    compose_tiny,
    sequence_batch,
    tiny_ctx,
    vector_space,
)
from sheeprl_tpu.obs import perf
from sheeprl_tpu.ops import scan_wgrad
from sheeprl_tpu.utils.blocks import make_train_block

T, B, ACTIONS = 12, 3, 3


def plain_dense_scan(step, variables, init, xs, *, unroll=1):
    """The control: the same scan, differentiated by JAX's own transposition."""
    carry, ys = jax.lax.scan(lambda c, x: step(variables, c, x), init, xs, unroll=unroll)
    return carry, ys, {}


@pytest.fixture(autouse=True)
def _clean_perf_registry():
    perf.reset()
    yield
    perf.reset()


def _tiny_agent(decoupled=False, precision="32-true", extra=()):
    cfg = compose_tiny(
        [
            "exp=dreamer_v3_dummy",
            "env=discrete_dummy",
            *DREAMER_TINY_OVERRIDES,
            *DREAMER_DISCRETE_OVERRIDES,
            f"algo.world_model.decoupled_rssm={decoupled}",
            f"mesh.precision={precision}",
            *extra,
        ]
    )
    obs_space = vector_space()
    world_model, actor, critic, params, _ = build_agent(tiny_ctx(cfg), (ACTIONS,), False, cfg, obs_space)
    return cfg, obs_space, world_model, actor, critic, params


def _unroll_inputs(obs_space, resets):
    rng = np.random.default_rng(0)
    obs = {"state": jnp.asarray(rng.standard_normal((T, B, *obs_space["state"].shape)), jnp.float32)}
    actions = jnp.asarray(rng.random((T, B, ACTIONS)), jnp.float32)
    is_first = np.zeros((T, B, 1), np.float32)
    is_first[0] = 1.0
    if resets:
        is_first[5, 1] = is_first[9] = 1.0
    return obs, actions, jnp.asarray(is_first)


def _unroll_value_and_grad(monkeypatch, world_model, wm_params, inputs, scan):
    """Outputs of ``rssm_unroll`` and the gradient of a fixed weighted sum of them with
    respect to the whole world model, under ``scan`` as the unroll's ``dense_scan``."""
    obs, actions, is_first = inputs
    monkeypatch.setattr(dv3, "dense_scan", scan)

    def loss(wm_params):
        embed = world_model.apply(wm_params, obs, method=WorldModel.encode)
        outs = dv3.rssm_unroll(world_model, wm_params, embed, actions, is_first, jax.random.PRNGKey(3))
        weighted = [jnp.sum(o * jnp.cos(0.37 * jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)) for o in outs]
        return sum(weighted), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(wm_params)
    return outs, grads


def _leaf_gaps(grads, reference):
    """Per leaf: ``(path, |g - ref|, |ref|)`` by the Euclidean norm."""
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    return [
        (jax.tree_util.keystr(path), float(jnp.linalg.norm(g - r)), float(jnp.linalg.norm(r)))
        for (path, g), r in zip(flat, jax.tree.leaves(reference))
    ]


@pytest.mark.parametrize("resets", [False, True], ids=["first_at_0", "resets_inside"])
@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_deferred_unroll_equals_plain_autodiff_in_float32(monkeypatch, decoupled, resets):
    _, obs_space, world_model, _, _, params = _tiny_agent(decoupled)
    inputs = _unroll_inputs(obs_space, resets)
    outs, grads = _unroll_value_and_grad(monkeypatch, world_model, params["world_model"], inputs, scan_wgrad.dense_scan)
    deferred = perf._notes["deferred_wgrad"]
    ref_outs, ref_grads = _unroll_value_and_grad(monkeypatch, world_model, params["world_model"], inputs, plain_dense_scan)
    # the representation model of the decoupled RSSM runs outside the scan: its two kernels stay JAX's
    assert deferred["kernels"] == (4 if decoupled else 6) and perf._notes["deferred_wgrad"]["kernels"] == 0
    for out, ref in zip(outs, ref_outs):
        assert np.array_equal(np.asarray(out), np.asarray(ref))
    gaps = _leaf_gaps(grads, ref_grads)
    assert sum(norm > 0 for _, _, norm in gaps) >= 14  # the encoder's and the RSSM's leaves all take a gradient
    for path, gap, norm in gaps:
        assert gap <= 1e-5 * norm, (path, gap, norm)


def test_deferred_unroll_under_bf16_mixed_tracks_the_float32_gradient(monkeypatch):
    """One rounding of each kernel's gradient where the loop made T: within the
    tolerance of the precision tier's parity tests (tests/test_precision), and no
    farther from float32 than the plain bf16 scan is."""
    LOSS_RTOL, LOSS_ATOL = 0.10, 0.05  # tests/test_precision/test_train_parity.py

    _, obs_space, world_model, _, _, params = _tiny_agent(precision="32-true")
    inputs = _unroll_inputs(obs_space, resets=True)
    _, ref = _unroll_value_and_grad(monkeypatch, world_model, params["world_model"], inputs, plain_dense_scan)
    _, _, mixed_model, _, _, mixed_params = _tiny_agent(precision="bf16-mixed")
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(mixed_params["world_model"]))
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(mixed_params)))
    _, grads = _unroll_value_and_grad(monkeypatch, mixed_model, mixed_params["world_model"], inputs, scan_wgrad.dense_scan)
    _, plain = _unroll_value_and_grad(monkeypatch, mixed_model, mixed_params["world_model"], inputs, plain_dense_scan)
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))
    for (path, gap, norm), (_, plain_gap, _) in zip(_leaf_gaps(grads, ref), _leaf_gaps(plain, ref)):
        assert gap <= LOSS_ATOL + LOSS_RTOL * norm, (path, gap, norm)
        assert gap <= 1.5 * plain_gap + 1e-3 * norm, (path, gap, plain_gap, norm)


def test_scan_without_flax_twice_tapped_kernel_and_untapped_parameters():
    """The core: a kernel tapped twice a step on operands of different row counts gets
    both contributions; parameters that no tap names keep JAX's gradient; a body that
    taps nothing is a plain scan."""
    rng = np.random.default_rng(1)
    params = {
        "w": jnp.asarray(rng.standard_normal((5, 5)), jnp.float32) * 0.3,
        "u": jnp.asarray(rng.standard_normal((4, 5)), jnp.float32) * 0.3,
        "b": jnp.asarray(rng.standard_normal(5), jnp.float32),
    }
    xs = jnp.asarray(rng.standard_normal((20, 3, 4)), jnp.float32)
    init = jnp.asarray(rng.standard_normal((3, 5)), jnp.float32)

    def body(params, h, x, tap):
        side = tap("w", params["b"][None], params["b"][None] @ params["w"])  # one row
        h = jnp.tanh(tap("w", h, h @ params["w"] + params["b"]) + tap("u", x, x @ params["u"]) + side)
        return h, h

    deferred = {}

    def loss(params, tapped):
        tap_or_not = body if tapped else (lambda p, h, x, tap: body(p, h, x, lambda name, inp, out: out))
        carry, ys, deferred[tapped] = scan_wgrad.scan(tap_or_not, params, init, xs, unroll=4)
        return jnp.sum(carry) + jnp.sum(ys * ys)

    value, grads = jax.jit(jax.value_and_grad(lambda p: loss(p, True)))(params)
    ref_value, ref = jax.jit(jax.value_and_grad(lambda p: loss(p, False)))(params)
    assert deferred == {True: {"w": (5, 5), "u": (4, 5)}, False: {}} and value == ref_value
    for name in params:
        np.testing.assert_allclose(grads[name], ref[name], rtol=2e-5, atol=1e-6, err_msg=name)


# --------------------------------------------------------------- the compiled block

_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_ARRAY = re.compile(r"^f32\[(\d+),(\d+)\]")


def loops_updating(hlo_text, shapes):
    """``{while instruction: [shape, ...]}``: for every ``while`` of a compiled module,
    the two-dimensional float32 arrays of one of ``shapes`` that its body *changes* from
    one iteration to the next: float32, as the parameters are under every precision
    policy but ``bf16-true``, and so their gradients' accumulators.  (A loop-invariant
    operand, a kernel that a forward or backward step only reads, is in the loop's
    tuple too, but the body hands it on untouched: position ``i`` of the body's root is
    element ``i`` of its parameter.  The TPU compiler also moves bf16 copies of a kernel
    through a loop's tuple to prefetch them, ``ConcatBitcast`` of ``slice-done``.)"""
    bodies, name = {}, ""
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name and _INSTRUCTION.match(line):
            bodies[name].append(_INSTRUCTION.match(line).groups())
    found = {}
    for lines in bodies.values():
        for _, loop, _, opcode, rest in lines:
            if opcode != "while":
                continue
            body = bodies[re.search(r"body=%?([\w.\-]+)", rest).group(1)]
            kinds = {inst: (kind, opcode, rest) for _, inst, kind, opcode, rest in body}
            (root,) = [rest for is_root, _, _, _, rest in body if is_root]
            updated = []
            for position, operand in enumerate(re.findall(r"%([\w.\-]+)", root.split(")")[0])):
                kind, opcode, rest = kinds[operand]
                array = _ARRAY.match(kind)
                passed_on = opcode == "get-tuple-element" and re.search(r"index=(\d+)", rest).group(1) == str(position)
                if array and not passed_on and (int(array.group(1)), int(array.group(2))) in shapes:
                    updated.append((int(array.group(1)), int(array.group(2))))
            found[loop] = updated
    return found


def _compile_tiny_block(tmp_path):
    """The tiny audit block of ``dreamer_v3.lower_for_audit`` with a sequence long
    enough that the RSSM scan stays a loop (unroll 8), registered with the perf plane
    as the training loop registers it."""
    cfg, obs_space, world_model, actor, critic, params = _tiny_agent(extra=["algo.per_rank_sequence_length=24"])
    train_step, init_opt_states = dv3.make_train_step(
        world_model, actor, critic, cfg, [], ["state"], {"state": obs_space["state"].shape}
    )

    def block_step(carry, batch, key, update_target):
        *carry, metrics = train_step(*carry, batch, key, update_target)
        return tuple(carry), metrics

    block = make_train_block(block_step, cfg.algo.critic.per_rank_target_network_update_freq, 1)
    batch = sequence_batch({"state": obs_space["state"].shape}, act_dim=ACTIONS, T=24, B=int(cfg.algo.per_rank_batch_size))
    carry = (params, init_opt_states(params), init_moments())
    perf.PerfPlane({"obs": {"perf": {"enabled": True}}}, log_dir=str(tmp_path))
    compiled = block.lower(carry, (batch,), jax.random.PRNGKey(0), 0).compile()
    perf.register_compiled("dreamer_v3/train_block", compiled)
    rssm = params["world_model"]["params"]["rssm"]
    kernels = {leaf.shape for leaf in jax.tree.leaves(rssm) if leaf.ndim == 2}
    return compiled.as_text(), kernels, json.loads((tmp_path / "scopes" / "dreamer_v3" / "train_block.json").read_text())


def test_no_loop_of_the_compiled_block_updates_a_kernel_shaped_array(tmp_path, caplog):
    with caplog.at_level("INFO", logger="sheeprl_tpu.obs.perf"):
        text, kernels, scope_map = _compile_tiny_block(tmp_path)
    assert len(kernels) == 5  # six kernels; the two logit heads share a shape
    loops = loops_updating(text, kernels)
    backward = [loop for loop in loops if any("world_model/rssm bwd" in key for key in scope_map["ops"].get(loop, {}))]
    assert backward and len(loops) > len(backward)
    assert not any(loops.values()), {loop: shapes for loop, shapes in loops.items() if shapes}
    # the engagement counter: what the trace deferred, in the scope map and in the log
    parameters = sum(i * o for i, o in kernels) + 8 * 16  # the shared shape counts twice
    assert scope_map["deferred_wgrad"] == {"kernels": 6, "parameters": parameters}
    assert any("deferred_wgrad" in record.getMessage() and '"kernels": 6' in record.getMessage() for record in caplog.records)


def test_control_the_plain_scan_carries_every_kernels_accumulator(tmp_path, monkeypatch):
    monkeypatch.setattr(dv3, "dense_scan", plain_dense_scan)
    text, kernels, scope_map = _compile_tiny_block(tmp_path)
    updated = {shape for shapes in loops_updating(text, kernels).values() for shape in shapes}
    assert updated == kernels and scope_map["deferred_wgrad"] == {"kernels": 0, "parameters": 0}
