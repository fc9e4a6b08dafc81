"""DreamerV3's train block with its carry handed in packed (``utils/packed.py``): the
same numbers as from the tree, through a jit boundary of one buffer a shape."""

import contextlib
import re

import jax
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
from sheeprl_tpu.analysis.ir.synth import (
    DREAMER_DISCRETE_OVERRIDES,
    DREAMER_TINY_OVERRIDES,
    compose_tiny,
    tiny_ctx,
    vector_space,
)
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import make_mirror_for, make_rb_add, sample_index_block
from sheeprl_tpu.obs import perf
from sheeprl_tpu.utils.blocks import IndexedBlockDispatcher
from sheeprl_tpu.utils.packed import Packed, pack

ACTIONS, T, B, ROWS = 3, 6, 2, 40
ALONE = 512  # bytes: at the tiny sizes 45 of 173 leaves stay alone and 128 are stacked, as XL's large kernels do


@pytest.fixture(scope="module")
def tiny():
    """The tiny audit agent, a filled ring of 40 rows, and the ring dispatcher's block."""
    cfg = compose_tiny(
        ["exp=dreamer_v3_dummy", "env=discrete_dummy", *DREAMER_TINY_OVERRIDES, *DREAMER_DISCRETE_OVERRIDES,
         f"algo.per_rank_sequence_length={T}", f"algo.per_rank_batch_size={B}", "mesh.precision=32-true"]
    )
    obs_space = vector_space()
    world_model, actor, critic, params, _ = build_agent(tiny_ctx(cfg), (ACTIONS,), False, cfg, obs_space)
    train_step, init_opt_states = dv3.make_train_step(
        world_model, actor, critic, cfg, [], ["state"], {"state": obs_space["state"].shape}
    )

    traces = []  # one entry each time a block is traced (the scan traces its step once)

    def block_step(carry, batch, key, update_target):
        traces.append(len(jax.tree.leaves(batch)))
        *carry, metrics = train_step(*carry, batch, key, update_target)
        return tuple(carry), metrics

    rb = EnvIndependentReplayBuffer(64, n_envs=1, obs_keys=["state"], memmap=False, buffer_cls=SequentialReplayBuffer)
    rb.seed(0)
    mirror = make_mirror_for(rb, [], ["state"], obs_space, [("actions", ACTIONS), ("rewards", 1), ("terminated", 1), ("truncated", 1), ("is_first", 1)])
    rb_add = make_rb_add(mirror, rb, contextlib.nullcontext(), 1)
    rng = np.random.default_rng(0)
    for i in range(ROWS):
        rb_add(
            {
                "state": rng.standard_normal((1, 1, 5)).astype(np.float32),
                "actions": np.eye(ACTIONS, dtype=np.float32)[rng.integers(0, ACTIONS, (1, 1))],
                "rewards": rng.standard_normal((1, 1, 1)).astype(np.float32),
                "terminated": np.zeros((1, 1, 1), np.float32),
                "truncated": np.zeros((1, 1, 1), np.float32),
                "is_first": np.full((1, 1, 1), float(i % 17 == 0), np.float32),
            }
        )
    key = jax.random.PRNGKey(5)
    indexed = IndexedBlockDispatcher(block_step, gather_fn=mirror.make_gather_fn(T), target_update_freq=2, base_key=key)
    # on its device as the loop's is (``ctx.shard_params``): a block's result is, and a
    # carry that changes its placement between two calls is traced twice
    carry = jax.device_put((params, init_opt_states(params), init_moments()), jax.devices()[0])
    return {"rb": rb, "mirror": mirror, "indexed": indexed, "carry": carry, "key": key, "traces": traces}


def _same(a, b):
    """Equal trees.  The two programs hold the same arithmetic, and all but a leaf or two
    of several hundred come out bit for bit; XLA's CPU backend fuses the stacked outputs
    with Adam's update and may contract a multiply-add there that it does not contract in
    the tree's program, a unit in the last place of a float32.  Returns the leaves that
    are not bit-identical."""
    assert jax.tree.structure(a) == jax.tree.structure(b)
    inexact = 0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        if not np.array_equal(x, y):
            inexact += 1
            np.testing.assert_allclose(x, y, rtol=2e-6, atol=1e-9)
    return inexact


def test_blocks_from_a_packed_carry_give_what_blocks_from_the_tree_give(tiny):
    """Three blocks of one step each and one block of four steps, each from the state the
    tree's loop has reached, handed in once as the tree and once packed: parameters,
    optimizer state, moments and metrics."""
    indexed, mirror = tiny["indexed"], tiny["mirror"]
    tree, count, inexact = tiny["carry"], 0, 0
    sizes = pack(tree, ALONE).spec.sizes
    assert len(sizes) == 58 and sizes.count(1) == 45 and max(sizes) > 20
    for n in (1, 1, 1, 4):
        envs, starts = sample_index_block(tiny["rb"], B, T, n)
        packed = indexed.dispatch(pack(tree, ALONE), mirror.arrays, envs, starts, count)
        tree = indexed.dispatch(tree, mirror.arrays, envs, starts, count)
        count += n
        assert isinstance(packed, Packed) and isinstance(tree, tuple)
        inexact += _same(tuple(packed), tree)
        metrics_packed, metrics_tree = indexed._futures._pending[-2:]
        assert set(metrics_tree) >= {"Loss/world_model_loss", "Loss/policy_loss", "Loss/value_loss", "Grads/world_model"}
        inexact += _same(metrics_packed, metrics_tree)
    assert inexact <= 4, inexact  # of 4 x (173 leaves + the metrics)
    params0 = tiny["carry"][0]
    assert any(not np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params0), jax.tree.leaves(tree[0])))  # it trained
    # one program a block size for each kind of carry, whatever goes round the loop
    assert len(tiny["traces"]) == 4
    # a packed result goes back in as it is: the loop's carry
    again = indexed.dispatch(packed, mirror.arrays, *sample_index_block(tiny["rb"], B, T, 1), count)
    assert again.spec == packed.spec and len(tiny["traces"]) == 4


def _entry_parameters(lowered):
    (signature,) = re.findall(r"func\.func public @main\((.*?)\) ->", lowered.as_text(), flags=re.S)
    return len(re.findall(r"%arg\d+:", signature))


def test_the_jitted_blocks_boundary_holds_the_class_buffers_not_the_leaves(tiny, caplog, tmp_path):
    """A count, so that a change which lets the leaves back into the call fails here."""
    mirror, key = tiny["mirror"], tiny["key"]
    packed = pack(tiny["carry"])
    leaves, buffers = len(jax.tree.leaves(tiny["carry"])), len(packed.buffers)
    envs, starts = sample_index_block(tiny["rb"], B, T, 1)
    block = tiny["indexed"]._block
    # beside the carry: the ring's arrays that the step reads, the two index arrays, the key, the count
    others = _entry_parameters(block.lower(tiny["carry"], mirror.arrays, envs, starts, key, 0)) - leaves
    assert 4 < others <= len(mirror.arrays) + 4
    assert _entry_parameters(block.lower(packed, mirror.arrays, envs, starts, key, 0)) == buffers + others
    for carry, n in ((packed, buffers), (tiny["carry"], leaves)):  # and as many come out
        assert len(jax.tree.leaves(jax.eval_shape(block, carry, mirror.arrays, envs, starts, key, 0)[0])) == n
    assert leaves == 173 and buffers < 40

    # the engagement counter: the packed block's trace noted what it packed (made here or
    # by the test before: a program is traced once), for the log and the scope map
    import json

    try:
        assert perf._notes["packed_carry"] == {"leaves": leaves, "buffers": buffers}
        perf.PerfPlane({"obs": {"perf": {"enabled": True}}}, log_dir=str(tmp_path))
        with caplog.at_level("INFO", logger="sheeprl_tpu.obs.perf"):
            perf.register_compiled("dreamer_v3/train_block", block.lower(packed, mirror.arrays, envs, starts, key, 0).compile())
        noted = f'"packed_carry": {{"buffers": {buffers}, "leaves": {leaves}}}'
        assert any(noted in record.getMessage() for record in caplog.records)
        scope_map = json.loads((tmp_path / "scopes" / "dreamer_v3" / "train_block.json").read_text())
        assert scope_map["packed_carry"] == {"leaves": leaves, "buffers": buffers} and scope_map["module"] == "jit_block"
    finally:
        perf.reset()
