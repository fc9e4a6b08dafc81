"""``utils/packed.py``: a tree that crosses a jit boundary as one buffer per shape and dtype."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sheeprl_tpu.utils.packed import ALONE_BYTES, Packed, PackSpec, pack


def _tree(scale=1.0):
    """Repeated shapes, mixed dtypes, scalars, a class of one, and an optax state."""
    rng = np.random.default_rng(3)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    params = {
        "dense_0": {"kernel": f32(8, 8), "bias": f32(8)},
        "dense_1": {"kernel": f32(8, 8), "bias": f32(8)},
        "norm": {"scale": f32(8)},
        "head": {"kernel": f32(8, 5)},  # a class of one
        "half": jnp.asarray(rng.standard_normal((8, 8)), jnp.bfloat16),  # the kernels' shape, another dtype
    }
    return params, optax.adam(1e-3).init(params), {"low": jnp.float32(0.25 * scale), "high": jnp.float32(4.0)}, jnp.int32(7)


def _assert_same_tree(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_round_trip_is_bit_identical_and_keeps_the_treedef():
    tree = _tree()
    packed = pack(tree)
    leaves = jax.tree.leaves(tree)
    assert len(packed.spec.slots) == len(leaves) == 25
    # f32 [8,8] x6 (2 kernels, their mu and nu), [8] x9, [8,5] x3, bf16 [8,8] x3, f32 () x2, i32 () x2
    assert sorted(packed.spec.sizes) == [2, 2, 3, 3, 6, 9] and len(packed.buffers) == 6
    assert {b.shape for b in packed.buffers} == {(6, 8, 8), (9, 8), (3, 8, 5), (3, 8, 8), (2,)}
    _assert_same_tree(jax.jit(lambda p: p.unpack())(packed), tree)
    _assert_same_tree(tuple(packed), tree)  # iterates as the tuple it packs
    _assert_same_tree(packed[1], tree[1])  # and indexes as it
    _assert_same_tree(jax.device_get(packed).unpack(), tree)  # on host copies, without a jit


def test_a_class_of_one_keeps_its_buffer_and_a_scalar_stays_a_scalar():
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "n": jnp.int32(4), "b": jnp.ones((5,))}
    packed = pack(tree)
    assert [b.shape for b in packed.buffers] == [(2, 3), (5,), ()]
    assert set(packed.spec.sizes) == {1}
    _assert_same_tree(jax.jit(Packed.unpack)(packed), tree)
    assert int(packed["n"]) == 4


def test_packed_passes_through_jit_device_get_and_tree_map():
    tree = _tree()
    packed = pack(tree)
    doubled = jax.jit(lambda p: jax.tree.map(lambda x: x * 2, p))(packed)
    assert isinstance(doubled, Packed) and doubled.spec == packed.spec
    _assert_same_tree(tuple(doubled), jax.tree.map(lambda x: x * 2, tree))
    host = jax.device_get(packed)
    assert isinstance(host, Packed) and all(isinstance(b, np.ndarray) for b in host.buffers)
    assert len(jax.tree.leaves(packed)) == len(packed.buffers)
    assert "25 leaves in 6 buffers" in repr(packed)


def test_two_trees_of_one_structure_give_one_spec_and_one_program():
    a, b = pack(_tree()), pack(_tree(scale=3.0))
    assert a.spec == b.spec and hash(a.spec) == hash(b.spec)
    assert PackSpec.of(_tree()) == a.spec
    step = jax.jit(lambda p: jax.tree.map(lambda x: x + 1, p))
    out = step(a)
    step(b)
    step(out)  # a result goes back in: the carry of a loop
    assert step._cache_size() == 1
    assert a.spec != pack({"other": jnp.ones((8,))}).spec


def test_a_large_leaf_keeps_a_buffer_of_its_own():
    tree = _tree()
    packed = pack(tree, alone=8 * 8 * 4)  # the float32 kernels of 8 x 8 and above it
    # six kernels (2 x parameter, mu, nu) alone; the bf16 ones (128 bytes) still share a buffer
    assert sorted(packed.spec.sizes) == [1] * 6 + [2, 2, 3, 3, 9]
    assert [b.shape for b in packed.buffers].count((8, 8)) == 6
    _assert_same_tree(tuple(packed), tree)
    assert packed.spec != pack(tree).spec and packed.spec == PackSpec.of(_tree(scale=2.0), alone=256)
    big = {"w": jnp.zeros((512, 512)), "mu": jnp.zeros((512, 512)), "b": jnp.zeros((512,)), "nu_b": jnp.zeros((512,))}
    assert ALONE_BYTES == 512 * 512 * 4 and sorted(pack(big).spec.sizes) == [1, 1, 2]


def test_a_jitted_function_unpacks_at_entry_and_packs_at_exit_by_the_same_spec():
    tree = _tree()
    packed = pack(tree)

    @jax.jit
    def block(carry):
        inner = carry.unpack()
        assert jax.tree.structure(inner) == jax.tree.structure(tree)
        return carry.spec.pack(jax.tree.map(lambda x: x + 1, inner))

    out = block(packed)
    assert isinstance(out, Packed) and out.spec == packed.spec
    _assert_same_tree(tuple(out), jax.tree.map(lambda x: x + 1, tree))
    # the boundary holds the class buffers, not the leaves
    assert block.lower(packed).as_text().count("%arg") >= len(packed.buffers)
    assert len(jax.tree.leaves(jax.eval_shape(block, packed))) == len(packed.buffers)


def test_leaves_sharded_differently_are_never_stacked():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(devices[:2]), ("model",))
    rows, cols, rep = (NamedSharding(mesh, spec) for spec in (P("model", None), P(None, "model"), P()))
    tree = {
        "by_rows": [jax.device_put(jnp.ones((4, 4)), rows), jax.device_put(jnp.zeros((4, 4)), rows)],
        "by_cols": jax.device_put(jnp.full((4, 4), 2.0), cols),
        "replicated": [jax.device_put(jnp.full((4, 4), 3.0), rep), jax.device_put(jnp.full((4, 4), 4.0), rep)],
    }
    packed = pack(tree)
    assert sorted(packed.spec.sizes) == [1, 2, 2]
    _assert_same_tree(dict(zip(tree, (packed[k] for k in tree))), tree)
    # on one device the sharding is no part of the key: all five are one class
    assert PackSpec.of(jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), tree)).sizes == (5,)
