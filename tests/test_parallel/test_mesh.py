"""Mesh/sharding substrate tests on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel.mesh import MeshContext, build_mesh


def test_virtual_device_count():
    assert len(jax.devices()) == 8


def test_build_mesh_shapes():
    mesh = build_mesh(data=-1)
    assert mesh.shape["data"] == 8
    mesh = build_mesh(data=4, model=2)
    assert mesh.shape["data"] == 4
    assert mesh.shape["model"] == 2
    with pytest.raises(ValueError):
        build_mesh(data=3, model=2)


def test_batch_sharding_and_replication():
    ctx = MeshContext(mesh=build_mesh())
    x = np.arange(16, dtype=np.float32).reshape(16, 1)
    sharded = jax.device_put(x, ctx.batch_sharding())
    assert len(sharded.sharding.device_set) == 8
    rep = ctx.replicate(jnp.ones(4))
    assert rep.sharding.is_fully_replicated


def test_data_parallel_grad_is_global_mean():
    """Loss mean over a sharded batch must produce the same grads as unsharded."""
    ctx = MeshContext(mesh=build_mesh())
    w = jnp.ones((4,))
    x = np.random.default_rng(0).normal(size=(16, 4)).astype(np.float32)

    def loss(w, x):
        return ((x @ w) ** 2).mean()

    g_ref = jax.grad(loss)(w, jnp.asarray(x))
    x_sharded = jax.device_put(x, ctx.batch_sharding())
    w_rep = ctx.replicate(w)
    g_sharded = jax.jit(jax.grad(loss))(w_rep, x_sharded)
    assert np.allclose(np.asarray(g_ref), np.asarray(jax.device_get(g_sharded)), atol=1e-5)


def test_rng_chain_advances():
    ctx = MeshContext(mesh=build_mesh())
    k1, k2 = ctx.rng(), ctx.rng()
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_a_key_chain_refill_is_one_span_in_64_draws_and_the_chain_is_as_it_was():
    from sheeprl_tpu.obs import tracer

    spans = tracer.SpanTracer()
    previous = tracer.set_active(spans)
    try:
        ctx = MeshContext(mesh=build_mesh(), seed=7)
        keys = [ctx.rng() for _ in range(130)]
    finally:
        tracer.set_active(previous)
    assert spans.percentiles()["Time/rng_refill"]["count"] == 3  # draws 1, 65 and 129
    first = jax.random.split(jax.random.PRNGKey(7), 65)
    np.testing.assert_array_equal(np.asarray(keys[0]), np.asarray(first[1]))
    np.testing.assert_array_equal(np.asarray(keys[64]), np.asarray(jax.random.split(first[0], 65)[1]))


def test_precision_policy():
    ctx = MeshContext(mesh=build_mesh(), precision="bf16-mixed")
    assert ctx.compute_dtype == jnp.bfloat16
    assert ctx.param_dtype == jnp.float32
    ctx = MeshContext(mesh=build_mesh(), precision="32-true")
    assert ctx.compute_dtype == jnp.float32


def test_put_batch_replication_fallback_warns_once(caplog):
    """dp>1 with a non-dividing batch must warn (once): silent replication is a perf
    cliff — a multi-chip mesh scaling like one chip with no message (VERDICT r2 #5)."""
    import logging

    ctx = MeshContext(mesh=build_mesh())  # 8-way data mesh
    with caplog.at_level(logging.WARNING, logger="sheeprl_tpu.parallel.mesh"):
        ctx.put_batch({"x": np.zeros((3, 2), np.float32)})  # 3 % 8 != 0
        ctx.put_batch({"x": np.zeros((5, 2), np.float32)})
    warnings = [r for r in caplog.records if "REPLICATED" in r.message]
    assert len(warnings) == 1  # once per run, not per call

    caplog.clear()
    ctx2 = MeshContext(mesh=build_mesh())
    with caplog.at_level(logging.WARNING, logger="sheeprl_tpu.parallel.mesh"):
        out = ctx2.put_batch({"x": np.zeros((16, 2), np.float32)})
    assert not [r for r in caplog.records if "REPLICATED" in r.message]
    assert "data" in str(out["x"].sharding.spec)  # actually sharded
