"""``perfbench/flops.py`` against XLA's own count of the plain reference's step.

With one RSSM step and one imagination step (sequence 1, horizon 1) every scan body
runs once, so XLA's ``cost_analysis()`` sees the whole step; the shape count holds
matmuls and convolutions only, XLA's also the elementwise work, which at these widths
is a few percent of the total: the two agree within 0.9 .. 1.05 (0.976 here).  With
longer scans XLA counts each scan body (the RSSM step, the imagination step) once and so
reads *lower* than the shape count: by 13% at sequence 8 / horizon 5 and by 24% at 32 / 15
at these widths, and by more where the scans hold more of the work.  That is why the
benchmark's ``step_mfu`` stands on shapes and prints XLA's count only beside it.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops
from perfbench.reference import dreamer_v3 as ref

ROOT = Path(__file__).resolve().parents[2]


def sizes(**kw):
    S = json.loads((ROOT / "perfbench" / "configs" / "dv3_XL.json").read_text())["sizes"]
    S.update(
        dense_units=128, mlp_layers=2, cnn_channels_multiplier=8, recurrent_state_size=256, transition_hidden_size=128,
        representation_hidden_size=128, stochastic_size=16, discrete_size=16, reward_bins=63, critic_bins=63, batch_size=4,
    )
    S.update(kw)
    return S


def xla_flops(S):
    T, B, A = S["sequence_length"], S["batch_size"], S["actions"]
    state = jax.eval_shape(lambda s: ref.init_state(ref.make_weights(S, s)), jnp.int32(0))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    batch = {
        "rgb": jax.ShapeDtypeStruct((T, B, 3, 64, 64), jnp.uint8),
        "reward": f32(T, B, 1),
        "actions": f32(T, B, A),
        "rewards": f32(T, B, 1),
        "terminated": f32(T, B, 1),
        "is_first": f32(T, B, 1),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    compiled = jax.jit(lambda s, b, k: ref.train_step(S, s, b, k)).lower(state, batch, key).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_shape_count_matches_xla_where_no_scan_repeats():
    S = sizes(sequence_length=1, horizon=1)
    ratio = flops.step_flops(S)["total"] / xla_flops(S)
    assert 0.9 <= ratio <= 1.05, ratio


def test_xla_counts_a_scan_body_once():
    S = sizes(sequence_length=8, horizon=5)
    ratio = flops.step_flops(S)["total"] / xla_flops(S)
    assert 1.08 < ratio < 1.3, ratio


def test_components_add_up_and_scale_with_the_batch():
    S = json.loads((ROOT / "perfbench" / "configs" / "dv3_XL.json").read_text())["sizes"]
    f = flops.step_flops(S)
    parts = ("world_model", "imagination", "behaviour_heads_fwd", "actor_train", "critic_train", "optimizer")
    assert f["total"] == pytest.approx(sum(f[p] for p in parts))
    assert f["optimizer"] < 0.01 * f["total"]
    doubled = flops.step_flops({**S, "batch_size": 2 * S["batch_size"]})
    assert (doubled["total"] - doubled["optimizer"]) == pytest.approx(2 * (f["total"] - f["optimizer"]))
    n = flops.count_params(S)
    assert 2.0e8 < n["world_model"] + n["actor"] + n["critic"] < 2.3e8  # DreamerV3-XL, ~210 M
