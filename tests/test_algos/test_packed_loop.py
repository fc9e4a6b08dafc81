"""DreamerV3's loop holds its train state packed (``utils/packed.py``); what it writes to
disk, a checkpoint or the flight recorder's dump, keeps the tree's format."""

import pickle
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import serialization

from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.cli import run
from sheeprl_tpu.obs.flight_recorder import FlightRecorder
from sheeprl_tpu.utils.packed import pack

DV3 = [
    "exp=dreamer_v3_dummy",
    "env=discrete_dummy",
    "algo.learning_starts=16",
    "algo.run_test=False",
    "dry_run=False",
    "env.num_envs=2",
    "env.sync_env=True",
    "env.capture_video=False",
    "algo.total_steps=96",
    "checkpoint.every=32",
    "checkpoint.save_last=True",
    "metric.log_every=8",
    "buffer.memmap=False",
    "buffer.device=True",
    "mesh.devices=1",  # the HBM ring and its in-jit gather: the dispatcher of the benchmark's cell
]


def _ckpts(root):
    return sorted(root.rglob("ckpt_*"), key=lambda p: int(p.name.split("_")[1]))


def _shapes(ckpt, name):
    """Key paths and shapes of a device tree as it lies in the checkpoint's msgpack."""
    raw = serialization.msgpack_restore((ckpt / f"{name}.msgpack").read_bytes())
    return {jax.tree_util.keystr(path): (leaf.shape, str(leaf.dtype)) for path, leaf in jax.tree_util.tree_flatten_with_path(raw)[0]}


def test_checkpoints_of_the_packed_loop_and_of_a_tree_are_one_format_and_both_resume(tmp_path):
    run(DV3 + [f"log_root={tmp_path / 'packed'}"])
    saved = _ckpts(tmp_path / "packed")[0]  # the first of three: a resume has steps left to run
    assert saved.name == "ckpt_32"
    # the same state written from trees, as the loop wrote it before it held a Packed
    templates = {}
    for name in ("params", "opt_states", "moments"):
        with open(saved / f"{name}.template.pkl", "rb") as f:
            templates[name] = pickle.load(f)  # the structure the manager keeps beside the bytes
    state = {k: v for k, v in CheckpointManager.load(saved, templates=templates).items() if k != "_step"}
    trees = {k: jax.tree.map(jnp.asarray, state[k]) for k in templates}
    assert set(trees["params"]) == {"world_model", "actor", "critic", "target_critic"}
    assert isinstance(trees["opt_states"]["actor"][1][0], optax.ScaleByAdamState)
    from_tree = CheckpointManager(tmp_path / "tree" / "checkpoints").save(32, {**state, **trees})
    shutil.copy(saved.parent.parent / "config.yaml", tmp_path / "tree" / "config.yaml")  # a resume reads the run's
    assert sorted(p.name for p in from_tree.iterdir()) == sorted(p.name for p in saved.iterdir())
    for name in trees:
        assert _shapes(from_tree, name) == _shapes(saved, name) and len(_shapes(saved, name)) > 1
        with open(saved / f"{name}.template.pkl", "rb") as a, open(from_tree / f"{name}.template.pkl", "rb") as b:
            assert jax.tree.structure(pickle.load(a), is_leaf=lambda x: x is None) == jax.tree.structure(pickle.load(b), is_leaf=lambda x: x is None)
    assert len(_shapes(saved, "params")) + len(_shapes(saved, "opt_states")) + len(_shapes(saved, "moments")) == 248
    # resume of either continues to a later checkpoint, whose trees are the same trees
    for origin, ckpt in (("packed", saved), ("tree", from_tree)):
        root = tmp_path / f"resumed_{origin}"
        run(DV3 + [f"checkpoint.resume_from={ckpt}", f"log_root={root}"])
        later = _ckpts(root)[-1]
        # (a resumed run fills its buffer for ``learning_starts`` iterations before it trains again)
        assert later.name == "ckpt_96" and _shapes(later, "opt_states") == _shapes(saved, "opt_states")
        before, after = (CheckpointManager.load(c)["params"]["actor"] for c in (ckpt, later))
        assert any(not np.array_equal(x, y) for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(after)))  # it trained on


def test_flight_recorder_dumps_a_staged_packed_carry_as_its_tree(tmp_path):
    """``make_device_replay`` stages the carry it is handed, a ``Packed`` in DreamerV3's
    loop; ``replay_update`` loads the dump with the tree's templates."""
    params = {"dense": {"kernel": jnp.arange(12.0).reshape(3, 4), "bias": jnp.ones((4,))}, "norm": {"scale": jnp.full((4,), 2.0)}}
    carry = (params, {"world_model": optax.adam(1e-3).init(params)}, {"low": jnp.float32(0.5), "high": jnp.float32(2.0)})
    recorder = FlightRecorder(str(tmp_path))
    recorder.stage_step(carry=pack(carry), base_key=jax.random.PRNGKey(1), scalars={"start_count": 3, "n_steps": 1})
    dump = recorder.dump("test")
    loaded = CheckpointManager.load(f"{dump}/state/ckpt_0", templates={"carry": jax.device_get(carry)})
    assert jax.tree.structure(tuple(loaded["carry"])) == jax.tree.structure(carry)
    for x, y in zip(jax.tree.leaves(tuple(loaded["carry"])), jax.tree.leaves(carry)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert loaded["scalars"]["start_count"] == 3
