"""The nine Dreamer entry points run through one host loop (``algos/dreamer/loop.py``):
each leaves the loop's spans, hands its block a packed carry, and writes a checkpoint in
the tree's format that resumes."""

import functools
import weakref

import jax
import pytest

from sheeprl_tpu.algos.dreamer import loop
from sheeprl_tpu.analysis.ir.synth import DREAMER_DISCRETE_OVERRIDES, DREAMER_TINY_OVERRIDES
from sheeprl_tpu.checkpoint.manager import CheckpointManager
from sheeprl_tpu.cli import run
from sheeprl_tpu.obs import tracer as tr
from sheeprl_tpu.utils import blocks

ENTRIES = [
    "dreamer_v3",
    "p2e_dv3_exploration",
    "p2e_dv3_finetuning",
    "dreamer_v2",
    "p2e_dv2_exploration",
    "p2e_dv2_finetuning",
    "dreamer_v1",
    "p2e_dv1_exploration",
    "p2e_dv1_finetuning",
]
STEPS = 24  # policy steps of a training run: twelve iterations of two envs, eight of them train


def _args(entry, root, steps, extra=()):
    base = entry.replace("_exploration", "").replace("_finetuning", "")
    return [
        f"exp={base}_dummy",
        f"algo.name={entry}",
        "env=discrete_dummy",
        *DREAMER_TINY_OVERRIDES,
        *([] if base.endswith("v1") else DREAMER_DISCRETE_OVERRIDES),
        *(["algo.ensembles.n=2", "algo.ensembles.dense_units=8", "algo.ensembles.mlp_layers=1"] if "p2e" in base else []),
        f"algo.total_steps={steps}",
        "algo.learning_starts=8",
        "algo.run_test=False",
        "dry_run=False",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "checkpoint.every=8",
        "checkpoint.save_last=True",
        "metric.log_every=8",
        "buffer.memmap=False",
        "buffer.checkpoint=True",
        "buffer.device=True",
        "mesh.devices=1",  # the HBM ring and its in-jit gather: the dispatcher of the benchmark's cell
        f"log_root={root}",
        *extra,
    ]


def _ckpts(root):
    return sorted(root.rglob("ckpt_*"), key=lambda p: int(p.name.split("_")[1]))


def _traced_run(args):
    """One run of the CLI under a span tracer; the spans it left by name, what its block's
    trace noted (the CLI clears the perf plane's own notes when it returns), and at each
    log flush whether a leaf of the state the entry point handed in was still alive."""
    notes, initial, alive = {}, [], []
    tracer = tr.SpanTracer(rank=0)
    prev, note, pack, rollout_metrics = tr.set_active(tracer), blocks.note, loop.pack, loop.rollout_metrics
    blocks.note = lambda key, value: (notes.__setitem__(key, value), note(key, value))
    loop.pack = lambda tree: (initial.append(weakref.ref(jax.tree.leaves(tree)[0])), pack(tree))[1]
    loop.rollout_metrics = lambda envs: (alive.append(initial[0]() is not None), rollout_metrics(envs))[1]
    try:
        run(args)
    finally:
        tr.set_active(prev)
        blocks.note, loop.pack, loop.rollout_metrics = note, pack, rollout_metrics
    spans = {}
    for event in tracer.chrome_trace()["traceEvents"]:
        if event["ph"] == "X":
            spans[event["name"]] = spans.get(event["name"], 0) + 1
    return spans, notes, alive


@functools.lru_cache(maxsize=None)
def _trained(entry, root):
    """``entry``'s training run, made once in the session (a finetuning case starts from
    its exploration case's last checkpoint)."""
    extra = ()
    if entry.endswith("_finetuning"):
        exploration = _trained(entry.replace("_finetuning", "_exploration"), root)
        extra = (f"checkpoint.exploration_ckpt_path={exploration[0][-1]}", "buffer.load_from_exploration=True")
    spans, notes, alive = _traced_run(_args(entry, root / entry, STEPS, extra))
    return _ckpts(root / entry), spans, notes, alive, extra


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tmp_path_factory.mktemp("dreamer_loop")


@pytest.mark.parametrize("entry", ENTRIES)
def test_entry_point_runs_through_the_one_loop(entry, root):
    ckpts, spans, notes, alive, extra = _trained(entry, root)
    iters = STEPS // 2
    trained = iters - 3  # from iteration 4 on; DV2's ratio gives its first one no gradient step
    # (i) the loop's phases, one span an iteration, and the dispatcher's call inside the dispatch
    assert spans["Time/phase_player"] == spans["Time/phase_env_step"] == spans["Time/phase_buffer_add"] == iters
    assert trained - 1 <= spans["Time/phase_dispatch"] <= trained <= spans["Time/dispatch_call"] + 1
    acted = spans.get("Rollout/policy_dispatch", 0)
    assert acted == (iters if entry.endswith("_finetuning") else iters - 4)  # a pretrained agent never prefills
    # (ii) the block was handed a packed carry: fewer buffers than leaves; and the loop holds
    # the state once (the small leaves handed in were stacked and let go: none outlives the pack)
    assert 0 < notes["packed_carry"]["buffers"] < notes["packed_carry"]["leaves"]
    assert alive == [False] * 3
    # (iii) checkpoints in the tree's format ...
    assert [c.name for c in ckpts] == ["ckpt_8", "ckpt_16", "ckpt_24"]
    state = CheckpointManager.load(ckpts[0])
    trees = {"params", "opt_states"} | ({"moments"} if "v3" in entry else set())
    assert trees <= set(state) and isinstance(state["params"]["world_model"], dict)
    for name in trees:
        assert (ckpts[0] / f"{name}.msgpack").is_file() and (ckpts[0] / f"{name}.template.pkl").is_file()
    leaves = sum(len(jax.tree.leaves(state[name])) for name in trees)
    assert leaves >= notes["packed_carry"]["leaves"]  # a finetuning run trains a slice of what it writes
    assert state["iter_num"] == 4 and state["policy_step"] == 8 and "rb" in state
    if entry.endswith("_finetuning"):
        assert state["actor_type"] == "task" and CheckpointManager.load(ckpts[-1])["actor_type"] == "task"
    # ... that resume (the run's own config decides its length): from the second checkpoint the
    # run goes on through iterations 9-12, acting and filling its ring and training no more
    resumed = root / f"{entry}_resumed"
    more, _, _ = _traced_run(_args(entry, resumed, STEPS, (*extra, f"checkpoint.resume_from={ckpts[1]}")))
    assert more["Time/phase_player"] == 4 and more["Rollout/policy_dispatch"] == 4  # no prefill after a resume
    assert "Time/phase_dispatch" not in more
    later = CheckpointManager.load(_ckpts(resumed)[-1])
    assert later["iter_num"] == 12 and later["policy_step"] == 24 and trees <= set(later)
    if entry.endswith("_finetuning"):
        assert later["actor_type"] == "task"  # what the checkpoint said, though no iteration of the resumed run trained
