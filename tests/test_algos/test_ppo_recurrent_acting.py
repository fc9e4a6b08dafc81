"""What crosses the host-device boundary an acting step of recurrent PPO
(``sheeprl_tpu/algos/ppo_recurrent/ppo_recurrent.py``), for its three sequence models at
tiny sizes on the CPU (and for the LSTM over an image key and two action heads): the step's
inputs go into the jitted call as host arrays, one a dtype, the sampling key lives in the
donated carry, and nothing but the one buffer of per-env results comes back.

Each model is run through the CLI for two rollouts with the module's ``jax`` / ``jnp`` seen
through counting proxies, the update wrapped (as the benchmark's adapter wraps it) and a span
tracer active, so the tests read what the program itself did: the calls it made in order, the
rows it stored, the carry it handed to the update and the spans it opened."""

import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as program
from sheeprl_tpu.cli import run
from sheeprl_tpu.obs import perf as obs_perf
from sheeprl_tpu.obs import tracer
from sheeprl_tpu.parallel.mesh import MeshContext

ENVS, STEPS, UPDATES = 4, 8, 2
SMALL = ["algo.mlp_keys.encoder=[state]", "algo.dense_units=8", "algo.rnn.lstm.hidden_size=8", "algo.mlp_layers=1", "algo.per_rank_num_batches=2"]
MODELS = {
    "lstm": ["exp=ppo_recurrent", "env=discrete_dummy", *SMALL],
    "attention": ["exp=ppo_recurrent", "env=discrete_dummy", "algo.sequence_model=attention", "algo.attention.num_heads=2", "algo.attention.window=8", *SMALL],
    # episodes of 3-10 tokens: terminations and truncations (the bootstrap's call) fall inside the rollouts
    "decoder": [
        "exp=ppo_recurrent_decoder", "env.wrapper.min_length=3", "env.wrapper.max_length=10", "algo.decoder.hidden_size=16", "algo.decoder.head_dim=8",
        "algo.decoder.heads_held=2", "algo.decoder.kv_heads_held=1", "algo.decoder.moe_num_primary_experts=4", "algo.decoder.experts_held=4",
        "algo.decoder.moe_ffn_hidden_size=8", "algo.decoder.vocab_held=16", "algo.decoder.layers=2", "algo.decoder.sliding_window_size=4",
        "algo.decoder.cache_capacity=16",
    ],
    # an image key beside the vector key, two action heads
    "pixels": ["exp=ppo_recurrent", "env=multidiscrete_dummy", "algo.cnn_keys.encoder=[rgb]", "algo.encoder.cnn_features_dim=16", *SMALL],
}  # fmt: skip
#: host arrays into the acting call: the vectors of one dtype as one array, an image key a buffer of its own
HOST_ARRAYS_IN = {"lstm": 1, "attention": 1, "decoder": 1, "pixels": 2}


class Counted:
    """A module seen through a proxy: calls of ``names`` are logged, ``jit`` hands back
    functions that log each of their calls under the jitted function's name."""

    def __init__(self, module, names, log):
        self._module, self._names, self._log = module, names, log

    def __getattr__(self, name):
        attr = getattr(self._module, name)
        if name == "jit":
            return lambda fn, **kwargs: CountedJit(attr(fn, **kwargs), self._log)
        if name in self._names:
            return lambda *args, **kwargs: (self._log.append(name), attr(*args, **kwargs))[1]
        return attr


class CountedJit:
    def __init__(self, jitted, log):
        self.__wrapped__, self._log = jitted, log

    def __call__(self, *args, **kwargs):
        self._log.append(self.__wrapped__.__name__)
        return self.__wrapped__(*args, **kwargs)

    def __getattr__(self, name):  # ``lower`` and the rest of the jitted function
        return getattr(self.__wrapped__, name)


def rollouts(model, tmp_path, rank=0, extra=()):
    """Two rollouts and updates of ``model`` from seed 5 through ``cli.run``: the module's calls
    in order, the rows each update was given, the acting call's note, and the spans
    ``(name, start, end, depth)`` in start order."""
    log, updates, notes = [], [], {}
    spans = tracer.SpanTracer()
    real_make, real_local_rng = program.make_ppo_recurrent_train_fn, MeshContext.local_rng

    def make_train_fn(ctx, agent, cfg, obs_keys):
        opt, train_fn = real_make(ctx, agent, cfg, obs_keys)

        def recording(params, opt_state, seq_data, state0, *rest):
            notes.update(obs_perf._notes)
            # the update's own view of the rollout, from the carry of its start, before the update overwrites anything
            logprob, _, values, _ = jax.jit(lambda p, b, s: program.evaluate_sequences(agent, p, b, obs_keys, s))(params, seq_data, state0)
            updates.append(
                {
                    # copies: on the CPU the update's rows can be views of the rollout buffer, which the next rollout overwrites
                    "rows": {k: np.array(seq_data[k]) for k in ("actions", "logprobs", "values")},
                    "evaluated": {"logprobs": np.array(logprob), "values": np.array(values)},
                    "state_leaves": len(jax.tree.leaves(state0)),
                    "parameter_shapes": {x.shape for x in jax.tree.leaves(params)},
                    "live": [(x.shape, str(x.dtype)) for x in jax.live_arrays()],
                }
            )
            log.append("train_fn")
            return train_fn(params, opt_state, seq_data, state0, *rest)

        recording.__wrapped__ = train_fn
        return opt, recording

    def local_rng(self):  # this process's chain as process ``rank`` would seed it; nothing else sees the rank
        log.append("local_rng")
        with mock.patch.object(jax, "process_index", lambda: rank):
            return real_local_rng(self)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(program, "jax", Counted(jax, ("device_put",), log))
        patch.setattr(program, "jnp", Counted(jnp, ("asarray", "array"), log))
        patch.setattr(program, "prepare_obs", lambda *args: log.append("prepare_obs"))
        patch.setattr(program, "make_ppo_recurrent_train_fn", make_train_fn)
        patch.setattr(MeshContext, "local_rng", local_rng)
        patch.setattr(tracer, "_ACTIVE", spans)
        run(
            MODELS[model]
            + [
                f"env.num_envs={ENVS}", f"algo.rollout_steps={STEPS}", f"algo.total_steps={ENVS * STEPS * UPDATES}", "algo.update_epochs=1",
                "algo.run_test=False", "seed=5", "env.sync_env=True", "env.capture_video=False", "checkpoint.every=0", "checkpoint.save_last=False",
                "metric.log_every=100000", "buffer.memmap=False", f"log_root={tmp_path}", *extra,
            ]  # fmt: skip
        )
    assert len(updates) == UPDATES
    events = sorted((e for e in spans.chrome_trace()["traceEvents"] if e["ph"] == "X"), key=lambda e: (e["ts"], -e["dur"]))
    opened = [(e["name"], e["ts"], e["ts"] + e["dur"], e["args"]["depth"]) for e in events]
    return {"log": log, "updates": updates, "notes": notes, "spans": opened}


@pytest.fixture(scope="module")
def first_run(tmp_path_factory):
    """One run a model, shared by the cases below."""
    runs = {}

    def of(model):
        if model not in runs:
            runs[model] = rollouts(model, tmp_path_factory.mktemp(model))
        return runs[model]

    return of


@pytest.mark.parametrize("model", list(MODELS))
def test_the_compiled_call_aliases_the_whole_carry_and_hands_back_the_results_only(first_run, model):
    ran = first_run(model)
    update = ran["updates"][0]
    assert ran["notes"]["acting_boundary"] == {
        "host_arrays_in": HOST_ARRAYS_IN[model],  # observation, previous action and is_first; the decoder's three are one int32 array
        "donated_aliased": update["state_leaves"] + 1,  # every leaf of the state, and the key
        "fresh_out": 1,  # actions, log-probability and value in one buffer
    }


@pytest.mark.parametrize("model", list(MODELS))
def test_nothing_but_the_acting_step_is_dispatched_between_two_acting_steps(first_run, model):
    log = first_run(model)["log"]
    assert not {"asarray", "array", "device_put", "prepare_obs"} & set(log), log
    assert log.count("local_rng") == 1  # the carry's key, once a run
    assert log.count("act") == STEPS * UPDATES
    acts = [i for i, name in enumerate(log) if name == "act"]
    between = [set(log[a + 1 : b]) for a, b in zip(acts, acts[1:])]
    boundaries = [names for names in between if "train_fn" in names]
    assert len(boundaries) == UPDATES - 1
    # inside a rollout: the truncation bootstrap's call, and no other
    assert all(names <= {"value"} for names in between if "train_fn" not in names), log
    if model == "decoder":
        assert {"value"} in between  # an episode was cut inside the rollouts, so the bootstrap's call is covered


@pytest.mark.parametrize("model", ["lstm", "attention", "decoder"])
def test_one_seed_stores_the_same_rows_and_another_rank_does_not(first_run, model, tmp_path):
    first = first_run(model)["updates"]
    again = rollouts(model, tmp_path / "again")["updates"]
    other = rollouts(model, tmp_path / "other", rank=1)["updates"]
    for a, b in zip(first, again):
        for name in ("actions", "logprobs", "values"):
            np.testing.assert_array_equal(a["rows"][name], b["rows"][name], err_msg=name)
    assert any((a["rows"]["actions"] != b["rows"]["actions"]).any() for a, b in zip(first, other))


#: the acting iteration's spans in program order, a letter each: call, fetch, env step, the truncation's bootstrap, store
ITERATION = {"Rollout/act_call": "c", "Rollout/action_fetch": "f", "Rollout/env_step": "e", "Rollout/truncation_value": "t", "Rollout/store": "s"}
#: a cycle's outermost spans in program order: the update boundary's around the rollout and the update
CYCLE = ("Time/rollout_prep", "Time/env_interaction_time", "Time/update_prep", "Time/train_time", "Time/update_after")


@pytest.mark.parametrize("model", list(MODELS))
def test_every_host_phase_of_the_cycle_is_a_span_in_program_order(first_run, model):
    """Each acting iteration opens, in the rollout, the call, the fetch, the env's step, the
    truncation's bootstrap where an episode was cut, and the store; each span of the update
    boundary opens once an update, the update's call and fetch inside ``Time/train_time``."""
    ran = first_run(model)
    opened = ran["spans"]
    cycle = [name for name, _, _, depth in opened if depth == 0 and name in CYCLE]
    assert cycle == list(CYCLE) * UPDATES
    rollouts = [(a, b) for name, a, b, depth in opened if name == "Time/env_interaction_time"]
    bootstraps = 0
    for a, b in rollouts:
        inside = "".join(ITERATION.get(name, "?") for name, s, e, depth in opened if a <= s and e <= b and depth == 1)
        assert re.fullmatch(f"(cfet?s){{{STEPS}}}", inside), inside
        bootstraps += inside.count("t")
    for a, b in [(a, b) for name, a, b, _ in opened if name == "Time/train_time"]:
        assert [name for name, s, e, _ in opened if a <= s and e <= b and name.startswith("Time/update_")] == ["Time/update_call", "Time/update_fetch"]
    # a bootstrap's span for every value call between two acting steps of one rollout, and no other
    acts = [i for i, name in enumerate(ran["log"]) if name == "act"]
    between = [ran["log"][i + 1 : j] for i, j in zip(acts, acts[1:]) if "train_fn" not in ran["log"][i + 1 : j]]
    assert bootstraps == sum(calls.count("value") for calls in between)
    if model == "decoder":
        assert bootstraps >= 1


#: The most the decoder's stored rows may lie from the update's evaluation in bfloat16 (log-probabilities
#: and values, absolute).  Between two readings on the CPU at this file's sizes (PERF.md section 6, PR 33):
#: as the program is, seeds 5-20 read 0.5e-4 to 4.6e-4 in the update that has a filled cache behind it
#: (exactly 0 in the first, whose caches are empty); with a fault planted in the update's attention alone,
#: seeds 5-8 read 4.2e-3 to 8.2e-3 (the cache's keys taken one position late) and 1.4e-2 to 5.8e-2 (the
#: first key block a row visits left unflagged).
DECODER_BF16 = 1.2e-3


@pytest.mark.parametrize("model", list(MODELS))
def test_the_stored_rows_are_what_the_update_evaluates_from_the_carry_of_the_rollouts_start(first_run, model):
    """On-policy before the first step: the log-probabilities and values the acting steps wrote,
    one step at a time through the carry, are those of ``evaluate_sequences`` over the whole
    rollout from ``state0`` (for the decoder: one chunk through the carried caches).  The
    decoder's chunk attends blockwise with an online softmax where its acting step forms the
    scores whole (``ops/blockwise_attention.py``): in this run's bfloat16 the one rounds the
    probabilities before the division by their sum and the other after it, so the two agree to
    a fraction of that dtype's rounding (``DECODER_BF16``) and no longer to float32's; in
    float32 they do (the next test)."""
    atol = DECODER_BF16 if model == "decoder" else 1e-5
    for update in first_run(model)["updates"]:
        np.testing.assert_allclose(update["rows"]["logprobs"], update["evaluated"]["logprobs"], atol=atol)
        np.testing.assert_allclose(update["rows"]["values"], update["evaluated"]["values"], atol=atol)


def test_in_float32_the_decoders_stored_rows_are_the_updates_to_that_dtypes_rounding(tmp_path):
    """The same comparison with nothing narrower than float32 in either program: what is left
    between the acting steps' whole scores and the update's blockwise ones is summation order."""
    for update in rollouts("decoder", tmp_path, extra=["mesh.precision=32-true"])["updates"]:
        np.testing.assert_allclose(update["rows"]["logprobs"], update["evaluated"]["logprobs"], atol=1e-5)
        np.testing.assert_allclose(update["rows"]["values"], update["evaluated"]["values"], atol=1e-5)


def cache_one_position_late(patch):
    """The update's chunk takes the cache's keys for one position later than they are: a
    window's oldest key drops out.  An acting step (one query a row) is left as it is."""
    from sheeprl_tpu.models import decoder

    attend = decoder.grouped_attention

    def late(q, k, v, cache, *rest, **kwargs):
        ck, cv, kv_pos, kv_seg = cache
        if q.shape[1] > 1:
            kv_pos = jnp.where(kv_pos >= 0, kv_pos + 1, kv_pos)
        return attend(q, k, v, (ck, cv, kv_pos, kv_seg), *rest, **kwargs)

    patch.setattr(decoder, "grouped_attention", late)


def first_visited_block_unflagged(patch):
    """The update's kernel skips the first key block that a row should visit."""
    from sheeprl_tpu.ops import blockwise_attention

    flags_of = blockwise_attention.key_block_flags

    def unflagged(*args):
        flags = flags_of(*args)
        return flags * (jnp.arange(flags.shape[1])[None] != flags.argmax(1)[:, None])

    patch.setattr(blockwise_attention, "key_block_flags", unflagged)


def gap(update):
    return max(float(np.abs(update["rows"][name] - update["evaluated"][name]).max()) for name in ("logprobs", "values"))


@pytest.mark.parametrize("fault", [cache_one_position_late, first_visited_block_unflagged], ids=lambda f: f.__name__)
def test_a_fault_planted_in_the_updates_attention_reads_three_times_over_the_bfloat16_bound(fault, tmp_path, monkeypatch):
    """What ``DECODER_BF16`` is for: the rounding stays under it, a wrong mask or a filled block
    skipped does not, in the same bfloat16 run from the same seed."""
    fault(monkeypatch)
    updates = rollouts("decoder", tmp_path)["updates"]
    assert gap(updates[0]) == 0  # nothing is carried into the first rollout: the fault has no key to hide
    assert gap(updates[1]) >= 3 * DECODER_BF16


def test_the_updates_program_notes_the_tile_its_blockwise_attention_took(first_run):
    """``blockwise_attention`` in the scope map of ``ppo_recurrent/train_fn``: an entry a layer
    whose chunk went through the kernel, as ``grouped_attention`` decided it from the shapes of
    that layer's call (8 tokens x 2 query heads on one key head; 16 slots, or the window's 4)."""
    tile = {"query_tile": STEPS * 2, "own_keys": "merged outside the kernel"}
    assert first_run("decoder")["notes"]["blockwise_attention"] == {"layer_0": {**tile, "key_block": 16}, "layer_1": {**tile, "key_block": 4}}


def test_the_acting_copy_of_the_weights_is_gone_when_the_update_runs(tmp_path):
    """The decoder's acting steps read a copy of the matmul weights in the compute dtype, which
    must go before the update needs the room: on the chip a reference kept for the boundary's
    note held 1.3 GB through every update (PERF.md section 6, PR 31)."""
    for update in rollouts("decoder", tmp_path, extra=["mesh.precision=bf16-mixed"])["updates"]:
        copies = [shape for shape, dtype in update["live"] if dtype == "bfloat16" and shape in update["parameter_shapes"]]
        assert not copies, copies
