"""Where the benchmark touches the DreamerV3 program: three seams, nothing else.

1. ``build_agent``'s parameters are replaced by the benchmark's own weights
   (``reference.make_weights`` from ``--seed``, one jitted call on the device); a
   program whose tree differs from the published layout is refused.
2. ``make_device_replay``'s jitted gradient block is wrapped: the wrapper counts
   gradient steps, keeps the newest metrics future (to drain the device by), and for
   the first three steps records what the plain reference needs to follow them: the
   indices and the key the program drew, the losses it reports, and two small
   vectors of per-leaf norms computed on the device from the program's own state
   (Adam's first moment after step 1, i.e. the first gradient as the optimizer got
   it; the parameters' change after step 3).
3. ``run_block`` and ``rb_add`` get a ``perf_counter`` pair each: the benchmark's own
   spans around the calls into the dispatch/replay layer.

The program itself is the system under test and is not otherwise told that it is
being measured.  The class satisfies ``adapters/base.py``'s ``Adapter``; the traffic's
generator is ``envs/pixel_env.py``.

What ``correct`` compares for this family (``compared``): the three trees' losses and
the batch's mean KL between posterior and prior, by name; and two groups of leaves
that ``reference.leaf_groups`` names: ``transition`` by its worst leaf (the prior's
layers, reached by the dynamic KL term only, so a wrong KL weight or a stop-gradient
on the wrong side shows there and nowhere in a loss) and ``world_model`` pooled (the
large leaves, whose norms a flipped categorical draw hardly moves and a lower
precision does, carry it).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import check
from perfbench.envs import clock, pixel_env

COMPARED_STEPS = 3
TREES = ("world_model", "actor", "critic")
#: the program's own names for what the reference calls each tree's loss and the mean KL
LOSS_KEYS = {"world_model": "Loss/world_model_loss", "actor": "Loss/policy_loss", "critic": "Loss/value_loss", "kl": "State/kl"}


class Span:
    """Accumulating wall-clock span (seconds, calls) on the host's clock."""

    __slots__ = ("seconds", "calls")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.calls = 0

    def snapshot(self) -> Dict[str, float]:
        return {"seconds": self.seconds, "calls": self.calls}


def _timed(fn, adapter: "DreamerV3Adapter", label: str):
    """``fn`` with a ``perf_counter`` pair around it: the span ``label`` accumulates, and
    while a capture runs the interval itself is kept for labelling idle gaps."""
    span = adapter.spans[label]

    def wrapper(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            t1 = time.perf_counter()
            span.seconds += t1 - t0
            span.calls += 1
            if adapter.keep_intervals:
                adapter.intervals.append((label, t0, t1))

    return wrapper


def gather_batch(rows: List[Dict[str, np.ndarray]], envs: np.ndarray, starts: np.ndarray, T: int) -> Dict[str, np.ndarray]:
    """``[T, B, ...]`` sequences from the environment's own rows: batch element ``b``
    is rows ``starts[b] .. starts[b]+T-1`` of env ``envs[b]``."""
    out: Dict[str, list] = {}
    for e, s in zip(envs.tolist(), starts.tolist()):
        have = len(rows[e]["rewards"])
        if s + T > have:
            raise RuntimeError(f"the program sampled rows {s}..{s + T - 1} of env {e}; the environment kept {have}")
        for k, v in rows[e].items():
            out.setdefault(k, []).append(v[s : s + T])
    return {k: np.stack(v, axis=1) for k, v in out.items()}


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])


def _change_norms(ref, S, params, seed):
    """Per-leaf norm of the trained trees' change from the weights the seed gives."""
    import jax

    w0 = ref.make_weights(S, seed)
    return _leaf_norms(jax.tree.map(lambda a, b: a - b, {k: params[k] for k in TREES}, {k: w0[k] for k in TREES}))


def _adam_mu(opt_state):
    """The first-moment tree inside an optax chain state, found by its field name."""
    found = []

    def walk(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer chain, found {len(found)}")
    return found[0]


_REFERENCE_PROGRAMS: Dict[Any, Any] = {}


def _reference_programs(ref, S: Dict[str, Any], quant: str):
    """The reference's three jitted programs (state from the seed, one step, the
    parameters' change), built once per sizes and precision in a process."""
    import json

    import jax

    key = (ref.__name__, json.dumps(S, sort_keys=True), quant)
    if key not in _REFERENCE_PROGRAMS:

        def step(state, batch, base_key, start_count):
            new_state, out = ref.train_step(S, state, batch, ref.step_key(base_key, start_count), quant)
            return new_state, {**out["loss"], "kl": out["kl"]["mean"], "kl_min": out["kl"]["min"]}, _leaf_norms(out["grads"])

        _REFERENCE_PROGRAMS[key] = (
            jax.jit(lambda seed: ref.init_state(ref.make_weights(S, seed))),
            jax.jit(step, donate_argnums=(0,)),
            jax.jit(lambda params, seed: _change_norms(ref, S, params, seed)),
        )
    return _REFERENCE_PROGRAMS[key]


class DreamerV3Adapter:
    def __init__(self, sizes: Dict[str, Any], seed: int, reference):
        self.S = sizes
        self.seed = int(seed)
        self.ref = reference
        self.grad_steps = 0
        self.blocks = 0
        self.last_metrics = None
        self.records: List[Dict[str, Any]] = []
        self.capture_error: Optional[str] = None
        self.spans = {"dispatch": Span(), "buffer_add": Span()}
        self.intervals: List = []  # (label, t0, t1) host spans, kept only while tracing
        self.keep_intervals = False
        self.ring_rows = None
        self._weights_fn = None
        self._norms = None
        self._restore = []

    # ------------------------------------------------------------------ seams
    def install(self) -> None:
        import jax

        from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as program

        S, ref = self.S, self.ref
        self._weights_fn = jax.jit(lambda seed: ref.make_weights(S, seed))

        self._norms = {
            "leaf": jax.jit(_leaf_norms),
            "change": jax.jit(lambda params, seed: _change_norms(ref, S, params, seed)),
        }

        orig_build, orig_replay = program.build_agent, program.make_device_replay
        self._restore = [(program, "build_agent", orig_build), (program, "make_device_replay", orig_replay)]

        def build_agent(ctx, *args, **kwargs):
            world_model, actor, critic, params, latent = orig_build(ctx, *args, **kwargs)
            return world_model, actor, critic, self._inject(ctx, params), latent

        def make_device_replay(*args, **kwargs):
            dispatcher, mirror, prefetcher, run_block, rb_add = orig_replay(*args, **kwargs)
            if mirror is None:
                raise RuntimeError("the cell asks for the HBM ring (buffer.device=True) and the program fell back")
            self.ring_rows = int(mirror.capacity) * int(mirror.n_envs)
            dispatcher._block = self._record(dispatcher._block)
            return (
                dispatcher,
                mirror,
                prefetcher,
                _timed(run_block, self, "dispatch"),
                _timed(rb_add, self, "buffer_add"),
            )

        program.build_agent = build_agent
        program.make_device_replay = make_device_replay

    def uninstall(self) -> None:
        for module, name, value in self._restore:
            setattr(module, name, value)
        self._restore = []

    def seed_array(self):
        import jax.numpy as jnp

        return jnp.asarray(self.seed % (2**31 - 1), jnp.int32)

    def _inject(self, ctx, params):
        import jax

        want = self.ref.flat_shapes(self.S)
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in leaves}
        if have != want:
            diff = sorted(set(have.items()) ^ set(want.items()))[:8]
            raise RuntimeError(f"the program's parameter tree is not the configuration's: {diff}")
        weights = self._weights_fn(self.seed_array())
        return {k: ctx.shard_params(weights[k]) for k in params}

    def _record(self, block):
        def wrapper(carry, mirror, envs, starts, base_key, start_count):
            n = int(envs.shape[0])
            out, metrics = self.call_block(block, carry, mirror, envs, starts, base_key, start_count)
            k = self.grad_steps + 1
            if k <= COMPARED_STEPS and self.capture_error is None:
                if n != 1:
                    self.capture_error = f"gradient step {k} came in a block of {n}: the first three are compared one by one"
                else:
                    rec = {
                        "envs": np.array(envs[0]),
                        "starts": np.array(starts[0]),
                        "start_count": int(start_count),
                        "base_key": base_key,
                        "loss": {t: metrics[name] for t, name in LOSS_KEYS.items()},
                        # everything else the program reports of the step, kept with the raw readings
                        "reported": {name: v for name, v in metrics.items() if getattr(v, "ndim", 1) == 0},
                    }
                    params, opt_states, _ = out
                    if k == 1:
                        rec["grad_norms"] = self._norms["leaf"]({t: _adam_mu(opt_states[t]) for t in TREES})
                    if k == COMPARED_STEPS:
                        rec["change_norms"] = self._norms["change"](params, self.seed_array())
                    self.records.append(rec)
            self.grad_steps += n
            self.blocks += 1
            self.last_metrics = metrics
            return out, metrics

        return wrapper

    def call_block(self, block, *args):
        """The program's jitted gradient block, called as the program calls it (the
        tests plant their faults by overriding this)."""
        return block(*args)

    # ------------------------------------------------------------------ for the harness
    def drain(self) -> None:
        """Wait until every gradient block dispatched so far has left the device."""
        if self.last_metrics is not None:
            import jax

            jax.block_until_ready(self.last_metrics)

    def captured(self) -> bool:
        return len(self.records) >= COMPARED_STEPS or self.capture_error is not None

    def program_readings(self) -> Dict[str, Any]:
        """Host copies of what the program produced in its first three steps."""
        import jax

        if self.capture_error is not None:
            raise RuntimeError(self.capture_error)
        if len(self.records) < COMPARED_STEPS:
            raise RuntimeError(f"only {len(self.records)} gradient steps were captured")
        recs = jax.device_get(self.records)
        return {
            "steps": [
                {
                    "envs": r["envs"],
                    "starts": r["starts"],
                    "start_count": r["start_count"],
                    "base_key": np.asarray(r["base_key"]),
                    "loss": {t: float(v) for t, v in r["loss"].items()},
                    "reported": {name: float(v) for name, v in r["reported"].items()},
                }
                for r in recs
            ],
            # mu_1 = (1 - b1) g_1
            "grad_norms": np.asarray(recs[0]["grad_norms"], np.float64) / (1.0 - self.ref.ADAM_B1),
            "change_norms": np.asarray(recs[COMPARED_STEPS - 1]["change_norms"], np.float64),
        }

    def rows(self) -> List[Dict[str, np.ndarray]]:
        """Per env, the rows the generator kept, in the layout a replay row has."""
        return pixel_env.stored_rows(self.S["actions"])

    def compared(self) -> Dict[str, Any]:
        return {"losses": tuple(LOSS_KEYS), "groups": self.ref.leaf_groups(self.S)}

    def coverage(self, reference: Dict[str, Any]) -> Dict[str, Any]:
        """The KL of the batch's states against the free nats (under them the KL terms are
        constants and the transition model gets no gradient), and the gradient floor."""
        return {
            "free_nats": self.S["kl_free_nats"],
            "kl_mean": [step["kl"] for step in reference["loss"]],
            "kl_min": [step["kl_min"] for step in reference["loss"]],
            **check.grad_floor_coverage(reference),
        }

    def facts(self) -> Dict[str, Any]:
        return {"ring rows": self.ring_rows, "rows kept": sum(len(env.rows) for env in clock.ENVS)}

    def reference_readings(self, rows, program: Dict[str, Any], quant: str = "f32", fault: Optional[str] = None):
        """Follow the program's first three steps with the plain reference, on the
        device the run used (the program's state is gone by now): the weights from the
        seed, batches gathered from the environment's own rows at the indices the
        program drew, the program's key schedule.

        ``quant`` other than ``"f32"`` computes the control; ``fault="half_batch"``
        leaves the second half of every batch out (its rows are replaced by the first
        half's, so the mean is taken over the rest) with the shapes unchanged."""
        import jax
        import jax.numpy as jnp

        ref, S = self.ref, self.S
        T = S["sequence_length"]
        init, step, change = _reference_programs(ref, S, quant)
        state = init(self.seed_array())
        losses, grad_norms = [], None
        for i, rec in enumerate(program["steps"]):
            batch = gather_batch(rows, rec["envs"], rec["starts"], T)
            batch.pop("truncated", None)
            if fault == "half_batch":
                half = batch["rewards"].shape[1] // 2
                batch = {k: np.concatenate([v[:, :half], v[:, :half]], axis=1) for k, v in batch.items()}
            elif fault is not None:
                raise ValueError(f"unknown fault {fault!r}")
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, loss, norms = step(state, batch, jnp.asarray(rec["base_key"]), rec["start_count"])
            losses.append({k: float(v) for k, v in jax.device_get(loss).items()})
            if i == 0:
                grad_norms = np.asarray(jax.device_get(norms), np.float64)
        change_norms = np.asarray(jax.device_get(change(state["params"], self.seed_array())), np.float64)
        del state
        return {"loss": losses, "grad_norms": grad_norms, "change_norms": change_norms}
