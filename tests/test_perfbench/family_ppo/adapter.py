"""Where the benchmark touches the PPO program: the agent's parameters are replaced by
the reference's weights from ``--seed``, and ``PPOTrainFns.train_fn`` (the jitted
update, ``sheeprl_tpu/algos/ppo/ppo.py``) is wrapped to count gradient steps and to
record the first three updates.  Satisfies ``perfbench/adapters/base.py``'s ``Adapter``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from perfbench import check

from . import env as generator

COMPARED_STEPS = 3
#: the program's own names for what the reference calls each loss
LOSS_KEYS = {"policy": "Loss/policy_loss", "value": "Loss/value_loss", "entropy": "Loss/entropy_loss"}


def _leaf_norms(tree):
    """Per-leaf L2 norms of a tree, stacked in ``jax.tree.leaves`` order (on the device)."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])


def _adam_mu(opt_state):
    """The first-moment tree inside an optax chain state, found by its field name: the
    first gradient as the optimizer got it is ``mu_1 / (1 - b1)``."""
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


class PPOAdapter:
    def __init__(self, sizes: Dict[str, Any], seed: int, reference):
        self.S = sizes
        self.seed = int(seed)
        self.ref = reference
        self.grad_steps = 0
        self.blocks = 0
        self.spans: Dict[str, Any] = {}
        self.intervals: List = []
        self.keep_intervals = False
        self.records: List[Dict[str, Any]] = []
        self.last = None
        self._restore = []

    def seed_array(self):
        import jax.numpy as jnp

        return jnp.asarray(self.seed % (2**31 - 1), jnp.int32)

    # ------------------------------------------------------------------ seams
    def install(self) -> None:
        import jax

        from sheeprl_tpu.algos.ppo import ppo as program

        adapter, S, ref = self, self.S, self.ref
        weights = jax.jit(lambda seed: ref.make_weights(S, seed))
        self._change = jax.jit(lambda params, seed: _leaf_norms(jax.tree.map(lambda a, b: a - b, params, ref.make_weights(S, seed))))
        orig_build, orig_fns = program.build_agent, program.PPOTrainFns
        self._restore = [(program, "build_agent", orig_build), (program, "PPOTrainFns", orig_fns)]

        def build_agent(ctx, *args, **kwargs):
            agent, params = orig_build(ctx, *args, **kwargs)
            have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
            if have != ref.flat_shapes(S):
                raise RuntimeError(f"the program's parameter tree is not the configuration's: {sorted(set(have.items()) ^ set(ref.flat_shapes(S).items()))[:8]}")
            return agent, ctx.replicate(weights(self.seed_array()))

        class RecordedTrainFns(orig_fns):
            def __init__(fns, *args, **kwargs):
                super().__init__(*args, **kwargs)
                fns.train_fn = adapter._record(fns.train_fn, fns.grad_steps_per_update)

        program.build_agent = build_agent
        program.PPOTrainFns = RecordedTrainFns

    def uninstall(self) -> None:
        for module, name, value in self._restore:
            setattr(module, name, value)
        self._restore = []

    def _record(self, train_fn, steps_per_update: int):
        def wrapper(params, opt_state, data, key, clip_coef, ent_coef):
            params, opt_state, metrics = train_fn(params, opt_state, data, key, clip_coef, ent_coef)
            k = self.blocks + 1
            if k <= COMPARED_STEPS:
                rec = {"loss": {name: metrics[key] for name, key in LOSS_KEYS.items()}, "reported": dict(metrics)}
                if k == 1:
                    rec["grad_norms"] = _leaf_norms(_adam_mu(opt_state))
                if k == COMPARED_STEPS:
                    rec["change_norms"] = self._change(params, self.seed_array())
                self.records.append(rec)
            self.grad_steps += steps_per_update
            self.blocks += 1
            self.last = params
            return params, opt_state, metrics

        return wrapper

    # ------------------------------------------------------------------ for the harness
    def drain(self) -> None:
        if self.last is not None:
            import jax

            jax.block_until_ready(self.last)

    def captured(self) -> bool:
        return len(self.records) >= COMPARED_STEPS

    def program_readings(self) -> Dict[str, Any]:
        import jax

        if len(self.records) < COMPARED_STEPS:
            raise RuntimeError(f"only {len(self.records)} updates were captured")
        recs = jax.device_get(self.records)
        return {
            "steps": [{"loss": {k: float(v) for k, v in r["loss"].items()}, "reported": {k: float(v) for k, v in r["reported"].items()}} for r in recs],
            # the first moment after the first update's epochs, as Adam holds it: both sides alike
            "grad_norms": np.asarray(recs[0]["grad_norms"], np.float64),
            "change_norms": np.asarray(recs[COMPARED_STEPS - 1]["change_norms"], np.float64),
        }

    def rows(self) -> Dict[str, np.ndarray]:
        return generator.stored_rows()

    def reference_readings(self, rows, program: Dict[str, Any]) -> Dict[str, Any]:
        """The reference following the program's first three updates over the
        environment's own transitions, from the seed's weights."""
        import jax
        import jax.numpy as jnp

        ref, S, T = self.ref, self.S, self.S["rollout_steps"]
        if len(rows["reward"]) < COMPARED_STEPS * T + 1:
            raise RuntimeError(f"the environment kept {len(rows['reward'])} transitions an env; three updates need {COMPARED_STEPS * T + 1}")
        update = jax.jit(lambda state, rollout: ref.update(S, state, rollout))
        state = ref.init_state(ref.make_weights(S, self.seed_array()))
        losses, grad_norms = [], None
        for k in range(COMPARED_STEPS):
            rollout = {name: jnp.asarray(v[k * T : (k + 1) * T]) for name, v in rows.items()}
            rollout["next_obs"] = jnp.asarray(rows["obs"][(k + 1) * T])
            state, loss = update(state, rollout)
            losses.append({name: float(v) for name, v in jax.device_get(loss).items()})
            if k == 0:
                grad_norms = np.asarray(jax.device_get(_leaf_norms(state["mu"])), np.float64)
        w0 = ref.make_weights(S, self.seed_array())
        change = _leaf_norms(jax.tree.map(lambda a, b: a - b, state["params"], w0))
        return {"loss": losses, "grad_norms": grad_norms, "change_norms": np.asarray(jax.device_get(change), np.float64)}

    def compared(self) -> Dict[str, Any]:
        return {"losses": tuple(LOSS_KEYS), "groups": {}}

    def coverage(self, reference: Dict[str, Any]) -> Dict[str, Any]:
        return check.grad_floor_coverage(reference)

    def facts(self) -> Dict[str, Any]:
        return {"rollout": f"{self.S['rollout_steps']} steps", "updates recorded": len(self.records)}
