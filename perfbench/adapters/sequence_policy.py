"""Where the benchmark touches recurrent PPO with the decoder as its sequence policy
(``sheeprl_tpu/algos/ppo_recurrent/ppo_recurrent.py``, ``algo.sequence_model=decoder``):

1. ``build_agent``'s parameters are replaced by the benchmark's own weights
   (``reference.make_weights`` from ``--seed``, one jitted call on the device); a
   program whose tree differs from the configuration's layout is refused;
2. the jitted update that ``make_ppo_recurrent_train_fn`` returns is wrapped: the
   wrapper counts gradient steps (``update_epochs`` x minibatches an update), keeps a
   ``perf_counter`` pair around the call, and for the first three updates records
   the losses the program reports, the rollout's log-probabilities as the acting path
   wrote them, and two small vectors of per-leaf norms from the program's own state
   (Adam's first moment after update 1; the parameters' change after update 3).

Nothing else of the program is told that it is measured.  The class satisfies
``adapters/base.py``'s ``Adapter``; the generator is ``envs/token_env.py``.  (The file
is not named after the algorithm: ``tests/test_perfbench/test_extend.py`` holds that no
file under ``perfbench/`` carries its stand-in family's name.)

What ``correct`` compares (``compared``): the three losses by name, averaged over an
update's epochs as the program reports them; ``old_logprob``, the distance per token
between the log-probabilities that the acting path wrote through its caches during
the rollout and those of the reference's full forward pass over the env's rows (root
of the mean square of the difference over that of the reference's: the cache's
proof); gradient norms pooled over the experts, the routers, the attention
projections and the two tables; and the parameters' change.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import check
from perfbench.envs import clock, token_env

COMPARED_STEPS = 3
#: the program's own names for what the reference calls each loss
LOSS_KEYS = {"policy": "Loss/policy_loss", "value": "Loss/value_loss", "entropy": "Loss/entropy_loss"}


class Span:
    __slots__ = ("seconds", "calls")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.calls = 0

    def snapshot(self) -> Dict[str, float]:
        return {"seconds": self.seconds, "calls": self.calls}


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])


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


def _rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


class SequencePolicyAdapter:
    def __init__(self, sizes: Dict[str, Any], seed: int, reference):
        self.S = sizes
        self.seed = int(seed)
        self.ref = reference
        self.grad_steps = 0
        self.blocks = 0
        self.spans = {"dispatch": Span()}
        self.intervals: List = []
        self.keep_intervals = False
        self.records: List[Dict[str, Any]] = []
        self.last = None
        self._ref_logp: Optional[List[np.ndarray]] = None
        self._chosen: Optional[np.ndarray] = None  # the float32 reference's expert choices in the first rollout
        self._restore = []

    def seed_array(self):
        import jax.numpy as jnp

        return jnp.asarray(self.seed % (2**31 - 1), jnp.int32)

    # ------------------------------------------------------------------ seams
    def install(self) -> None:
        import jax

        from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent as program

        S, ref = self.S, self.ref
        weights = jax.jit(lambda seed: ref.make_weights(S, seed))
        orig_build, orig_train = program.build_agent, program.make_ppo_recurrent_train_fn
        self._restore = [(program, "build_agent", orig_build), (program, "make_ppo_recurrent_train_fn", orig_train)]

        def build_agent(ctx, *args, **kwargs):
            agent, params = orig_build(ctx, *args, **kwargs)
            have = {"/".join(str(k.key) for k in path): tuple(x.shape) for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
            if have != ref.flat_shapes(S):
                raise RuntimeError(f"the program's parameter tree is not the configuration's: {sorted(set(have.items()) ^ set(ref.flat_shapes(S).items()))[:8]}")
            del params
            return agent, ctx.replicate(weights(self.seed_array()))

        def make_train_fn(ctx, agent, cfg, obs_keys):
            opt, train_fn = orig_train(ctx, agent, cfg, obs_keys)
            steps = int(cfg.algo.update_epochs) * max(int(cfg.algo.per_rank_num_batches), 1)
            if (int(cfg.env.num_envs), int(cfg.algo.rollout_steps), int(cfg.algo.update_epochs)) != (S["num_envs"], S["rollout_steps"], S["update_epochs"]):
                raise RuntimeError("the traffic's envs, rollout and epochs are not the configuration's sizes as run")
            return opt, self._record(train_fn, steps)

        program.build_agent = build_agent
        program.make_ppo_recurrent_train_fn = make_train_fn

    def uninstall(self) -> None:
        for module, name, value in self._restore:
            setattr(module, name, value)
        self._restore = []

    def call_update(self, train_fn, *args):
        """The program's jitted update, called as the program calls it (the tests plant
        their faults by overriding this)."""
        return train_fn(*args)

    def _record(self, train_fn, steps_per_update: int):
        span = self.spans["dispatch"]

        def wrapper(params, opt_state, seq_data, state0, key, clip_coef, ent_coef):
            logprobs = seq_data["logprobs"]
            t0 = time.perf_counter()
            params, opt_state, metrics = self.call_update(train_fn, params, opt_state, seq_data, state0, key, clip_coef, ent_coef)
            t1 = time.perf_counter()
            span.seconds += t1 - t0
            span.calls += 1
            if self.keep_intervals:
                self.intervals.append(("dispatch", t0, t1))
            k = self.blocks + 1
            if k <= COMPARED_STEPS:
                rec = {"loss": {name: metrics[key] for name, key in LOSS_KEYS.items()}, "reported": dict(metrics), "logprobs": logprobs}
                if k == 1:
                    rec["grad_norms"] = self._norms_of(_adam_mu(opt_state))
                if k == COMPARED_STEPS:
                    rec["change_norms"] = self._change_of(params)
                self.records.append(rec)
            self.grad_steps += steps_per_update
            self.blocks += 1
            self.last = metrics
            return params, opt_state, metrics

        wrapper.__wrapped__ = train_fn  # the program's cost-model registration lowers the jitted function itself
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
            "steps": [
                {
                    "loss": {k: float(v) for k, v in r["loss"].items()},
                    "reported": {k: float(v) for k, v in r["reported"].items()},
                    "logprobs": np.asarray(r["logprobs"], np.float64),
                }
                for r in recs
            ],
            # the first moment after the first update's epochs, as Adam holds it: both sides alike
            "grad_norms": np.asarray(recs[0]["grad_norms"], np.float64),
            "change_norms": np.asarray(recs[COMPARED_STEPS - 1]["change_norms"], np.float64),
        }

    def rows(self) -> Dict[str, np.ndarray]:
        return token_env.stored_rows()

    def rollouts(self, rows: Dict[str, np.ndarray], fault: Optional[str] = None) -> List[Dict[str, np.ndarray]]:
        """The environment's rows cut into the three rollouts, each with what the policy
        was shown beside the observation: the previous action, whether an episode starts,
        and every token's episode and position."""
        T = self.S["rollout_steps"]
        need = COMPARED_STEPS * T + 1
        if len(rows["obs"]) < need:
            raise RuntimeError(f"the environment kept {len(rows['obs'])} rows an env; three updates need {need}")
        if fault == "half_batch":
            half = rows["obs"].shape[1] // 2
            rows = {k: np.concatenate([v[:, :half], v[:, :half]], axis=1) for k, v in rows.items()}
        elif fault not in (None, "planted"):  # "planted": the caller's sizes are the fault
            raise ValueError(f"unknown fault {fault!r}")
        done = (rows["terminated"] + rows["truncated"] > 0).astype(np.float32)
        is_first = np.concatenate([np.ones_like(done[:1]), done[:-1]], 0)
        prev = np.concatenate([np.zeros_like(rows["action"][:1]), rows["action"][:-1]], 0) * (1 - is_first).astype(rows["action"].dtype)
        N = done.shape[1]
        ep, pos, _, _ = self.ref.episodes_and_positions(is_first[:need].T, np.zeros(N, np.int32), np.zeros(N, np.int32))
        ep, pos = ep.T, pos.T
        out = []
        for k in range(COMPARED_STEPS):
            a, b = k * T, (k + 1) * T
            out.append(
                {
                    "obs": rows["obs"][a:b], "prev": prev[a:b], "is_first": is_first[a:b], "pos": pos[a:b], "ep": ep[a:b],
                    "action": rows["action"][a:b], "reward": rows["reward"][a:b], "done": done[a:b], "truncated": rows["truncated"][a:b],
                    "final_obs": rows["final_obs"][a:b],
                    "next_obs": rows["obs"][b : b + 1], "next_prev": prev[b : b + 1], "next_is_first": is_first[b : b + 1],
                    "next_pos": pos[b : b + 1], "next_ep": ep[b : b + 1],
                }
            )  # fmt: skip
        return out

    def reference_readings(self, rows, program: Dict[str, Any], quant: str = "f32", fault: Optional[str] = None) -> Dict[str, Any]:
        """The plain reference following the program's first three updates over the
        environment's own rows, from the seed's weights.  ``quant`` other than ``"f32"``
        computes the control; ``fault="half_batch"`` gives the second half of the envs the
        first half's rows (``"planted"``: the rows as they are, for an adapter built with wrong
        sizes).  ``old_logprob`` is a distance per token, so each side's number is
        made here: the reference's is the root mean square of its own log-probabilities,
        the other side's (the program's, written into ``program``; a control's, returned) is
        that plus the root mean square of its difference from the float32 reference's."""
        import jax

        ref, S = self.ref, self.S
        if (quant != "f32" or fault is not None) and self._ref_logp is None:
            self.reference_readings(rows, program)
        update = jax.jit(lambda state, context, roll: ref.update(S, state, context, roll, quant), donate_argnums=(0, 1))
        state = jax.jit(lambda seed: ref.init_state(ref.make_weights(S, seed)))(self.seed_array())
        rolls = self.rollouts(rows, fault)
        context = ref.empty_context(S, rolls[0]["obs"].shape[1], COMPARED_STEPS * S["rollout_steps"])
        losses, logps, grad_norms = [], [], None
        for k, roll in enumerate(rolls):
            state, context, loss, logp, chosen = update(state, context, roll)
            losses.append({name: float(v) for name, v in jax.device_get(loss).items()})
            logps.append(np.asarray(jax.device_get(logp), np.float64))
            if k == 0:
                grad_norms = np.asarray(jax.device_get(self._norms_of(state["mu"])), np.float64)
                self._chosen = np.asarray(jax.device_get(chosen))
        change = np.asarray(jax.device_get(self._change_of(state["params"])), np.float64)
        del state, context
        plain = quant == "f32" and fault is None
        if plain:
            self._ref_logp = logps
        for k, loss in enumerate(losses):
            base = _rms(self._ref_logp[k])
            loss["old_logprob"] = base if plain else base + _rms(logps[k] - self._ref_logp[k])
            if plain:
                program["steps"][k]["loss"]["old_logprob"] = base + _rms(program["steps"][k]["logprobs"] - logps[k])
        return {"loss": losses, "grad_norms": grad_norms, "change_norms": change}

    def _norms_of(self, tree):
        """Per-leaf norms of a tree, on the device."""
        import jax

        return jax.jit(_leaf_norms)(tree)

    def _change_of(self, params):
        """Per-leaf norms of the parameters' change from the weights the seed gives."""
        import jax

        ref, S = self.ref, self.S
        return jax.jit(lambda p, seed: _leaf_norms(jax.tree.map(lambda a, b: a - b, p, ref.make_weights(S, seed))))(params, self.seed_array())

    def compared(self) -> Dict[str, Any]:
        return {"losses": (*LOSS_KEYS, "old_logprob"), "groups": self.ref.leaf_groups(self.S)}

    def coverage(self, reference: Dict[str, Any]) -> Dict[str, Any]:
        """Episode ends inside the compared rollouts, the update's own counters as the three
        compared updates reported them, and how many of the first rollout's
        expert choices differ between the float32 reference and the same pass with its
        matmul operands rounded to bfloat16 (the program's arithmetic, near enough): a
        top-k choice on a tie flips on rounding, and a flipped token reads other experts."""
        import jax

        ref, S = self.ref, self.S
        rows = self.rows()
        n = COMPARED_STEPS * S["rollout_steps"]
        out = {
            "terminated_in_compared_rows": int(rows["terminated"][:n].sum()),
            "truncated_in_compared_rows": int(rows["truncated"][:n].sum()),
            **check.grad_floor_coverage(reference),
        }
        reported = jax.device_get([r["reported"] for r in self.records[:COMPARED_STEPS]])
        for name in ("Health/ratio_first_epoch", "MoE/dropped", "MoE/held_share"):
            out[name] = [float(r[name]) for r in reported if name in r]
        if self._chosen is not None:
            roll = self.rollouts(rows)[0]
            weights = jax.jit(lambda seed: ref.make_weights(S, seed))(self.seed_array())
            rounded = jax.jit(lambda w, r: ref.rollout_pass(S, w, ref.empty_context(S, r["obs"].shape[1], S["rollout_steps"]), r, "bf16")["chosen"])(weights, roll)
            a, b = np.sort(self._chosen, -1), np.sort(np.asarray(jax.device_get(rounded)), -1)
            out["expert_choices_in_first_rollout"] = int(a.size)
            out["expert_choices_flipped_by_bf16"] = int((a != b).any(-1).sum())
            out["tokens_x_layers"] = int(a.shape[0] * a.shape[1] * a.shape[2])
        return out

    def facts(self) -> Dict[str, Any]:
        S = self.S
        slots = sum(S["window"] if w else S["cache_capacity"] for w in S["window_layout"][: S["layers"]])
        cache = 2 * S["num_envs"] * slots * S["kv_heads_held"] * S["head_dim"] * (2 if S["precision"].startswith("bf16") else 4)
        return {"rollout": f"{S['rollout_steps']} steps x {S['num_envs']} envs", "updates recorded": len(self.records), "cache bytes": cache, "rows kept": sum(len(e.rows) for e in clock.ENVS)}
