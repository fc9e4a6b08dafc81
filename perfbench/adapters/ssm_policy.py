"""``sequence_policy.SequencePolicyAdapter`` for a decoder policy with state-space layers whose
update takes more than one sequence minibatch (``nemotron3nano30b_1of16``): the same seams and
the same comparison, with four things more.

* The update's key is kept for the compared updates, and each compared rollout is handed to
  the reference with the program's own minibatches (``perm``: for every epoch, the envs of
  each sequence minibatch, drawn from the key by the program's own
  ``ppo_recurrent.epoch_keys`` and ``epoch_minibatches``), so that the reference steps Adam
  over the same envs in the same order.
* The dtype of the SSM states that each compared update starts from is held to the
  configuration's (``assumed.ssm_state``: float32): ``ssm_state_float32``, beside the losses,
  reads 1 on the reference's side and on the program's where every Mamba block's carried
  state is float32, 2 where one is not.  No number of the comparison tells a state rounded to
  bfloat16 from a float32 one over the compared rollouts (``PERF.md``, PR 39: the carried
  state itself, by its worst head, reads as far from the reference for the program as for
  that control), so the dtype is held as the configuration states it.
* Every update's ``SSM/resets_in_chunk_share`` is kept (a reference to a device scalar, fetched
  once for the run's log): the share of the scan's (row, chunk) pairs that an episode's
  start cuts, update by update, the timed window's too.
* The printed facts count the carry as this model holds it: the attention blocks' keys and
  values, the Mamba blocks' float32 states and convolution tails.
* Once warm-up is over (the harness's first ``drain()``) and until ``uninstall()``, the counts
  that the harness reads (``grad_steps``, ``blocks``) hold the update in flight in parts: the
  time since its call over the shortest of the last three cycles (call to call: a warm-up cycle
  is longer, by what it compiles, and so is one with a stall), at most a whole update, in whole
  gradient steps. A cycle here is ~2.8 s and a 30 s window holds ~10.7 of them, so a count of
  whole updates alone reads 10 or 11 by where the window's end falls, a step of ~9 % between
  runs of the same code (``PERF.md``, PR 39); in parts, the window's count follows the loop's
  pace. ``grad_steps / blocks`` stays the gradient steps of one update, as the per-step device
  readers divide by it, and the base class's own bookkeeping inside the update sees whole
  counts.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from perfbench.adapters.sequence_policy import COMPARED_STEPS, SequencePolicyAdapter
from perfbench.envs import clock

RESETS = "SSM/resets_in_chunk_share"
FLOAT32 = "ssm_state_float32"


class SsmPolicyAdapter(SequencePolicyAdapter):
    def __init__(self, sizes: Dict[str, Any], seed: int, reference):
        self._parts = False  # count the update in flight in parts (see the module's docstring)
        self._steps_per_update = 1
        self.dispatched: deque = deque(maxlen=4)  # perf_counter at the start of the last updates' calls
        super().__init__(sizes, seed, reference)
        self.keys: List[Any] = []
        self.state_dtypes: List[str] = []  # of the carried SSM states each compared update started from
        self.resets: List[Any] = []

    @property
    def grad_steps(self) -> int:
        return self._whole_steps + self._in_flight()

    @grad_steps.setter
    def grad_steps(self, value: int) -> None:
        self._whole_steps = value

    @property
    def blocks(self) -> float:
        part = self._in_flight()
        return self._whole_blocks + part / self._steps_per_update if part else self._whole_blocks

    @blocks.setter
    def blocks(self, value: int) -> None:
        self._whole_blocks = value

    def _in_flight(self) -> int:
        """Gradient steps of the update in flight: its share of a cycle's time since its
        dispatch, at most one, in whole steps; 0 outside the harness's window reads."""
        t = self.dispatched
        if not self._parts or len(t) < 2:
            return 0
        cycle = min(b - a for a, b in zip(list(t)[:-1], list(t)[1:]))
        return round(self._steps_per_update * min(1.0, (time.perf_counter() - t[-1]) / cycle))

    def drain(self) -> None:
        super().drain()
        self._parts = True  # the harness drains first when warm-up is over

    def uninstall(self) -> None:
        super().uninstall()
        self._parts = False

    def _record(self, train_fn, steps_per_update: int):
        inner = super()._record(train_fn, steps_per_update)
        self._steps_per_update = steps_per_update

        def wrapper(*args):
            self.dispatched.append(time.perf_counter())
            parts, self._parts = self._parts, False
            try:
                return inner(*args)
            finally:
                self._parts = parts

        wrapper.__wrapped__ = train_fn
        return wrapper

    def call_update(self, train_fn, *args):
        if len(self.keys) < COMPARED_STEPS:
            self.keys.append(args[4])
            self.state_dtypes += [str(layer["ssm"].dtype) for layer in args[3]["layers"] if "ssm" in layer]
        out = train_fn(*args)
        if RESETS in out[2]:
            self.resets.append(out[2][RESETS])
        return out

    def minibatches(self, key) -> np.ndarray:
        """``[epochs, minibatches, envs a minibatch]``: the envs of each gradient step of an
        update whose key is ``key``, by the program's own draw."""
        from sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent import epoch_keys, epoch_minibatches

        S = self.S
        return np.stack([np.asarray(epoch_minibatches(k, S["num_envs"], S["num_batches"])) for k in epoch_keys(key, S["update_epochs"])])

    def rollouts(self, rows: Dict[str, np.ndarray], fault: Optional[str] = None) -> List[Dict[str, np.ndarray]]:
        out = super().rollouts(rows, fault)
        for roll, key in zip(out, self.keys):
            roll["perm"] = self.minibatches(key)
        return out

    def carried_dtype_reading(self) -> float:
        """``ssm_state_float32`` on the program's side: 1 where every carried SSM state the
        compared updates started from is float32, else 2."""
        return 1.0 if self.state_dtypes and set(self.state_dtypes) == {"float32"} else 2.0

    def reference_readings(self, rows, program: Dict[str, Any], quant: str = "f32", fault: Optional[str] = None) -> Dict[str, Any]:
        """``SequencePolicyAdapter.reference_readings`` with ``ssm_state_float32`` beside the
        losses: 1 on the reference's side (its states are float32 whatever it rounds), the
        program's reading on the program's."""
        out = super().reference_readings(rows, program, quant, fault)
        for k, loss in enumerate(out["loss"]):
            loss[FLOAT32] = 1.0
            if quant == "f32" and fault is None:
                program["steps"][k]["loss"][FLOAT32] = self.carried_dtype_reading()
        return out

    def compared(self) -> Dict[str, Any]:
        out = super().compared()
        return {**out, "losses": (*out["losses"], FLOAT32)}

    def facts(self) -> Dict[str, Any]:
        import jax

        S = self.S
        half = 2 if S["precision"].startswith("bf16") else 4
        attention = S["pattern"][: S["layers"]].count("*")
        mamba = S["pattern"][: S["layers"]].count("M")
        width = S["mamba_heads"] * S["mamba_head_dim"] + 2 * S["ssm_groups"] * S["ssm_state"]
        return {
            "rollout": f"{S['rollout_steps']} steps x {S['num_envs']} envs, {S['num_batches']} minibatches an epoch",
            "updates recorded": len(self.records),
            "cache bytes": 2 * attention * S["num_envs"] * S["cache_capacity"] * S["kv_heads_held"] * S["head_dim"] * half,
            "ssm state bytes": mamba * S["num_envs"] * S["mamba_heads"] * S["mamba_head_dim"] * S["ssm_state"] * 4,
            "conv tail bytes": mamba * S["num_envs"] * (S["conv_kernel"] - 1) * width * half,
            "rows kept": sum(len(e.rows) for e in clock.ENVS),
            "resets_in_chunk_share by update": [round(float(v), 4) for v in jax.device_get(self.resets)],
        }
