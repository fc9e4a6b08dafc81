"""``PipelinedPlayer``: overlap policy inference, the device→host action copy
and env stepping (Podracer/Sebulba decoupling, PAPERS.md arXiv:2104.06272).

Synchronous acting serializes three things every env step: the env step itself,
the policy dispatch, and the blocking ``device_get`` of the action.  The player
removes the serialization:

* ``pipeline_depth=0`` — synchronous: dispatch the policy, fetch, step.  This is
  bit-for-bit today's acting path (the parity tests assert it) and the default.
* ``pipeline_depth=k>=1`` — *policy-lag* mode: each ``act`` call dispatches the
  policy jit on the newest observation and returns the action of the dispatch
  made ``k`` calls ago, whose device→host copy was started at dispatch time
  (``copy_to_host_async``) and completed while the workers were stepping.  The
  device therefore computes action *t+1* while the env pool executes step *t*,
  and the host never blocks on the device→host copy.  The action applied at step *t* was
  computed from obs *t−k*: an explicit, opt-in policy lag (off-policy algos
  tolerate it; on-policy losses see slightly stale log-probs — see
  ``howto/async_rollout.md``).  While the pipeline fills, the first ``k`` steps
  replay the initial action.

The policy contract keeps all algorithm state in the caller's closure:
``policy(*args) -> device_tree`` (called at dispatch time — recurrent state
threads through device futures without blocking), and
``postprocess(host_tree) -> (env_actions, payload)`` converts the fetched tree
on the host (argmax, clipping, ...).
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Optional, Tuple

import jax

from sheeprl_tpu.obs.tracer import span


def _default_postprocess(fetched: Any) -> Tuple[Any, Any]:
    return fetched, None


def _start_host_copy(tree: Any) -> None:
    """Begin the device→host copy early so the later ``device_get`` is a wait,
    not a round trip (no-op for committed/numpy arrays)."""
    for leaf in jax.tree.leaves(tree):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:  # non-addressable shards etc. — device_get still works
                pass


class PipelinedPlayer:
    def __init__(
        self,
        envs: Any,
        policy: Callable[..., Any],
        postprocess: Optional[Callable[[Any], Tuple[Any, Any]]] = None,
        depth: int = 0,
    ):
        if depth < 0:
            raise ValueError(f"pipeline_depth must be >= 0, got {depth}")
        self.envs = envs
        self.depth = int(depth)
        self._policy = policy
        self._post = postprocess or _default_postprocess
        self._queue: deque = deque()

    # ------------------------------------------------------------------ acting
    def act(self, *args: Any, **kwargs: Any) -> Tuple[Any, Any]:
        """Dispatch the policy; return ``(env_actions, payload)`` — the current
        dispatch's result at depth 0, a ``depth``-lagged one otherwise."""
        with span("Rollout/policy_dispatch"):
            fut = self._policy(*args, **kwargs)
        if self.depth == 0:
            with span("Rollout/action_fetch"):
                return self._post(jax.device_get(fut))
        _start_host_copy(fut)
        self._queue.append(fut)
        if len(self._queue) > self.depth:
            fut = self._queue.popleft()
        else:
            # Pipeline still filling: replay the oldest dispatch's action (it
            # stays queued, so the lag ramps up to ``depth`` over the first calls).
            fut = self._queue[0]
        with span("Rollout/action_fetch"):
            return self._post(jax.device_get(fut))

    def env_step(self, actions: Any):
        """Step the vector env.  With ``depth>=1`` the device is computing the
        next action concurrently — the overlap needs no extra bookkeeping here."""
        with span("Rollout/env_step"):
            return self.envs.step(actions)

    def step(self, *args: Any, **kwargs: Any):
        """Combined ``act`` + ``env_step`` for loops without work between them."""
        env_actions, payload = self.act(*args, **kwargs)
        transition = self.env_step(env_actions)
        return env_actions, payload, transition

    def reset_pipeline(self) -> None:
        """Drop queued dispatches (e.g. when the caller rebuilds its env state)."""
        self._queue.clear()
