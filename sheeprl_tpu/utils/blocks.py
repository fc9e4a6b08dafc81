"""Batched gradient-step dispatch: run G gradient steps as ONE jitted call.

The reference dispatches each gradient step eagerly (its train() call per step,
``/root/reference/sheeprl/algos/dreamer_v3/dreamer_v3.py:682``); every dispatch
costs host time (argument handling, the runtime call, a pass of params/opt-state
through the program boundary), and with replay ratios of 0.5–1 and a ~20 ms step
that per-call overhead is paid once per gradient step.  Here
the per-step batches are stacked to ``[G, T, B, ...]`` and a ``lax.scan`` over the
leading axis executes the whole block inside one jit:

* ONE dispatch (and one traversal of params/opt-state through the program) per
  iteration instead of G;
* per-step PRNG keys are split INSIDE the jit from a single base key (no per-step
  host-side key-split round trips);
* the ``update_target`` cadence (every Nth cumulative step) is computed inside the
  scan from the starting step count.

``G`` is a static shape, so each distinct block size compiles once.  ``chunk_sizes``
decomposes large/irregular G (e.g. the Ratio governor's one-off pretrain burst) into
a bounded set of sizes — powers of two up to ``max_chunk`` — keeping the number of
compiled programs small no matter what replay ratio the user picks.
"""

from __future__ import annotations

from typing import Any, Callable, List, Sequence

import jax
import jax.numpy as jnp

from sheeprl_tpu.obs.perf import note, scope, scopes_tag
from sheeprl_tpu.utils.packed import Packed
from sheeprl_tpu.utils.timer import timer


def chunk_sizes(n: int, max_chunk: int = 8) -> List[int]:
    """Decompose ``n`` into descending powers of two ≤ ``max_chunk``.

    Every chunk size is a power of two, so across a whole run only
    ``log2(max_chunk)+1`` distinct block programs ever compile.
    """
    if n <= 0:
        return []
    out: List[int] = []
    size = max_chunk
    while n > 0 and size > 1:
        while n >= size:
            out.append(size)
            n -= size
        size //= 2
    out.extend([1] * n)
    return out


def _open(carry):
    """A block's carry as the tree its steps work on, and what turns the result back into
    what came in.  A ``Packed`` carry (``utils/packed.py``) crosses the jit boundary as
    one buffer a shape and is a tree in between; the trace notes how many leaves came in
    how many buffers.  Any other carry passes as it is: its block is the program it was."""
    if not isinstance(carry, Packed):
        return carry, lambda tree: tree
    note("packed_carry", {"leaves": len(carry.spec.slots), "buffers": len(carry.buffers)})
    return carry.unpack(), carry.spec.pack


def make_train_block(step_fn: Callable, target_update_freq: int = 1, count_offset: int = 1) -> Callable:
    """Wrap a per-step ``step_fn(carry, batch, key, update_target) -> (carry,
    metrics)`` into a jitted ``block(carry, stacked_batch, base_key, start_count)``
    that scans over the leading ``G`` axis of ``stacked_batch``.

    ``carry`` is the algorithm's whole train state pytree (params, optimizer states,
    moments, ...).  ``start_count`` is the cumulative gradient-step count BEFORE this
    block; each scan step's ``update_target`` flag is computed from it, matching the
    eager loop's ``cumulative % freq == 0`` cadence — with ``count_offset=1`` the
    count is tested AFTER the increment (DV3), with ``0`` before it (DV2's hard copy
    fires on the very first step).  Returns the final carry and the LAST step's
    metrics (what the loops log).  The carry is not donated: the loops keep live
    references to params/opt-states between calls (checkpointing, acting).  A carry
    handed in as a ``Packed`` comes back as one (:func:`_open`).
    """
    freq = max(int(target_update_freq), 1)

    def block(carry, step_batches, base_key, start_count):
        carry, close = _open(carry)
        # Stack the per-step batches INSIDE the jit: an eager jnp.stack per leaf
        # would cost one dispatch each — the exact per-call overhead this block
        # exists to remove.
        if len(step_batches) == 1:
            stacked = jax.tree.map(lambda x: x[None], step_batches[0])
        else:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *step_batches)
        G = len(step_batches)
        # Per-step keys derived in-jit from a long-lived base key + the running
        # step count: deterministic, and no host-side key-split dispatches.
        keys = jax.random.split(jax.random.fold_in(base_key, start_count), G)
        counts = jnp.asarray(start_count, jnp.int32) + count_offset + jnp.arange(G, dtype=jnp.int32)

        def step(carry, x):
            batch, key, count = x
            carry, metrics = step_fn(carry, batch, key, (count % freq) == 0)
            return carry, metrics

        carry, metrics = jax.lax.scan(step, carry, (stacked, keys, counts))
        with scopes_tag():  # after the scan, whose trace declares the step's scopes
            last = jax.tree.map(lambda m: m[-1], metrics)
        return close(carry), last

    return jax.jit(block, static_argnames=())


class WindowedFutures:
    """Deferred metrics + window-based throughput bookkeeping.

    Training loops ``track()`` each dispatched block's metrics (device futures — no
    sync), ``drain()`` them into the aggregator at the log cadence (the window's only
    blocking device_get), and read ``pop_window_sps()`` for an honest end-to-end
    grad-steps/s over the window's wall-clock.
    """

    def __init__(self, max_pending: int = 256, max_spill: int = 8192):
        self._pending: List[Any] = []
        self._spill: List[Any] = []  # host-side metrics fetched early (backlog cap)
        self._max_pending = max_pending
        self._max_spill = max_spill
        self._warned_trim = False
        self._window_grad_steps = 0
        self._window_t0 = 0.0

    def track(self, metrics: Any, n_steps: int) -> None:
        import time

        if self._window_grad_steps == 0:
            self._window_t0 = time.perf_counter()
        self._pending.append(metrics)
        self._window_grad_steps += n_steps
        if len(self._pending) >= self._max_pending:
            # Bound the device-future backlog between flushes; the values are kept
            # host-side so the next drain still aggregates them.  Only if no drain
            # ever comes (e.g. logging disabled) does the spill itself get trimmed —
            # bounded memory beats an unobservable full history — and trimming warns
            # once, since with logging enabled it means log_every spans more blocks
            # than the window can hold.
            self._spill.extend(jax.device_get(self._pending))
            self._pending.clear()
            if len(self._spill) > self._max_spill:
                if not self._warned_trim:
                    self._warned_trim = True
                    import logging

                    logging.getLogger(__name__).warning(
                        "metrics window exceeded %d gradient blocks without a drain; "
                        "oldest entries dropped (lower metric.log_every to keep full "
                        "window statistics).",
                        self._max_spill,
                    )
                del self._spill[: len(self._spill) - self._max_spill]

    def drain(self, aggregator) -> None:
        if not self._pending and not self._spill:
            return
        fetched = self._spill + (jax.device_get(self._pending) if self._pending else [])
        self._pending.clear()
        self._spill.clear()
        if aggregator is not None:
            for chunk in fetched:
                for k, v in chunk.items():
                    aggregator.update(k, float(v))

    def pop_window_sps(self):
        import time

        if self._window_grad_steps == 0:
            return None
        sps = self._window_grad_steps / max(time.perf_counter() - self._window_t0, 1e-9)
        self._window_grad_steps = 0
        return sps


class BlockDispatcher:
    """Per-loop driver around :func:`make_train_block`: dispatches an iteration's
    gradient steps as chunked scan calls, keeps the metrics ON DEVICE as futures, and
    reports a window-based end-to-end grad-steps/s.

    Usage per iteration (BEFORE stepping the envs, so the device trains while the
    host walks the environments)::

        carry = dispatcher.dispatch(carry, sample_entries, key, start_count)

    and at the log cadence::

        dispatcher.drain(aggregator)          # the window's only blocking sync
        sps = dispatcher.pop_window_sps()     # grad-steps/s over the window, or None
    """

    def __init__(
        self,
        step_fn: Callable,
        target_update_freq: int = 1,
        max_chunk: int = 8,
        count_offset: int = 1,
        base_key=None,
    ):
        self._block = make_train_block(step_fn, target_update_freq, count_offset)
        self._max_chunk = max_chunk
        self._futures = WindowedFutures()
        # Long-lived device-resident base key: per-chunk keys derive from it
        # in-jit (fold_in with the running step count), so dispatch() performs
        # zero host-side PRNG ops.  Must be process-identical in multi-host runs
        # (pass ctx.rng()).
        self._base_key = base_key

    def dispatch(self, carry, entries: Sequence[Any], start_count: int):
        """Run ``len(entries)`` gradient steps (chunked powers of two); returns the
        new carry (device futures — nothing blocks here)."""
        offset = 0
        for size in chunk_sizes(len(entries), self._max_chunk):
            chunk = tuple(entries[offset : offset + size])
            offset += size
            with timer("Time/dispatch_call"):
                carry, metrics = self._block(carry, chunk, self._base_key, start_count)
            start_count += size
            self._futures.track(metrics, size)
        return carry

    def drain(self, aggregator) -> None:
        """Fetch every pending metrics future (one blocking device_get) and feed the
        aggregator; the sync point that makes the window wall-clock honest."""
        self._futures.drain(aggregator)

    def pop_window_sps(self):
        """End-to-end grad-steps/s since the window opened (None if no steps ran);
        resets the window.  Call right after :meth:`drain`."""
        return self._futures.pop_window_sps()


class FusedRingDispatcher:
    """Dispatcher for the SAC family's fused scanned update blocks over the
    device-resident transition ring (``data/device_buffer.py``).

    Where :class:`IndexedBlockDispatcher` still ships host-sampled ``[G, B]``
    index arrays, here even the index sampling happens INSIDE the jit from the
    carried PRNG key: the host passes only the ring handle, the filled-row count
    and the cumulative step counters, so a whole K-step UTD block (DroQ: 20 critic
    updates + the actor update) is ONE dispatch with zero per-step host work.

    ``block_builder(k, last)`` returns the python block function for a ``k``-step
    chunk; ``last`` marks the chunk that closes the iteration's block (DroQ runs
    its once-per-iteration actor update only there — builders without per-block
    tails ignore it, and ``last_sensitive=False`` caches on ``k`` alone).  Blocks
    are jitted with ``donate_argnums=(0,)``: the carry (params + optimizer state)
    is donated and updated in place — callers MUST rebind the carry from the
    return value and never reuse a pre-dispatch reference (jaxlint JL005).

    Program-count bound: each distinct ``k`` compiles once and is dispatched
    exactly K→1; once ``max_programs`` distinct sizes exist, new irregular sizes
    decompose into cached powers of two (:func:`chunk_sizes`) instead of
    compiling more programs.  The steady-state Ratio/UTD count is constant, so
    real runs stay at one program (plus the pretrain burst's chunks).
    """

    def __init__(
        self,
        block_builder: Callable,
        base_key=None,
        max_programs: int = 8,
        max_chunk: int = 8,
        last_sensitive: bool = False,
        futures: "WindowedFutures" = None,
        cfg=None,
        perf_name: str = None,
    ):
        self._builder = block_builder
        self._blocks: dict = {}
        # Perf cost-model registration (obs/perf.py): each distinct chunk size is
        # its own compiled program, so each registers its own FLOPs model.
        self._cfg = cfg
        self._perf_name = perf_name
        self._base_key = base_key
        self._max_programs = max_programs
        self._max_chunk = max_chunk
        self._last_sensitive = last_sensitive
        # Loops that mix host/device paths pass their own WindowedFutures so one
        # drain covers whichever path dispatched.
        self._futures = futures if futures is not None else WindowedFutures()
        # dispatches() counts jit calls — the parity tests assert K→1 per block.
        self.dispatch_count = 0

    def _plan(self, n: int) -> List[int]:
        if n <= 0:
            return []
        if any(k == n for (k, _) in self._blocks) or len(self._blocks) < self._max_programs:
            return [n]
        return chunk_sizes(n, self._max_chunk)

    def _get(self, k: int, last: bool):
        cache_key = (k, last if self._last_sensitive else True)
        block = self._blocks.get(cache_key)
        if block is None:
            block = jax.jit(self._builder(k, cache_key[1]), donate_argnums=(0,))
            if self._perf_name:
                from sheeprl_tpu.obs import perf as obs_perf

                block = obs_perf.instrument(self._cfg, f"{self._perf_name}_k{k}", block)
            self._blocks[cache_key] = block
        return block

    def dispatch(self, carry, ring_arrays: dict, filled: int, rows_added: int, n: int, start_count: int):
        """Run ``n`` gradient steps as one fused block (or cached-size chunks);
        returns the new carry.  Nothing blocks here — metrics stay device futures."""
        sizes = self._plan(n)
        for i, size in enumerate(sizes):
            block = self._get(size, i == len(sizes) - 1)
            carry, metrics = block(carry, ring_arrays, filled, rows_added, self._base_key, start_count)
            self.dispatch_count += 1
            start_count += size
            self._futures.track(metrics, size)
        return carry

    def drain(self, aggregator) -> None:
        self._futures.drain(aggregator)

    def pop_window_sps(self):
        return self._futures.pop_window_sps()


class IndexedBlockDispatcher:
    """BlockDispatcher variant for the device-resident replay mirror
    (``data/device_buffer.py``): the host ships only ``[G, B]`` (env, start) index
    arrays; each scan step GATHERS its ``[T, B]`` batch from the mirror inside the
    jit before running the train step.  Zero bulk host→device traffic per block."""

    def __init__(
        self,
        step_fn: Callable,
        gather_fn: Callable,
        target_update_freq: int = 1,
        max_chunk: int = 8,
        count_offset: int = 1,
        base_key=None,
        globalize: Callable = None,
    ):
        freq = max(int(target_update_freq), 1)

        def block(carry, mirror, envs, starts, base_key, start_count):
            carry, close = _open(carry)
            G = envs.shape[0]
            keys = jax.random.split(jax.random.fold_in(base_key, start_count), G)
            counts = jnp.asarray(start_count, jnp.int32) + count_offset + jnp.arange(G, dtype=jnp.int32)

            def step(carry, x):
                e, s, key, count = x
                with scope("replay_gather"):
                    batch = gather_fn(mirror, e, s)
                carry, metrics = step_fn(carry, batch, key, (count % freq) == 0)
                return carry, metrics

            carry, metrics = jax.lax.scan(step, carry, (envs, starts, keys, counts))
            with scopes_tag():  # after the scan, whose trace declares the step's scopes
                last = jax.tree.map(lambda m: m[-1], metrics)
            return close(carry), last

        self._block = jax.jit(block)
        self._max_chunk = max_chunk
        self._futures = WindowedFutures()
        self._base_key = base_key
        # Multi-process hook (MultiProcessDeviceReplayMirror.globalize_indices):
        # turns each chunk's per-process [size, B_local] numpy index block into
        # batch-sharded global arrays.  None = single-process, numpy goes in as-is.
        self._globalize = globalize

    def dispatch(self, carry, mirror: dict, envs, starts, start_count: int):
        """``envs``/``starts``: ``[G, B]`` numpy int arrays (per-process local under
        multi-process).  Returns the new carry (device futures — nothing blocks
        here)."""
        import numpy as np

        G = envs.shape[0]
        offset = 0
        for size in chunk_sizes(G, self._max_chunk):
            e = np.ascontiguousarray(envs[offset : offset + size], dtype=np.int32)
            s = np.ascontiguousarray(starts[offset : offset + size], dtype=np.int32)
            offset += size
            if self._globalize is not None:
                e, s = self._globalize(e, s)
            with timer("Time/dispatch_call"):
                carry, metrics = self._block(carry, mirror, e, s, self._base_key, start_count)
            start_count += size
            self._futures.track(metrics, size)
        return carry

    def drain(self, aggregator) -> None:
        self._futures.drain(aggregator)

    def pop_window_sps(self):
        return self._futures.pop_window_sps()


