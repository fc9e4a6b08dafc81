"""Sebulba roles: the actor and learner process bodies for the decoupled algorithms.

The thread-decoupled entry points (``sac_decoupled``, ``ppo_decoupled``) already
split acting from learning; this module re-places those two roles into separate
OS processes connected by the transport channel (Podracer's Sebulba topology,
arXiv 2104.06272 §3):

* **actor** (``distributed.role=actor``, one process per ``actor_id``): owns its
  env shard (seeded disjointly via ``rank=actor_id``, exactly the multi-host
  seeding contract of ``make_vector_env``) and its replay SHARD — ``buffer.size /
  (num_envs * num_actors)`` rows, so no process ever materializes the global
  buffer.  It acts with the freshest published params, samples its gradient
  blocks locally, and streams them to the learner.
* **learner** (``distributed.role=learner``): accepts actor channels, consumes
  transition blocks from one bounded inbox (TCP backpressure throttles actors
  when it fills), runs the same jitted mesh update as the thread path, and
  broadcasts stamped params back through the weight publisher.

Parity contract with the thread path (pinned by
``tests/test_distributed/test_sebulba_smoke.py``): with ``num_actors=1`` and the
same seed, the PPO lockstep schedule feeds the learner bit-identical batches and
produces a bit-identical final checkpoint — every per-iteration count below uses
``num_actors`` exactly where the thread path uses ``jax.process_count()``.

Liveness contract (pinned by ``tests/test_distributed/test_actor_kill.py``): a
SIGKILLed actor closes its channel; the learner keeps consuming the surviving
channels (no barrier anywhere on the block path) while the launcher respawns the
actor with a bumped generation; the respawn reconnects, receives the latest
params as a welcome publish, and refills its replay shard from scratch.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from sheeprl_tpu.distributed.placement import SUMMARY_ENV_VAR, PlacementSpec
from sheeprl_tpu.distributed.publish import (
    PARAMS_KIND,
    ChannelWeightPublisher,
    staleness_steps,
)
from sheeprl_tpu.distributed.transport import (
    Channel,
    ChannelClosed,
    FramingError,
    Listener,
    connect,
    maybe_digest,
)
from sheeprl_tpu.obs import perf as obs_perf
from sheeprl_tpu.obs import flight_recorder as _flight_recorder
from sheeprl_tpu.obs import tracer as _tracer
from sheeprl_tpu.obs.fleet import maybe_exporter
from sheeprl_tpu.parallel.mesh import device_identity
from sheeprl_tpu.rollout.sharding import shard_pool_cfg

HELLO_KIND = "hello"
BLOCK_KIND = "block"
DONE_KIND = "done"
ABANDON_KIND = "abandon"

#: Sebulba observability keys (howto/observability.md): inbox depth in blocks,
#: actor-side policy-step age of the params each block was acted with, and the
#: transport byte counters (per-channel keys get a ``/ch<actor_id>`` suffix).
SEBULBA_METRIC_KEYS = frozenset(
    {"Sebulba/queue_depth", "Sebulba/param_staleness_steps", "Sebulba/xfer_bytes"}
)


# ----------------------------------------------------------------------- inbox
class LearnerInbox:
    """Accept loop + one reader thread per actor channel, all feeding ONE bounded
    queue — the process analogue of the thread path's ``batch_q``.

    The queue depth (``distributed.queue_depth``) is the whole flow control: when
    the learner falls behind, readers block on ``put``, the kernel socket buffers
    fill, and every actor's ``send`` stalls — backpressure without any protocol.
    A dead actor never wedges the learner: its reader dies with ``ChannelClosed``
    and enqueues a ``closed`` control item instead of a block.
    """

    def __init__(self, listener: Listener, spec: PlacementSpec, on_connect=None):
        self._listener = listener
        self._spec = spec
        self._q: "queue.Queue[Tuple[str, int, Dict[str, Any], Any]]" = queue.Queue(
            maxsize=spec.queue_depth
        )
        self._lock = threading.Lock()
        self._channels: Dict[int, Channel] = {}
        self._bytes_drained = 0
        self._stop = threading.Event()
        #: [monotonic_t, actor_id, generation, event] — the learner summary's
        #: lifecycle trace (the actor-kill test reads the kill window off it).
        self.events: List[List[Any]] = []
        self.on_connect = on_connect
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="sebulba-accept", daemon=True
        )
        self._accept_thread.start()

    def channels(self) -> List[Channel]:
        with self._lock:
            return list(self._channels.values())

    def qsize(self) -> int:
        return self._q.qsize()

    def bytes_received(self) -> int:
        # Closed channels fold their totals into _bytes_drained (exactly once,
        # in their reader's finally) so the counter survives actor churn.
        with self._lock:
            return self._bytes_drained + sum(ch.bytes_received for ch in self._channels.values())

    def record(self, actor_id: int, generation: int, event: str) -> None:
        with self._lock:
            self.events.append([time.monotonic(), int(actor_id), int(generation), event])

    def get(self, timeout: Optional[float] = None) -> Tuple[str, int, Dict[str, Any], Any]:
        return self._q.get(timeout=timeout)

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                ch = self._listener.accept(timeout=0.5)
            except TimeoutError:
                continue
            except OSError:
                return
            threading.Thread(target=self._reader, args=(ch,), daemon=True).start()

    def _reader(self, ch: Channel) -> None:
        actor_id: Optional[int] = None
        generation = 0
        done = False
        try:
            kind, meta, _ = ch.recv(timeout=self._spec.connect_timeout_s)
            if kind == ABANDON_KIND:
                # The launcher gave up respawning this slot; tell the learner so
                # it does not wait forever for a ``done`` that will never come.
                self._q.put((ABANDON_KIND, int(meta["actor_id"]), dict(meta), None))
                return
            if kind != HELLO_KIND:
                return
            actor_id = int(meta["actor_id"])
            generation = int(meta.get("generation", 0))
            with self._lock:
                stale = self._channels.get(actor_id)
                self._channels[actor_id] = ch
            if stale is not None:
                stale.close()
            self.record(actor_id, generation, "connected")
            if self.on_connect is not None:
                self.on_connect(ch)
            while not done:
                before = ch.bytes_received
                kind, meta, payload = ch.recv()
                meta = dict(meta)
                meta["_wire_bytes"] = ch.bytes_received - before
                meta["_generation"] = generation
                done = kind == DONE_KIND
                self._q.put((kind, actor_id, meta, payload))
            # Retire the channel at ``done``: the publisher must stop sending to
            # a finished actor (a publish RSTing its draining socket is harmless,
            # but pointless) and closing here gives its drain loop prompt EOF.
        except (ChannelClosed, FramingError, TimeoutError):
            pass
        finally:
            was_current = False
            if actor_id is not None:
                with self._lock:
                    if self._channels.get(actor_id) is ch:
                        del self._channels[actor_id]
                        was_current = True
            with self._lock:
                self._bytes_drained += ch.bytes_received
            ch.close()
            if was_current and not done and not self._stop.is_set():
                self.record(actor_id, generation, "closed")
                self._q.put(("closed", actor_id, {"generation": generation}, None))

    def close(self) -> None:
        self._stop.set()
        self._listener.close()
        for ch in self.channels():
            ch.close()


# ------------------------------------------------------------------- utilities
class _StatsCollector:
    """Duck-typed aggregator for ``record_episode_stats``: captures the
    (name, value) updates so an actor can ship episode stats in block meta
    instead of owning a metrics pipeline."""

    def __init__(self) -> None:
        self.pairs: List[List[Any]] = []

    def update(self, name: str, value: Any) -> None:
        self.pairs.append([name, float(value)])

    def drain(self) -> List[List[Any]]:
        pairs, self.pairs = self.pairs, []
        return pairs


def _stamp_of(meta: Dict[str, Any]) -> Dict[str, Any]:
    """Consumer-side stamp: the pinned ``{seq, grad_step, policy_step}`` plus the
    publisher's ``t_pub`` lineage timestamp riding separately in transport meta."""
    stamp = dict(meta.get("stamp") or {})
    if meta.get("t_pub") is not None:
        stamp["t_pub"] = float(meta["t_pub"])
    return stamp


def _freshest(
    latest: Optional[Tuple[Any, Dict[str, Any]]], candidate: Tuple[Any, Dict[str, Any]]
) -> Tuple[Any, Dict[str, Any]]:
    """Max-seq wins, not last-arrived: publisher sends are lock-free, so a
    welcome publish can overtake a newer broadcast on the wire — applying it
    would regress params."""
    if latest is None or int(candidate[1].get("seq", 0)) >= int(latest[1].get("seq", 0)):
        return candidate
    return latest


def _pickup_params(ch: Channel, latest: Optional[Tuple[Any, Dict[str, Any]]]):
    """Drain every pending publish, keep only the freshest (actors may skip
    publishes, never act on older-than-latest params)."""
    while ch.poll(0):
        kind, meta, payload = ch.recv()
        if kind == PARAMS_KIND:
            latest = _freshest(latest, (payload, _stamp_of(meta)))
    return latest


def _await_params(ch: Channel, last_seq: int, timeout_s: float):
    """PPO lockstep: block until a publish NEWER than ``last_seq`` arrives, then
    drain to the freshest (one publish per consumed block keeps this 1:1 with
    the thread path's blocking ``param_q.get``)."""
    deadline = time.monotonic() + timeout_s
    latest: Optional[Tuple[Any, Dict[str, Any]]] = None
    while latest is None or int(latest[1].get("seq", 0)) <= last_seq:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError(f"no param publish newer than seq={last_seq} within {timeout_s}s")
        kind, meta, payload = ch.recv(timeout=remaining)
        if kind == PARAMS_KIND:
            latest = _freshest(latest, (payload, _stamp_of(meta)))
    return _pickup_params(ch, latest)


#: Set once any summary (success or error) reached disk in this process, so the
#: setup-crash fallback in :func:`run` never clobbers the loop's richer one.
_summary_written = False


def _write_summary(summary: Dict[str, Any]) -> None:
    global _summary_written
    path = os.environ.get(SUMMARY_ENV_VAR)
    if not path:
        return
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(summary, f)
    os.replace(tmp, path)
    _summary_written = True


def _exc_summary(exc: BaseException) -> Dict[str, Any]:
    return {"type": type(exc).__name__, "message": str(exc)[:2000]}


def _actor_observability(cfg, spec: PlacementSpec, log_dir: str, algo: str):
    """Arm the actor-side observability stack (actors historically ran dark —
    only the learner had a TrainingMonitor): a flight recorder whose ring the
    fleet blackbox collects from survivors, a span tracer when ``obs.enabled``
    turns tracing on (the exporter ships its events at close, so this process
    gets a track in the merged Perfetto timeline), and the fleet exporter
    itself.  Returns ``(exporter, tracer)``; both may be ``None``."""
    obs_cfg = dict(cfg.get("obs") or {})
    if bool(obs_cfg.get("flight_recorder", True)) and _flight_recorder.get_active() is None:
        _flight_recorder.install(
            _flight_recorder.FlightRecorder(
                log_dir=log_dir,
                capacity=int(obs_cfg.get("flight_recorder_capacity", 4096)),
                keep_events=int(obs_cfg.get("flight_recorder_keep_events", 512)),
                algo=f"{algo}_sebulba_actor",
                cfg=cfg,
            )
        )
    tracer = None
    if bool(obs_cfg.get("enabled", False)) and bool(obs_cfg.get("trace", True)):
        tracer = _tracer.SpanTracer(rank=0, max_events=int(obs_cfg.get("max_events", 100_000)))
        _tracer.set_active(tracer)
    exporter = maybe_exporter(
        cfg, "actor", actor_id=spec.actor_id, generation=spec.generation, log_dir=log_dir
    )
    return exporter, tracer


def _actor_obs_teardown(exporter, tracer) -> None:
    """Ship the trace (exporter close does it while the tracer is still active),
    then restore tracer state.  Never raises — actor teardown already has
    channel/env cleanup to finish."""
    try:
        if exporter is not None:
            exporter.close()
    except Exception:
        pass
    if tracer is not None and _tracer.get_active() is tracer:
        _tracer.set_active(None)


def _note_param_apply(exporter, stamp: Dict[str, Any], policy_step: int) -> None:
    """Staleness lineage: the consumer folds the publisher's transport-meta
    ``t_pub`` into publish→apply latency, making a publish traceable from
    learner emit to actor apply (the flight-recorder event joins the two rings
    in a fleet blackbox bundle)."""
    apply_ms = None
    if stamp.get("t_pub") is not None:
        apply_ms = max((time.time() - float(stamp["t_pub"])) * 1000.0, 0.0)
    _flight_recorder.record_event(
        "param_apply", seq=stamp.get("seq"), grad_step=stamp.get("grad_step"), apply_ms=apply_ms
    )
    if exporter is None:
        return
    exporter.gauge("Sebulba/publish_seq_applied", stamp.get("seq"))
    exporter.gauge("Sebulba/publish_apply_ms", apply_ms)
    staleness = staleness_steps(stamp, policy_step)
    if staleness is not None:
        exporter.gauge("Sebulba/param_staleness_steps", staleness)


class _SlotAccounting:
    """Monotonic global env-step counter across actor generations: each slot
    reports its own cumulative steps; a closed slot's latest count folds into a
    base offset so the respawn (restarting at zero) never moves the total
    backwards."""

    def __init__(self) -> None:
        self._latest: Dict[int, int] = {}
        self._offset = 0

    def report(self, actor_id: int, env_steps: int) -> None:
        self._latest[actor_id] = max(self._latest.get(actor_id, 0), int(env_steps))

    def fold(self, actor_id: int) -> None:
        self._offset += self._latest.pop(actor_id, 0)

    @property
    def total(self) -> int:
        return self._offset + sum(self._latest.values())


# ------------------------------------------------------------------ SAC: actor
def _run_sac_actor(ctx, cfg, spec: PlacementSpec) -> None:
    import jax

    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.utils import prepare_obs
    from sheeprl_tpu.data.buffers import ReplayBuffer
    from sheeprl_tpu.fault import chaos
    from sheeprl_tpu.utils.env import make_vector_env
    from sheeprl_tpu.utils.logger import get_log_dir
    from sheeprl_tpu.utils.metric import record_episode_stats
    from sheeprl_tpu.utils.utils import Ratio

    actor_id = spec.actor_id
    log_dir = get_log_dir(cfg)
    fleet_exporter, actor_tracer = _actor_observability(cfg, spec, log_dir, "sac")
    shard_pool_cfg(cfg, spec.num_actors, actor_id)
    envs = make_vector_env(cfg, cfg.seed, actor_id, log_dir if cfg.env.capture_video else None)
    obs_space = envs.single_observation_space
    act_space = envs.single_action_space
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    act_low, act_high = act_space.low, act_space.high
    rescale = np.isfinite(act_low).all() and np.isfinite(act_high).all()

    # Same seed -> same ctx.rng() chain -> bit-identical initial params as the
    # learner built; the first publish only has to arrive before they diverge.
    actor_net, _, params = build_agent(ctx, act_space, obs_space, cfg)
    local_actor_params = params["actor"]

    num_envs = cfg.env.num_envs
    num_actors = spec.num_actors
    rb = ReplayBuffer(
        max(int(cfg.buffer.size) // max(num_envs * num_actors, 1), 1),
        num_envs,
        obs_keys=mlp_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{actor_id}")
        if cfg.buffer.memmap
        else None,
    )
    rb.seed(cfg.seed + actor_id)
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    batch_size = cfg.algo.per_rank_batch_size
    stats = _StatsCollector()

    @jax.jit
    def act_fn(p, obs, key):
        mean, log_std = actor_net.apply(p, obs)
        dist = actor_net.dist(mean, log_std)
        return dist.sample(key)

    # num_actors plays exactly the role jax.process_count() plays in the thread
    # path: per-iter global step increment, learning-starts conversion, and the
    # replay-ratio normalization all divide by the acting world size.
    policy_steps_per_iter = num_envs * num_actors
    total_steps = int(cfg.algo.total_steps)
    num_iters = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_iters = max(learning_starts - 1, 0)

    ch = connect(spec.host, spec.port, spec.connect_timeout_s)
    try:
        ch.send(HELLO_KIND, None, actor_id=actor_id, generation=spec.generation, algo="sac")
        key = jax.random.PRNGKey(cfg.seed + 10_000 + actor_id)
        latest: Optional[Tuple[Any, Dict[str, Any]]] = None
        stamp: Dict[str, Any] = {}
        policy_step = 0
        obs, _ = envs.reset(seed=cfg.seed + actor_id)
        step_data: Dict[str, np.ndarray] = {}
        for iter_num in range(1, num_iters + 1):
            chaos.maybe_actor_fault(actor_id, spec.generation, iter_num)
            picked = _pickup_params(ch, latest)
            if picked is not latest and picked is not None:
                latest = picked
                local_actor_params, stamp = jax.device_put(picked[0]["actor"]), picked[1]
                _note_param_apply(fleet_exporter, stamp, policy_step)
            env_t0 = time.perf_counter()
            if iter_num <= learning_starts:
                actions = np.stack([act_space.sample() for _ in range(num_envs)])
                tanh_actions = (
                    2 * (actions - act_low) / (act_high - act_low) - 1 if rescale else actions
                )
            else:
                key, sub = jax.random.split(key)
                obs_t = prepare_obs(obs, mlp_keys)
                tanh_actions = np.asarray(jax.device_get(act_fn(local_actor_params, obs_t, sub)))
                actions = (
                    act_low + (tanh_actions + 1) * 0.5 * (act_high - act_low)
                    if rescale
                    else tanh_actions
                )
            with _tracer.span("Time/env_interaction"):
                next_obs, reward, terminated, truncated, info = envs.step(actions)
            done = np.logical_or(terminated, truncated)

            real_next = {k: np.asarray(next_obs[k]).copy() for k in mlp_keys}
            if done.any() and "final_obs" in info:
                for i in np.nonzero(done)[0]:
                    if info["final_obs"][i] is not None:
                        for k in mlp_keys:
                            real_next[k][i] = np.asarray(info["final_obs"][i][k])

            for k in mlp_keys:
                step_data[k] = np.asarray(obs[k])[None]
                step_data[f"next_{k}"] = real_next[k][None]
            step_data["actions"] = tanh_actions.astype(np.float32)[None]
            step_data["rewards"] = np.asarray(reward, dtype=np.float32).reshape(num_envs, 1)[None]
            step_data["dones"] = terminated.astype(np.float32).reshape(num_envs, 1)[None]
            rb.add(step_data, validate_args=cfg.buffer.validate_args)
            obs = next_obs
            policy_step += policy_steps_per_iter
            record_episode_stats(stats, info)
            env_time = time.perf_counter() - env_t0

            grad_steps = 0
            batches = None
            if iter_num >= learning_starts:
                grad_steps = ratio(
                    (policy_step - prefill_iters * policy_steps_per_iter) / num_actors
                )
                if grad_steps > 0:
                    sample = rb.sample(batch_size * grad_steps)
                    batches = {
                        "obs": np.concatenate(
                            [sample[k].reshape(grad_steps, batch_size, -1) for k in mlp_keys], -1
                        ),
                        "next_obs": np.concatenate(
                            [sample[f"next_{k}"].reshape(grad_steps, batch_size, -1) for k in mlp_keys],
                            -1,
                        ),
                        "actions": sample["actions"].reshape(grad_steps, batch_size, -1),
                        "rewards": sample["rewards"].reshape(grad_steps, batch_size, 1),
                        "dones": sample["dones"].reshape(grad_steps, batch_size, 1),
                    }
            with _tracer.span("Time/block_send"):
                ch.send(
                    BLOCK_KIND,
                    {"batches": batches},
                    iter_num=iter_num,
                    grad_steps=grad_steps,
                    policy_step=policy_step,
                    env_time=env_time,
                    env_steps=iter_num * num_envs,
                    staleness=staleness_steps(stamp, policy_step),
                    stats=stats.drain(),
                )
            if fleet_exporter is not None:
                fleet_exporter.counter("env_steps", iter_num * num_envs)
                fleet_exporter.counter("blocks", iter_num)
                fleet_exporter.counter("bytes_sent", ch.bytes_sent)
                fleet_exporter.gauge("policy_step", policy_step)
        ch.send(DONE_KIND, None, env_steps=num_iters * num_envs)
        ch.drain_until_closed(spec.connect_timeout_s)
    finally:
        _actor_obs_teardown(fleet_exporter, actor_tracer)
        ch.close()
        envs.close()


# ---------------------------------------------------------------- SAC: learner
def _run_sac_learner(ctx, cfg, spec: PlacementSpec) -> None:
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.sac import make_sac_train_fn
    from sheeprl_tpu.algos.sac.utils import AGGREGATOR_KEYS
    from sheeprl_tpu.analysis.strict import assert_finite, strict_guard
    from sheeprl_tpu.checkpoint.manager import CheckpointManager
    from sheeprl_tpu.config.core import save_config
    from sheeprl_tpu.fault.guard import TrainingGuard
    from sheeprl_tpu.obs import TrainingMonitor
    from sheeprl_tpu.utils.logger import get_log_dir, get_logger
    from sheeprl_tpu.utils.metric import MetricAggregator

    log_dir = get_log_dir(cfg)
    save_config(cfg, Path(log_dir) / "config.yaml")
    logger = get_logger(cfg, log_dir)
    monitor = TrainingMonitor(cfg, log_dir)
    fleet_exporter = maybe_exporter(cfg, "learner", generation=spec.generation, log_dir=log_dir)

    obs_space, act_space = _probe_spaces(cfg)
    actor_net, critic, params = build_agent(ctx, act_space, obs_space, cfg)
    actor_opt, critic_opt, alpha_opt, train_fn = make_sac_train_fn(actor_net, critic, cfg, act_space)
    train_fn = obs_perf.instrument(cfg, "sac_sebulba/train_fn", strict_guard(cfg, "sac_sebulba/train_fn", train_fn))
    opt_state = ctx.replicate(
        {
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
            "alpha": alpha_opt.init(params["log_alpha"]),
        }
    )
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from,
            templates={"params": jax.device_get(params), "opt_state": jax.device_get(opt_state)},
        )
        params = ctx.replicate(state["params"])
        opt_state = ctx.replicate(state["opt_state"])

    aggregator = MetricAggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(AGGREGATOR_KEYS | SEBULBA_METRIC_KEYS | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    guard = TrainingGuard(cfg, log_dir)

    def train_block(meta, payload, cumulative_grad_steps):
        grad_steps = int(meta["grad_steps"])
        if grad_steps <= 0 or payload.get("batches") is None:
            return 0, 0.0
        maybe_digest(f"sac:{int(meta['iter_num'])}", payload["batches"])
        batches = ctx.put_batch(payload["batches"], batch_axis=1)
        key = ctx.rng()
        t0 = time.perf_counter()
        nonlocal_params[0], nonlocal_opt[0], train_metrics = train_fn(
            nonlocal_params[0], nonlocal_opt[0], batches, key, jnp.asarray(cumulative_grad_steps)
        )
        train_metrics = jax.device_get(train_metrics)
        assert_finite(cfg, train_metrics, "sac_sebulba/update")
        for k, v in train_metrics.items():
            aggregator.update(k, float(v))
        return grad_steps, time.perf_counter() - t0

    nonlocal_params = [params]
    nonlocal_opt = [opt_state]

    def publish(publisher, cumulative_grad_steps, policy_step):
        # SAC actors only act — publish the actor net alone (a fraction of the
        # full params+critic+targets tree on the wire).
        publisher.publish(
            {"actor": nonlocal_params[0]["actor"]},
            grad_step=cumulative_grad_steps,
            policy_step=policy_step,
        )

    def save_state(policy_step, cumulative_grad_steps, blocks):
        return {
            "params": nonlocal_params[0],
            "opt_state": nonlocal_opt[0],
            "iter_num": blocks,
            "policy_step": policy_step,
            "cumulative_grad_steps": cumulative_grad_steps,
        }

    _learner_loop(
        cfg,
        spec,
        logger=logger,
        monitor=monitor,
        aggregator=aggregator,
        ckpt_manager=ckpt_manager,
        guard=guard,
        train_block=train_block,
        publish=publish,
        save_state=save_state,
        sps_env_steps=cfg.env.num_envs,
        fleet_exporter=fleet_exporter,
    )


# ------------------------------------------------------------------ PPO: actor
def _run_ppo_actor(ctx, cfg, spec: PlacementSpec) -> None:
    import jax

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns
    from sheeprl_tpu.algos.ppo.utils import prepare_obs
    from sheeprl_tpu.data.buffers import ReplayBuffer
    from sheeprl_tpu.fault import chaos
    from sheeprl_tpu.utils.env import make_vector_env
    from sheeprl_tpu.utils.logger import get_log_dir
    from sheeprl_tpu.utils.metric import record_episode_stats

    actor_id = spec.actor_id
    log_dir = get_log_dir(cfg)
    fleet_exporter, actor_tracer = _actor_observability(cfg, spec, log_dir, "ppo")
    shard_pool_cfg(cfg, spec.num_actors, actor_id)
    envs = make_vector_env(cfg, cfg.seed, actor_id, log_dir if cfg.env.capture_video else None)
    obs_space = envs.single_observation_space
    act_space = envs.single_action_space
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    agent, params = build_agent(ctx, act_space, obs_space, cfg)
    is_continuous = agent.is_continuous

    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    num_actors = spec.num_actors
    policy_steps_per_iter = int(num_envs * rollout_steps * num_actors)
    total_steps = int(cfg.algo.total_steps)
    num_updates = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1

    # The actor only needs the jitted policy/value calls + GAE from the bundle.
    fns = PPOTrainFns(ctx, agent, cfg, obs_keys, num_updates)
    act_fn, values_fn, gae_fn, batch_n = fns.act_fn, fns.values_fn, fns.gae_fn, fns.batch_n
    gamma = cfg.algo.gamma
    stats = _StatsCollector()

    rb = ReplayBuffer(
        rollout_steps,
        num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{actor_id}")
        if cfg.buffer.memmap
        else None,
    )
    rb.seed(cfg.seed + actor_id)

    ch = connect(spec.host, spec.port, spec.connect_timeout_s)
    try:
        ch.send(HELLO_KIND, None, actor_id=actor_id, generation=spec.generation, algo="ppo")
        key = jax.random.PRNGKey(cfg.seed + 10_000 + actor_id)
        local_params = params
        stamp: Dict[str, Any] = {}
        last_seq = 0
        policy_step = 0
        obs, _ = envs.reset(seed=cfg.seed + actor_id)
        step_data: Dict[str, np.ndarray] = {}
        for update in range(1, num_updates + 1):
            chaos.maybe_actor_fault(actor_id, spec.generation, update)
            env_t0 = time.perf_counter()
            for _ in range(rollout_steps):
                key, sub = jax.random.split(key)
                obs_t = prepare_obs(obs, cnn_keys, mlp_keys)
                env_act, stored_act, logprob, value = act_fn(local_params, obs_t, sub)
                env_act_np = np.asarray(jax.device_get(env_act))
                if is_continuous:
                    low, high = act_space.low, act_space.high
                    env_actions = (
                        np.clip(env_act_np, low, high) if np.isfinite(low).all() else env_act_np
                    )
                elif len(agent.action_dims) == 1:
                    env_actions = env_act_np[..., 0]
                else:
                    env_actions = env_act_np
                with _tracer.span("Time/env_interaction"):
                    next_obs, reward, terminated, truncated, info = envs.step(env_actions)
                if cfg.env.clip_rewards:
                    reward = np.clip(reward, -1, 1)
                done = np.logical_or(terminated, truncated)
                reward = np.asarray(reward, dtype=np.float32).reshape(num_envs)

                if truncated.any() and "final_obs" in info:
                    trunc_idx = np.nonzero(truncated)[0]
                    final_obs = {
                        k: np.stack([np.asarray(info["final_obs"][i][k]) for i in trunc_idx])
                        for k in obs_keys
                    }
                    v_final = np.asarray(
                        jax.device_get(values_fn(local_params, prepare_obs(final_obs, cnn_keys, mlp_keys)))
                    )
                    reward[trunc_idx] += gamma * v_final

                for k in obs_keys:
                    step_data[k] = np.asarray(obs[k])[None]
                step_data["actions"] = env_act_np.reshape(num_envs, -1).astype(np.float32)[None]
                step_data["logprobs"] = np.asarray(jax.device_get(logprob)).reshape(num_envs, 1)[None]
                step_data["values"] = np.asarray(jax.device_get(value)).reshape(num_envs, 1)[None]
                step_data["rewards"] = reward.reshape(num_envs, 1)[None]
                step_data["dones"] = done.astype(np.float32).reshape(num_envs, 1)[None]
                rb.add(step_data, validate_args=cfg.buffer.validate_args)

                obs = next_obs
                policy_step += num_envs * num_actors
                record_episode_stats(stats, info)
            env_time = time.perf_counter() - env_t0

            local = rb.to_tensor()
            next_value = values_fn(local_params, prepare_obs(obs, cnn_keys, mlp_keys))[:, None]
            returns, advantages = gae_fn(local["rewards"], local["values"], local["dones"], next_value)
            data = {
                **{k: local[k] for k in obs_keys},
                "actions": local["actions"],
                "logprobs": local["logprobs"][..., 0],
                "values": local["values"][..., 0],
                "returns": returns[..., 0],
                "advantages": advantages[..., 0],
            }
            data = jax.tree.map(lambda x: np.asarray(x).reshape(batch_n, *x.shape[2:]), data)
            with _tracer.span("Time/block_send"):
                ch.send(
                    BLOCK_KIND,
                    {"data": data},
                    update=update,
                    policy_step=policy_step,
                    env_time=env_time,
                    env_steps=update * rollout_steps * num_envs,
                    staleness=staleness_steps(stamp, policy_step),
                    stats=stats.drain(),
                )

            # Lockstep publish pickup (the thread player's blocking param_q.get):
            # this is what makes the 1-actor schedule bit-identical.
            with _tracer.span("Time/param_wait"):
                payload, stamp = _await_params(ch, last_seq, spec.connect_timeout_s)
            last_seq = int(stamp.get("seq", last_seq + 1))
            local_params = jax.device_put(payload)
            _note_param_apply(fleet_exporter, stamp, policy_step)
            if fleet_exporter is not None:
                fleet_exporter.counter("env_steps", update * rollout_steps * num_envs)
                fleet_exporter.counter("blocks", update)
                fleet_exporter.counter("bytes_sent", ch.bytes_sent)
                fleet_exporter.gauge("policy_step", policy_step)
        ch.send(DONE_KIND, None, env_steps=num_updates * rollout_steps * num_envs)
        ch.drain_until_closed(spec.connect_timeout_s)
    finally:
        _actor_obs_teardown(fleet_exporter, actor_tracer)
        ch.close()
        envs.close()


# ---------------------------------------------------------------- PPO: learner
def _run_ppo_learner(ctx, cfg, spec: PlacementSpec) -> None:
    import jax

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import PPOTrainFns
    from sheeprl_tpu.algos.ppo.utils import AGGREGATOR_KEYS
    from sheeprl_tpu.analysis.strict import assert_finite, strict_guard
    from sheeprl_tpu.checkpoint.manager import CheckpointManager
    from sheeprl_tpu.config.core import save_config
    from sheeprl_tpu.fault.guard import TrainingGuard
    from sheeprl_tpu.obs import TrainingMonitor
    from sheeprl_tpu.utils.logger import get_log_dir, get_logger
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.utils import polynomial_decay

    log_dir = get_log_dir(cfg)
    save_config(cfg, Path(log_dir) / "config.yaml")
    logger = get_logger(cfg, log_dir)
    monitor = TrainingMonitor(cfg, log_dir)
    fleet_exporter = maybe_exporter(cfg, "learner", generation=spec.generation, log_dir=log_dir)

    obs_space, act_space = _probe_spaces(cfg)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    agent, params = build_agent(ctx, act_space, obs_space, cfg)
    num_envs = cfg.env.num_envs
    rollout_steps = cfg.algo.rollout_steps
    policy_steps_per_iter = int(num_envs * rollout_steps * spec.num_actors)
    total_steps = int(cfg.algo.total_steps)
    num_updates = max(total_steps // policy_steps_per_iter, 1) if not cfg.dry_run else 1

    fns = PPOTrainFns(ctx, agent, cfg, obs_keys, num_updates)
    opt_state = ctx.replicate(fns.opt.init(params))
    train_fn = obs_perf.instrument(cfg, "ppo_sebulba/train_fn", strict_guard(cfg, "ppo_sebulba/train_fn", fns.train_fn))
    if cfg.checkpoint.get("resume_from"):
        state = CheckpointManager.load(
            cfg.checkpoint.resume_from,
            templates={"params": jax.device_get(params), "opt_state": jax.device_get(opt_state)},
        )
        params = ctx.replicate(state["params"])
        opt_state = ctx.replicate(state["opt_state"])

    aggregator = MetricAggregator(cfg.metric.aggregator.get("metrics", {}))
    aggregator.keep(AGGREGATOR_KEYS | SEBULBA_METRIC_KEYS | set(cfg.metric.aggregator.get("metrics", {})))
    ckpt_manager = CheckpointManager(Path(log_dir) / "checkpoints", keep_last=cfg.checkpoint.keep_last)
    guard = TrainingGuard(cfg, log_dir)

    nonlocal_params = [params]
    nonlocal_opt = [opt_state]

    def train_block(meta, payload, cumulative_grad_steps):
        update = int(meta["update"])
        maybe_digest(f"ppo:{update}", payload["data"])
        clip_coef = cfg.algo.clip_coef
        ent_coef = cfg.algo.ent_coef
        if cfg.algo.anneal_clip_coef:
            clip_coef = polynomial_decay(update, initial=clip_coef, final=0.0, max_decay_steps=num_updates)
        if cfg.algo.anneal_ent_coef:
            ent_coef = polynomial_decay(update, initial=ent_coef, final=0.0, max_decay_steps=num_updates)
        key = ctx.rng()
        t0 = time.perf_counter()
        nonlocal_params[0], nonlocal_opt[0], train_metrics = train_fn(
            nonlocal_params[0], nonlocal_opt[0], payload["data"], key, clip_coef, ent_coef
        )
        train_metrics = jax.device_get(train_metrics)
        assert_finite(cfg, train_metrics, "ppo_sebulba/update")
        for k, v in train_metrics.items():
            aggregator.update(k, float(v))
        return fns.grad_steps_per_update, time.perf_counter() - t0

    def publish(publisher, cumulative_grad_steps, policy_step):
        publisher.publish(
            nonlocal_params[0], grad_step=cumulative_grad_steps, policy_step=policy_step
        )

    def save_state(policy_step, cumulative_grad_steps, blocks):
        return {
            "params": nonlocal_params[0],
            "opt_state": nonlocal_opt[0],
            "update": blocks,
            "policy_step": policy_step,
        }

    _learner_loop(
        cfg,
        spec,
        logger=logger,
        monitor=monitor,
        aggregator=aggregator,
        ckpt_manager=ckpt_manager,
        guard=guard,
        train_block=train_block,
        publish=publish,
        save_state=save_state,
        sps_env_steps=num_envs * rollout_steps,
        publish_empty_blocks=True,
        fleet_exporter=fleet_exporter,
    )


# -------------------------------------------------------------- learner kernel
def _learner_loop(
    cfg,
    spec: PlacementSpec,
    *,
    logger,
    monitor,
    aggregator,
    ckpt_manager,
    guard,
    train_block,
    publish,
    save_state,
    sps_env_steps: int,
    publish_empty_blocks: bool = False,
    fleet_exporter=None,
) -> None:
    """Algorithm-agnostic learner body: inbox consumption, publishing, metrics,
    checkpoint cadence, lifecycle accounting, and the exit summary.

    ``train_block(meta, payload, cumulative_grad_steps) -> (grad_steps, train_time)``
    runs the jitted update and mutates the closed-over params/opt state;
    ``publish`` broadcasts them; ``save_state`` materializes the checkpoint tree.
    ``publish_empty_blocks`` keeps the PPO lockstep alive on blocks that carry no
    gradient work (SAC prefill blocks skip the publish like the thread path).
    """
    listener = Listener(spec.host, spec.port)
    publisher = ChannelWeightPublisher(lambda: inbox.channels())
    inbox = LearnerInbox(listener, spec, on_connect=publisher.maybe_welcome)

    t_start = time.monotonic()
    done_slots: set = set()
    slots = _SlotAccounting()
    cumulative_grad_steps = 0
    blocks = 0
    policy_step = 0
    last_log = 0
    last_checkpoint = 0
    last_progress = time.monotonic()
    #: [monotonic_t, cumulative_grad_steps] per consumed block — the liveness
    #: trace the actor-kill test asserts strict increase on across the kill window.
    grad_trace: List[List[float]] = []
    idle_timeout_s = max(float(spec.connect_timeout_s) * 5.0, 60.0)

    def save_ckpt():
        nonlocal last_checkpoint
        path = ckpt_manager.save(policy_step, save_state(policy_step, cumulative_grad_steps, blocks))
        last_checkpoint = policy_step
        return path

    error: Optional[Dict[str, Any]] = None
    try:
        while len(done_slots) < spec.num_actors:
            try:
                kind, actor_id, meta, payload = inbox.get(timeout=1.0)
            except queue.Empty:
                if time.monotonic() - last_progress > idle_timeout_s:
                    raise RuntimeError(
                        f"sebulba learner starved: no actor message for {idle_timeout_s:.0f}s "
                        f"({len(done_slots)}/{spec.num_actors} actors done)"
                    )
                continue
            last_progress = time.monotonic()
            if kind == DONE_KIND:
                done_slots.add(actor_id)
                slots.report(actor_id, int(meta.get("env_steps", 0)))
                inbox.record(actor_id, int(meta.get("_generation", 0)), "done")
                continue
            if kind == "closed":
                if actor_id not in done_slots:
                    slots.fold(actor_id)
                continue
            if kind == ABANDON_KIND:
                # The launcher exhausted this slot's respawn budget; stop
                # waiting for it (its env steps stay folded from the close).
                done_slots.add(actor_id)
                inbox.record(actor_id, -1, "abandoned")
                continue
            if kind != BLOCK_KIND:
                continue

            monitor.advance()
            blocks += 1
            policy_step = max(policy_step, int(meta.get("policy_step", 0)))
            slots.report(actor_id, int(meta.get("env_steps", 0)))
            grad_steps, train_time = train_block(meta, payload, cumulative_grad_steps)
            cumulative_grad_steps += grad_steps
            grad_trace.append([time.monotonic(), cumulative_grad_steps])
            if grad_steps > 0 or publish_empty_blocks:
                publish(publisher, cumulative_grad_steps, policy_step)

            for name, value in meta.get("stats") or []:
                aggregator.update(name, value)
            aggregator.update("Sebulba/queue_depth", inbox.qsize())
            if meta.get("staleness") is not None:
                aggregator.update("Sebulba/param_staleness_steps", float(meta["staleness"]))
            aggregator.update("Sebulba/xfer_bytes", float(meta.get("_wire_bytes", 0)))
            aggregator.update(f"Sebulba/xfer_bytes/ch{actor_id}", float(meta.get("_wire_bytes", 0)))

            if fleet_exporter is not None:
                # Dict writes only — the exporter's daemon thread owns the sends.
                fleet_exporter.counter("grad_steps", cumulative_grad_steps)
                fleet_exporter.counter("env_steps", slots.total)
                fleet_exporter.counter("blocks", blocks)
                fleet_exporter.counter("publishes", publisher.seq)
                fleet_exporter.counter("bytes_published", publisher.bytes_published)
                fleet_exporter.gauge("policy_step", policy_step)
                fleet_exporter.gauge("Sebulba/queue_depth", inbox.qsize())
                if meta.get("staleness") is not None:
                    fleet_exporter.gauge("Sebulba/param_staleness_steps", float(meta["staleness"]))

            if logger is not None and (policy_step - last_log >= cfg.metric.log_every or cfg.dry_run):
                metrics = aggregator.compute()
                aggregator.reset()
                if train_time > 0:
                    metrics["Time/sps_train"] = grad_steps / train_time
                env_time = float(meta.get("env_time", 0) or 0)
                if env_time > 0:
                    metrics["Time/sps_env_interaction"] = sps_env_steps / env_time
                monitor.log_metrics(logger, metrics, policy_step)
                last_log = policy_step

            if cfg.checkpoint.every > 0 and (policy_step - last_checkpoint) >= cfg.checkpoint.every:
                save_ckpt()
            guard.boundary(policy_step, save_ckpt)

        if cfg.checkpoint.save_last:
            save_ckpt()
    except BaseException as exc:
        # A crashing learner must still leave its summary behind: the grad-step
        # trace and lifecycle events are exactly what the chaos tests and
        # sebulba_bench.py need to diagnose the death (satellite of this PR —
        # previously only the happy path wrote it).
        error = _exc_summary(exc)
        raise
    finally:
        bytes_received = inbox.bytes_received()
        if fleet_exporter is not None:
            try:
                # Before monitor.close(): the exporter ships the tracer's spans
                # for the merged fleet Perfetto file, and close() deactivates it.
                fleet_exporter.close()
            except Exception:
                pass
        inbox.close()
        try:
            # monitor.close() can itself raise (strict mode drains pending NaN
            # trips there) — the summary write may not depend on it surviving.
            monitor.close()
        except BaseException as exc:
            if error is None:
                error = _exc_summary(exc)
            raise
        finally:
            _write_summary(
                {
                    "wall_time_s": time.monotonic() - t_start,
                    "blocks": blocks,
                    "cumulative_grad_steps": cumulative_grad_steps,
                    "env_steps_total": slots.total,
                    "policy_step": policy_step,
                    "bytes_received": bytes_received,
                    "bytes_published": publisher.bytes_published,
                    "publishes": publisher.seq,
                    "grad_step_trace": grad_trace,
                    "events": inbox.events,
                    "t_start": t_start,
                    "error": error,
                    # the device the learner measured on, read in this process
                    "device": device_identity(),
                }
            )
    if logger is not None:
        logger.close()


def _probe_spaces(cfg):
    """The learner never steps envs; build ONE wrapped env to read the spaces the
    agent builder needs, then tear it down (same thunk as the actors' env 0, so
    the spaces — and thus the built params — match bit-for-bit)."""
    from sheeprl_tpu.utils.env import make_env

    probe = make_env(cfg, cfg.seed, 0)()
    obs_space, act_space = probe.observation_space, probe.action_space
    probe.close()
    return obs_space, act_space


# ----------------------------------------------------------------------- entry
_RUNNERS = {
    ("sac", "learner"): _run_sac_learner,
    ("sac", "actor"): _run_sac_actor,
    ("ppo", "learner"): _run_ppo_learner,
    ("ppo", "actor"): _run_ppo_actor,
}


def run(ctx, cfg, spec: PlacementSpec, algo: str) -> None:
    """Role dispatch for a Sebulba child process (called from the decoupled
    algorithm ``main``s when ``distributed.mode=sebulba``)."""
    key = (algo, spec.role)
    if key not in _RUNNERS:
        raise ValueError(f"no sebulba runner for algo={algo!r} role={spec.role!r}")
    try:
        _RUNNERS[key](ctx, cfg, spec)
    except BaseException as exc:
        # Learner crashes BEFORE _learner_loop (agent build, checkpoint resume,
        # space probe) never reach the loop's summary-writing finally; leave a
        # minimal error summary so the launcher/bench still learn what happened.
        if spec.is_learner and not _summary_written:
            _write_summary(
                {
                    "wall_time_s": 0.0,
                    "blocks": 0,
                    "cumulative_grad_steps": 0,
                    "env_steps_total": 0,
                    "policy_step": 0,
                    "bytes_received": 0,
                    "bytes_published": 0,
                    "publishes": 0,
                    "grad_step_trace": [],
                    "events": [],
                    "t_start": time.monotonic(),
                    "error": _exc_summary(exc),
                }
            )
        raise
