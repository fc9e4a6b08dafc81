"""The policy inference server: registry-backed, AOT-precompiled, continuously
batched, drain-on-SIGTERM.

One :class:`PolicyServer` hosts any number of registered policies.  Startup does
ALL the expensive work: each ``serve.policies`` spec resolves through the
registry router, rebuilds its agent from the run config copied into the version
payload, loads the checkpoint (checksum-verified), and AOT-compiles the full
batch ladder (``precompile.precompile_ladder``) — with the persistent compile
cache wired, a warm replica restart deserializes every executable from disk.
After ``mark_warm()`` the steady state is numpy in, ``Compiled`` call, numpy
out: zero traces, zero compiles, enforced by the PR-1 recompile watchdog
(``analysis.strict=True`` upgrades any violation to :class:`RecompileError`).

Threads (all I/O-bound; the GIL is irrelevant because dispatch blocks in XLA):

* the **accept loop** (``run()``, main thread) — admits connections, watches the
  preemption flag;
* one **reader** per client channel — decodes requests and routes them onto the
  owning endpoint's bounded queue (a full queue blocks the reader, which blocks
  the client's TCP stream: backpressure, not unbounded buffering);
* one **dispatcher** per endpoint — pulls continuous batches
  (``batching.collect_batch``), pads to the ladder bucket, runs the
  precompiled executable, and replies to every request in the batch with
  latency/queue stamps.

Wire protocol (framed transport from ``distributed.transport``):

* ``("ping", {}) → ("pong", {policies, draining, queue_depth, p99_ms})`` —
  readiness + load probe (the fleet front routes on the load stamps);
* ``("act", {policy, req_id}, obs_dict) → ("act_result", {req_id, queue_ms,
  infer_ms, batch_fill, bucket, p99_ms}, {"action": row})`` — one observation
  in, one action out; stateful (recurrent) policies also accept ``session``
  (client id whose device-resident act state continues across requests —
  :class:`~sheeprl_tpu.serve.state_cache.SessionStateCache`) and ``reset``
  (force an episode restart for that session);
* ``("act", ...) during drain → ("draining", {req_id})`` — the client retries
  against another replica;
* unknown policy / malformed obs → ``("error", {req_id, error})``.

Drain contract (chaos-tested): on SIGTERM the server stops accepting, answers
new requests with ``draining``, dispatches everything already queued, replies to
every accepted request, writes its summary, and exits ``RESUMABLE_EXIT_CODE``
(75) so the supervisor's serving mode respawns it.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
import warnings
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from sheeprl_tpu.distributed.transport import Channel, ChannelClosed, Listener
from sheeprl_tpu.fault import preemption as fault_preemption
from sheeprl_tpu.obs import perf as obs_perf
from sheeprl_tpu.obs.fleet import maybe_exporter
from sheeprl_tpu.serve.batching import bucket_ladder, collect_batch, pad_obs_batch, pick_bucket
from sheeprl_tpu.serve.precompile import dispatch_key, precompile_ladder, zero_key
from sheeprl_tpu.serve.router import resolve_policy
from sheeprl_tpu.utils.metric import MetricAggregator

#: Env var override for where the exit summary lands (CI smoke / chaos harness).
SERVE_SUMMARY_ENV_VAR = "SHEEPRL_TPU_SERVE_SUMMARY"


@dataclass
class _Request:
    channel: Channel
    req_id: Any
    obs: Dict[str, np.ndarray]
    t_enq: float
    session: Optional[str] = None  # stateful policies: the client id owning act state
    reset: bool = False  # force an episode restart for that session


class _Endpoint:
    """One loaded policy: its precompiled ladder, request queue, dispatcher state."""

    def __init__(self, name: str, version: int, policy, compiled, ladder, queue_depth: int, seed: int):
        import queue as _queue

        self.name = name
        self.version = version
        self.policy = policy
        self.compiled = compiled
        self.ladder = ladder
        self.queue: "_queue.Queue[_Request]" = _queue.Queue(maxsize=queue_depth)
        self.seed = seed
        self.state_cache = None  # SessionStateCache for stateful policies
        self.dispatch_counter = 0
        # Per-bucket dispatch count + infer seconds — with the registered XLA
        # cost models (obs/perf.py) this yields per-bucket MFU in the exit
        # summary.  Single writer (the dispatcher thread); readers tolerate a
        # torn [count, seconds] pair (one 1 Hz gauge sample, self-correcting).
        self.bucket_stats: Dict[int, List[float]] = {}
        # accepted is bumped by one reader thread per client connection; an
        # unguarded += is a read-modify-write that loses updates (JL008), which
        # would silently break the accepted == replied + dropped summary
        # invariant.  replied/dropped/dispatch_counter have a single writer
        # (the endpoint's dispatcher thread) and stay lock-free.
        self.stats_lock = threading.Lock()
        self.accepted = 0
        self.replied = 0
        self.dropped = 0
        self.slo_violations = 0  # replies whose end-to-end latency beat serve.slo_ms
        self.metrics = MetricAggregator(
            {
                "Serve/latency_ms": "histogram",
                "Serve/infer_ms": "histogram",
                "Serve/batch_fill": "mean",
                "Serve/queue_depth": "mean",
                "Serve/dispatches": "sum",
            }
        )

    @property
    def canonical(self) -> str:
        return f"{self.name}:{self.version}"


class PolicyServer:
    """Load → precompile → serve → drain.  One instance per replica process."""

    def __init__(self, cfg: Any):
        self.cfg = cfg
        serve_cfg = cfg.serve
        self.serve_cfg = serve_cfg
        self.max_batch = int(serve_cfg.max_batch_size)
        self.delay_s = float(serve_cfg.max_batch_delay_ms) / 1000.0
        self.drain_timeout_s = float(serve_cfg.drain_timeout_s)
        self.log_every_s = float(serve_cfg.log_every_s)
        self.greedy = bool(serve_cfg.greedy)
        slo = serve_cfg.get("slo_ms", None)
        self.slo_ms: Optional[float] = float(slo) if slo else None
        self.precision = _normalize_precision(serve_cfg.get("precision", "f32"))
        self.parity: Dict[str, Dict[str, Any]] = {}  # canonical -> parity stamp
        self._draining = False
        self._stop = threading.Event()
        self._channels: List[Channel] = []
        self._channels_lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self.endpoints: Dict[str, _Endpoint] = {}  # canonical "name:version" -> endpoint
        self.aliases: Dict[str, str] = {}  # request spec -> canonical
        self.listener: Optional[Listener] = None
        self.startup_seconds = 0.0
        self.precompile_seconds = 0.0
        self.watchdog = None
        self._stats_lock = threading.Lock()  # guards rejected_draining (readers race)
        self.rejected_draining = 0
        self._fleet = None  # FleetExporter, attached in run()

        t0 = time.perf_counter()
        self._perf_t0 = t0  # perf attribution clock: startup compiles count too
        self._load_policies()
        self.startup_seconds = time.perf_counter() - t0

    # ------------------------------------------------------------------ startup
    def _load_policies(self) -> None:
        import jax

        from sheeprl_tpu.config.core import load_config
        from sheeprl_tpu.obs.watchdog import RecompileWatchdog
        from sheeprl_tpu.parallel.mesh import MeshContext, build_mesh
        from sheeprl_tpu.utils.model_manager import build_model_manager
        from sheeprl_tpu.utils.policy import load_policy, parity_stamp

        specs = list(self.serve_cfg.policies)
        if not specs:
            raise ValueError("serve.policies is empty: nothing to serve")
        self.watchdog = RecompileWatchdog()
        manager = build_model_manager(self.cfg)
        ladder = bucket_ladder(self.max_batch, self.serve_cfg.buckets)
        seed = int(self.cfg.seed)
        for spec in specs:
            name, entry = resolve_policy(manager, spec)
            canonical = f"{name}:{int(entry['version'])}"
            if canonical in self.endpoints:
                self.aliases.setdefault(str(spec), canonical)
                continue
            payload_dir = Path(entry["path"])
            run_cfg_path = payload_dir / "config.yaml"
            if not run_cfg_path.is_file():
                raise FileNotFoundError(
                    f"{canonical}: no config.yaml inside the registered payload "
                    f"{payload_dir} (re-register the model; registration now copies "
                    "the run config into the version payload)"
                )
            run_cfg = load_config(run_cfg_path)
            precision = (run_cfg.get("mesh") or {}).get("precision", "fp32")
            ctx = MeshContext(
                mesh=build_mesh(devices=jax.devices()[:1]), precision=precision, seed=seed
            )
            policy = load_policy(
                ctx, run_cfg, str(payload_dir), greedy=self.greedy, precision=self.precision
            )
            if self.precision != "f32":
                # Parity stamp: reload at f32 (fresh run cfg — load_policy mutates
                # it) and compare greedy actions on seeded random obs.  Runs
                # before mark_warm, so its compiles are startup work, not
                # watchdog violations.
                reference = load_policy(
                    MeshContext(
                        mesh=build_mesh(devices=jax.devices()[:1]), precision=precision, seed=seed
                    ),
                    load_config(run_cfg_path),
                    str(payload_dir),
                    greedy=self.greedy,
                    precision="f32",
                )
                self.parity[canonical] = parity_stamp(policy, reference, seed=seed)
                print(f"[serve] {canonical}: parity {self.parity[canonical]}", flush=True)
            compiled, secs = precompile_ladder(
                policy,
                ladder,
                perf_name=f"serve/{canonical}" if obs_perf.perf_enabled(self.cfg) else None,
            )
            self.precompile_seconds += secs
            ep = _Endpoint(
                name=name,
                version=int(entry["version"]),
                policy=policy,
                compiled=compiled,
                ladder=ladder,
                queue_depth=int(self.serve_cfg.queue_depth),
                seed=seed,
            )
            if policy.stateful:
                from sheeprl_tpu.serve.state_cache import SessionStateCache

                ep.state_cache = SessionStateCache(
                    policy.zero_state_fn, capacity=int(self.serve_cfg.session_capacity)
                )

                # Warm gather/scatter THROUGH the compiled act fn: its output
                # sharding is what dispatch-time scatters (and, once committed
                # to the storage, gathers) trace against.
                def _warm_step(bucket: int, state: Any, _ep: _Endpoint = ep) -> Any:
                    warm_obs = _ep.policy.zero_obs(bucket)
                    warm_first = np.ones((bucket, 1), np.float32)
                    _, new_state = _ep.compiled[bucket](
                        _ep.policy.params, warm_obs, warm_first, state, zero_key()
                    )
                    return new_state

                ep.state_cache.warmup(ladder, step_fn=_warm_step)
            self.endpoints[canonical] = ep
            self._register_aliases(spec, ep, entry)
            print(
                f"[serve] {canonical}: algo={policy.algo} ladder={ladder} "
                f"precompile={secs:.2f}s",
                flush=True,
            )
        # Everything compiled from here on is a recompile.
        self.watchdog.mark_warm()

    def _register_aliases(self, spec: str, ep: _Endpoint, entry: Dict[str, Any]) -> None:
        """Route keys for one endpoint: the spec as configured, the canonical
        ``name:version``, the bare name and ``name:latest`` (first loaded version
        of a name wins those two — pin ``name:version`` to be explicit)."""
        self.aliases[ep.canonical] = ep.canonical
        self.aliases.setdefault(str(spec), ep.canonical)
        self.aliases.setdefault(ep.name, ep.canonical)
        self.aliases.setdefault(f"{ep.name}:latest", ep.canonical)
        stage = str(entry.get("stage", "") or "")
        if stage and stage.lower() != "none":
            self.aliases.setdefault(f"{ep.name}:{stage}", ep.canonical)

    # ------------------------------------------------------------------ serving
    def run(self) -> int:
        """Listen, serve until stop/preemption, drain, summarize.  Returns the
        process exit code (75 when preempted, 0 on a clean ``shutdown()``)."""
        serve_cfg = self.serve_cfg
        self.listener = Listener(host=str(serve_cfg.host), port=int(serve_cfg.port))
        self._write_ready_file()
        print(
            f"[serve] listening on {self.listener.address} "
            f"(policies: {sorted(self.endpoints)})",
            flush=True,
        )
        for ep in self.endpoints.values():
            t = threading.Thread(
                target=self._dispatch_loop, args=(ep,), name=f"serve-dispatch-{ep.canonical}",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
        # Fleet telemetry: the replica generation is the supervisor's restart
        # counter, so respawned replicas land in a fresh snapshot slot lineage;
        # the fleet manager numbers replica slots via SHEEPRL_TPU_SERVE_SLOT so
        # N replicas show as serve0..serveN-1 instead of colliding on serve0.
        self._fleet = maybe_exporter(
            self.cfg,
            "serve",
            actor_id=int(os.environ.get("SHEEPRL_TPU_SERVE_SLOT", "0") or 0),
            generation=int(os.environ.get("SHEEPRL_TPU_FAULT_RESTARTS", "0") or 0),
        )
        last_log = time.monotonic()
        last_fleet = 0.0
        try:
            while not self._stop.is_set() and not fault_preemption.preemption_requested():
                try:
                    ch = self.listener.accept(timeout=0.2)
                except TimeoutError:
                    pass
                except OSError:
                    break
                else:
                    with self._channels_lock:
                        self._channels.append(ch)
                    t = threading.Thread(
                        target=self._reader_loop, args=(ch,), name="serve-reader", daemon=True
                    )
                    t.start()
                    self._threads.append(t)
                if self.log_every_s > 0 and time.monotonic() - last_log >= self.log_every_s:
                    last_log = time.monotonic()
                    self._log_status()
                if self._fleet is not None and time.monotonic() - last_fleet >= 1.0:
                    last_fleet = time.monotonic()
                    self._fleet_update()
        finally:
            preempted = fault_preemption.preemption_requested()
            self._drain()
            if self._fleet is not None:
                self._fleet_update()  # final counters cover the drained queue
                try:
                    self._fleet.close()
                except Exception:
                    pass
            self._write_summary(preempted=preempted)
            self._write_perf_report()
            self._close()
        return fault_preemption.RESUMABLE_EXIT_CODE if preempted else 0

    def shutdown(self) -> None:
        """Clean stop (tests/benchmarks): same drain path, exit code 0."""
        self._stop.set()

    # ------------------------------------------------------------------ readers
    def _reader_loop(self, ch: Channel) -> None:
        while not ch.closed:
            try:
                kind, meta, payload = ch.recv(timeout=0.5)
            except TimeoutError:
                continue
            except (ChannelClosed, Exception):
                return
            try:
                self._handle(ch, kind, meta, payload)
            except ChannelClosed:
                return

    def _handle(self, ch: Channel, kind: str, meta: Dict[str, Any], payload: Any) -> None:
        if kind == "ping":
            p99 = float("nan")
            for ep in self.endpoints.values():
                hist = ep.metrics.metrics["Serve/latency_ms"].compute()
                if hist:
                    p = float(hist["p99"])
                    if not (p99 == p99) or p > p99:  # max over endpoints, NaN-safe
                        p99 = p
            ch.send(
                "pong",
                policies=sorted(self.endpoints),
                aliases=sorted(self.aliases),
                draining=bool(self._draining),
                precision=self.precision,
                parity=self.parity,
                # Load stamps: the fleet front's routing probe.
                queue_depth=sum(ep.queue.qsize() for ep in self.endpoints.values()),
                p99_ms=p99 if p99 == p99 else None,
            )
            return
        if kind != "act":
            ch.send("error", req_id=meta.get("req_id"), error=f"unknown message kind {kind!r}")
            return
        req_id = meta.get("req_id")
        if self._draining:
            with self._stats_lock:
                self.rejected_draining += 1
            ch.send("draining", req_id=req_id)
            return
        spec = str(meta.get("policy", ""))
        canonical = self.aliases.get(spec)
        if canonical is None:
            ch.send(
                "error",
                req_id=req_id,
                error=f"no policy routed as {spec!r} (served: {sorted(self.aliases)})",
            )
            return
        ep = self.endpoints[canonical]
        if not isinstance(payload, dict):
            ch.send("error", req_id=req_id, error="act payload must be an obs dict")
            return
        session = meta.get("session")
        ep.queue.put(
            _Request(
                channel=ch,
                req_id=req_id,
                obs=payload,
                t_enq=time.monotonic(),
                session=str(session) if session is not None else None,
                reset=bool(meta.get("reset", False)),
            )
        )
        with ep.stats_lock:
            ep.accepted += 1

    # --------------------------------------------------------------- dispatcher
    def _dispatch_loop(self, ep: _Endpoint) -> None:
        while True:
            batch = collect_batch(ep.queue, self.max_batch, self.delay_s, first_timeout_s=0.05)
            if not batch:
                if self._stop.is_set() or self._draining:
                    if ep.queue.empty():
                        return
                continue
            try:
                self._dispatch(ep, batch)
            except Exception as e:  # reply rather than killing the dispatcher
                from sheeprl_tpu.obs.watchdog import RecompileError

                for req in batch:
                    try:
                        req.channel.send("error", req_id=req.req_id, error=str(e))
                    except ChannelClosed:
                        ep.dropped += 1
                if isinstance(e, RecompileError):
                    raise

    def _dispatch(self, ep: _Endpoint, batch: List[_Request]) -> None:
        import jax

        from sheeprl_tpu.obs.watchdog import RecompileError, RecompileWarning

        n = len(batch)
        bucket = pick_bucket(ep.ladder, n)
        try:
            obs = pad_obs_batch([r.obs for r in batch], ep.policy.obs_template, bucket)
        except (KeyError, ValueError) as e:
            for req in batch:
                try:
                    req.channel.send("error", req_id=req.req_id, error=str(e))
                except ChannelClosed:
                    ep.dropped += 1
            return
        key = dispatch_key(ep.seed, ep.dispatch_counter)
        ep.dispatch_counter += 1
        t0 = time.monotonic()
        if ep.state_cache is not None:
            # Stateful dispatch: map sessions to device state rows, pad with the
            # scratch row (padding scatters there harmlessly), one recurrent step.
            cache = ep.state_cache
            idx, is_first = cache.assign([r.session for r in batch], [r.reset for r in batch])
            idx_p = np.full((bucket,), cache.scratch, np.int32)
            idx_p[:n] = idx
            is_first_p = np.ones((bucket, 1), np.float32)
            is_first_p[:n] = is_first
            state = cache.gather(idx_p)
            out, new_state = ep.compiled[bucket](ep.policy.params, obs, is_first_p, state, key)
            actions = np.asarray(jax.device_get(out))
            cache.scatter(idx_p, new_state)
        else:
            actions = np.asarray(jax.device_get(ep.compiled[bucket](ep.policy.params, obs, key)))
        t1 = time.monotonic()

        new_compiles = self.watchdog.poll_new() if self.watchdog is not None else 0
        if new_compiles:
            msg = (
                f"{ep.canonical}: {new_compiles} post-warmup compile(s) during a "
                f"bucket-{bucket} dispatch — the AOT ladder should make this impossible"
            )
            if bool(self.cfg.analysis.strict):
                raise RecompileError(msg)
            warnings.warn(msg, RecompileWarning)

        stats = ep.bucket_stats.setdefault(bucket, [0, 0.0])
        stats[0] += 1
        stats[1] += t1 - t0

        infer_ms = (t1 - t0) * 1000.0
        ep.metrics.update("Serve/infer_ms", infer_ms)
        ep.metrics.update("Serve/batch_fill", n / bucket)
        ep.metrics.update("Serve/queue_depth", ep.queue.qsize())
        ep.metrics.update("Serve/dispatches", 1.0)
        latencies = [(t1 - r.t_enq) * 1000.0 for r in batch]
        ep.metrics.update("Serve/latency_ms", latencies)
        if self.slo_ms is not None:
            ep.slo_violations += sum(1 for lat in latencies if lat > self.slo_ms)
        hist = ep.metrics.metrics["Serve/latency_ms"].compute()
        p99 = float(hist["p99"]) if hist else float("nan")
        for i, req in enumerate(batch):
            try:
                req.channel.send(
                    "act_result",
                    payload={"action": actions[i]},
                    req_id=req.req_id,
                    queue_ms=(t0 - req.t_enq) * 1000.0,
                    infer_ms=infer_ms,
                    batch_fill=n / bucket,
                    bucket=bucket,
                    p99_ms=p99,
                )
                ep.replied += 1
            except ChannelClosed:
                ep.dropped += 1

    # ------------------------------------------------------------------ teardown
    def _drain(self) -> None:
        """Stop admitting, flush every queue, reply to everything accepted."""
        self._draining = True
        time.sleep(0.05)  # let in-flight reader enqueues land before emptiness checks
        deadline = time.monotonic() + self.drain_timeout_s
        for ep in self.endpoints.values():
            while not ep.queue.empty() and time.monotonic() < deadline:
                time.sleep(0.01)
        self._stop.set()
        for t in self._threads:
            if t.name.startswith("serve-dispatch"):
                t.join(timeout=max(deadline - time.monotonic(), 1.0))

    def _close(self) -> None:
        if self.listener is not None:
            self.listener.close()
        with self._channels_lock:
            channels = list(self._channels)
        for ch in channels:
            ch.close()

    def _fleet_update(self) -> None:
        """Push replica-wide counters/gauges to the fleet plane.  Dict writes +
        one framed send on the exporter's own thread — nothing here touches the
        dispatchers' hot path."""
        exporter = self._fleet
        if exporter is None:
            return
        accepted = sum(ep.accepted for ep in self.endpoints.values())
        replied = sum(ep.replied for ep in self.endpoints.values())
        dropped = sum(ep.dropped for ep in self.endpoints.values())
        dispatches = sum(ep.dispatch_counter for ep in self.endpoints.values())
        violations = sum(ep.slo_violations for ep in self.endpoints.values())
        exporter.counter("requests_accepted", accepted)
        exporter.counter("requests_replied", replied)
        exporter.counter("requests_dropped", dropped)
        exporter.counter("dispatches", dispatches)
        exporter.counter("slo_violations", violations)
        exporter.gauge("Serve/queue_depth", sum(ep.queue.qsize() for ep in self.endpoints.values()))
        if self.slo_ms is not None:
            exporter.gauge("Serve/slo_ms", self.slo_ms)
            exporter.gauge("Serve/slo_burn", violations / max(replied, 1))
        p99 = float("nan")
        for ep in self.endpoints.values():
            hist = ep.metrics.metrics["Serve/latency_ms"].compute()
            if hist:
                p = float(hist["p99"])
                if not (p99 == p99) or p > p99:  # max over endpoints, NaN-safe
                    p99 = p
        if p99 == p99:
            exporter.gauge("Serve/latency_p99_ms", p99)
        if obs_perf.perf_enabled(self.cfg):
            perf = self.perf_summary()
            exporter.gauge("Perf/mfu", perf["mfu"])
            exporter.gauge("Perf/goodput", perf["goodput"])

    def _log_status(self) -> None:
        for ep in self.endpoints.values():
            computed = ep.metrics.compute()
            p99 = computed.get("Serve/latency_ms/p99", float("nan"))
            fill = computed.get("Serve/batch_fill", float("nan"))
            print(
                f"[serve] {ep.canonical}: accepted={ep.accepted} replied={ep.replied} "
                f"p99={p99:.2f}ms fill={fill:.2f} depth={ep.queue.qsize()}",
                flush=True,
            )

    # ------------------------------------------------------------------ artifacts
    def _write_ready_file(self) -> None:
        from sheeprl_tpu.parallel.mesh import device_identity

        ready = self.serve_cfg.ready_file
        if not ready:
            return
        doc = {
            "host": self.listener.host,
            "port": self.listener.port,
            "policies": sorted(self.endpoints),
            "startup_seconds": self.startup_seconds,
            "precompile_seconds": self.precompile_seconds,
            "precision": self.precision,
            "parity": self.parity,
            # where THIS replica's JAX runs: benchmark rows copy it from here
            **device_identity(),
        }
        _atomic_write_json(Path(ready), doc)

    def summary(self, preempted: bool = False) -> Dict[str, Any]:
        per_policy = {}
        for canonical, ep in self.endpoints.items():
            per_policy[canonical] = {
                "accepted": ep.accepted,
                "replied": ep.replied,
                "dropped": ep.dropped,
                "dispatches": ep.dispatch_counter,
                "slo_violations": ep.slo_violations,
                "metrics": ep.metrics.compute(),
            }
            if ep.state_cache is not None:
                per_policy[canonical]["sessions"] = ep.state_cache.stats()
        total_replied = sum(ep.replied for ep in self.endpoints.values())
        total_violations = sum(ep.slo_violations for ep in self.endpoints.values())
        return {
            "preempted": bool(preempted),
            "drained": True,
            "rejected_draining": self.rejected_draining,
            "accepted": sum(ep.accepted for ep in self.endpoints.values()),
            "replied": total_replied,
            "dropped": sum(ep.dropped for ep in self.endpoints.values()),
            "slo_ms": self.slo_ms,
            "slo_violations": total_violations,
            "slo_burn": total_violations / max(total_replied, 1),
            "recompiles": int(self.watchdog.recompiles) if self.watchdog else 0,
            "startup_seconds": self.startup_seconds,
            "precompile_seconds": self.precompile_seconds,
            "precision": self.precision,
            "parity": self.parity,
            "policies": per_policy,
            "perf": self.perf_summary() if obs_perf.perf_enabled(self.cfg) else None,
        }

    def perf_summary(self) -> Dict[str, Any]:
        """Cost-model MFU + goodput for this replica (``obs/perf.py`` plane).

        MFU is over the whole process lifetime (startup included), so an idle
        replica honestly reads near zero; per-bucket MFU uses each bucket's own
        infer seconds, so it reads the hardware efficiency of the compiled
        program itself.  Goodput classifies infer time as compute and the
        ladder's AOT compiles as recompile; the rest (queue waits, idle accept
        loop) is other.
        """
        import jax

        device = jax.devices()[0]
        peak = obs_perf.peak_flops(device)
        models = obs_perf.registered_cost_models()
        per_policy: Dict[str, Any] = {}
        total_flops = total_bytes = total_infer_s = 0.0
        for canonical, ep in self.endpoints.items():
            buckets: Dict[str, Any] = {}
            for bucket, (count, seconds) in sorted(ep.bucket_stats.items()):
                model = models.get(f"serve/{canonical}/b{bucket}", {})
                flops_per_dispatch = float(model.get("flops", 0.0))
                flops = flops_per_dispatch * count
                total_flops += flops
                total_bytes += float(model.get("bytes_accessed", 0.0)) * count
                total_infer_s += seconds
                buckets[str(bucket)] = {
                    "dispatches": int(count),
                    "infer_s": seconds,
                    "flops_per_dispatch": flops_per_dispatch,
                    "mfu": flops / seconds / peak if seconds > 0 and peak > 0 else 0.0,
                }
            per_policy[canonical] = buckets
        elapsed = max(time.perf_counter() - self._perf_t0, 1e-9)
        ledger = obs_perf.GoodputLedger()
        fractions = ledger.classify(
            {"Time/phase_dispatch": total_infer_s},
            elapsed,
            recompile_s=self.watchdog.compile_seconds if self.watchdog is not None else 0.0,
        )
        return {
            "role": "serve",
            "device_kind": str(getattr(device, "device_kind", "") or ""),
            "peak_flops": peak,
            "elapsed_s": elapsed,
            "total_flops": total_flops,
            "total_bytes_accessed": total_bytes,
            "infer_s": total_infer_s,
            "achieved_flops_per_sec": total_flops / elapsed,
            "mfu": total_flops / elapsed / peak if peak > 0 else 0.0,
            "goodput": fractions["compute"] + fractions["env"],
            "goodput_fractions": fractions,
            "per_policy": per_policy,
            "cost_models": {k: v for k, v in models.items() if k.startswith("serve/")},
        }

    def _write_summary(self, preempted: bool) -> None:
        path = os.environ.get(SERVE_SUMMARY_ENV_VAR) or self.serve_cfg.summary_path
        if not path:
            return
        _atomic_write_json(Path(path), self.summary(preempted=preempted))

    def _write_perf_report(self) -> None:
        """``perf_report.json``: env override, else next to the exit summary."""
        if not obs_perf.perf_enabled(self.cfg):
            return
        path = os.environ.get(obs_perf.PERF_REPORT_ENV_VAR)
        if not path:
            summary_path = os.environ.get(SERVE_SUMMARY_ENV_VAR) or self.serve_cfg.summary_path
            if summary_path:
                path = str(Path(summary_path).parent / "perf_report.json")
        if not path:
            return
        try:
            _atomic_write_json(Path(path), self.perf_summary())
        except OSError:
            pass


def _normalize_precision(spec: Any) -> str:
    """serve.precision → canonical tier name (f32 | bf16 | int8)."""
    key = str(spec if spec is not None else "f32").lower()
    if key in ("", "none", "null", "f32", "fp32", "float32"):
        return "f32"
    if key in ("bf16", "bfloat16"):
        return "bf16"
    if key == "int8":
        return "int8"
    raise ValueError(f"Unknown serve.precision {spec!r}; expected f32, bf16 or int8")


def _atomic_write_json(path: Path, doc: Dict[str, Any]) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp_name = tempfile.mkstemp(prefix=f".{path.name}.", suffix=".tmp", dir=path.parent)
    with os.fdopen(fd, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp_name, path)
