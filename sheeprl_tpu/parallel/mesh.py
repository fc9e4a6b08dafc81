"""Device-mesh context: the TPU-native replacement for Lightning Fabric.

The reference's L0 substrate is ``Fabric(devices, strategy, accelerator, precision)``
plus NCCL collectives (``/root/reference/sheeprl/cli.py:101,149``).  Here the substrate
is a ``jax.sharding.Mesh`` over ICI/DCN:

* data parallelism = shard the batch over the ``data`` axis; XLA/GSPMD inserts the
  gradient ``psum`` when params are replicated and the loss is a global mean;
* an optional ``model`` (tensor-parallel) axis is free with GSPMD sharding rules —
  something the reference never had (SURVEY §2.4);
* multi-host runs initialise ``jax.distributed`` and the same code path scales over DCN.

``MeshContext`` carries mesh + shardings + precision policy + process topology, and is
passed to every algorithm ``main`` the way ``fabric`` is in the reference.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.obs.tracer import span


_distributed_initialized = False

#: Default multi-host barrier timeout (seconds); override with
#: ``SHEEPRL_TPU_BARRIER_TIMEOUT_S`` (<=0 disables the timeout entirely).
DEFAULT_BARRIER_TIMEOUT_S = 600.0


class BarrierTimeoutError(RuntimeError):
    """A multi-host barrier did not complete in time: a peer process is likely dead
    (preempted, OOM-killed, crashed before reaching the barrier).  Raised instead of
    hanging forever so the supervisor can classify and relaunch the run."""


def _wait_with_timeout(fn, name: str, timeout_s: float) -> None:
    """Run blocking ``fn`` on a side thread and give up after ``timeout_s``.

    ``sync_global_devices`` has no cancellation API, so the orphaned thread is left
    to die with the process — acceptable, because the only caller reaction to a
    barrier timeout is to tear the process down and let the supervisor relaunch."""
    import threading

    result: Dict[str, Any] = {}

    def target() -> None:
        try:
            fn()
            result["ok"] = True
        except Exception as e:  # pragma: no cover - backend-specific failures
            result["error"] = e

    t = threading.Thread(target=target, name=f"barrier-{name}", daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise BarrierTimeoutError(
            f"multi-host barrier {name!r} timed out after {timeout_s:.0f}s: a peer "
            "process is likely dead or preempted (this rank would otherwise hang "
            "forever). Restart the run from the latest checkpoint — "
            "`python -m sheeprl_tpu.supervise` automates this — or raise/disable the "
            "timeout with SHEEPRL_TPU_BARRIER_TIMEOUT_S (<=0 disables)."
        )
    if "error" in result:
        raise result["error"]


def sync_global_devices_with_timeout(name: str, timeout_s: Optional[float] = None) -> None:
    """``multihost_utils.sync_global_devices`` with a deadline and an actionable
    error.  No-op in single-process runs; the env var
    ``SHEEPRL_TPU_BARRIER_TIMEOUT_S`` overrides the default (read per call, so a
    long planned stall — e.g. one rank compiling — can widen it mid-run)."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    if timeout_s is None:
        timeout_s = float(os.environ.get("SHEEPRL_TPU_BARRIER_TIMEOUT_S", DEFAULT_BARRIER_TIMEOUT_S))
    if timeout_s <= 0:
        multihost_utils.sync_global_devices(name)
        return
    _wait_with_timeout(lambda: multihost_utils.sync_global_devices(name), name, timeout_s)


def device_identity() -> Dict[str, Any]:
    """The device THIS process's JAX runs on, as every benchmark row, ready file
    and summary names it — read where the measurement happens, never inherited
    from a parent's idea of the platform."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


#: Env-var spellings of ``mesh.distributed.*`` so the Sebulba launcher and
#: hand-started processes share one init path with config-driven runs (config
#: wins when both are set — an explicit override beats ambient environment).
COORDINATOR_ADDRESS_ENV_VAR = "SHEEPRL_TPU_COORDINATOR_ADDRESS"
NUM_PROCESSES_ENV_VAR = "SHEEPRL_TPU_NUM_PROCESSES"
PROCESS_ID_ENV_VAR = "SHEEPRL_TPU_PROCESS_ID"


def maybe_init_distributed(mesh_cfg: Dict[str, Any], timeout_s: Optional[float] = None) -> None:
    """Initialise multi-host JAX when requested (replaces Fabric ``num_nodes``).
    Takes the ``mesh`` sub-config (not the root config).  Idempotent:
    ``jax.distributed.initialize`` may only run once per process, and multirun
    sweeps call this once per job.

    Coordinator address / process count / process id come from the config or —
    when the config leaves them unset — from ``SHEEPRL_TPU_COORDINATOR_ADDRESS``
    / ``SHEEPRL_TPU_NUM_PROCESSES`` / ``SHEEPRL_TPU_PROCESS_ID``, so a launcher
    can stamp the rendezvous on child environments without config surgery.  The
    init itself runs under the barrier-timeout machinery: a peer that never
    shows up raises :class:`BarrierTimeoutError` instead of hanging this process
    forever (``SHEEPRL_TPU_BARRIER_TIMEOUT_S`` overrides, <=0 disables)."""
    global _distributed_initialized
    dist = mesh_cfg.get("distributed", {}) or {}
    coordinator = dist.get("coordinator_address") or os.environ.get(COORDINATOR_ADDRESS_ENV_VAR)
    if not coordinator or _distributed_initialized:
        return

    def pick(key: str, env_var: str) -> Optional[int]:
        value = dist.get(key)
        if value is None and os.environ.get(env_var):
            value = os.environ[env_var]
        return None if value is None else int(value)

    num_processes = pick("num_processes", NUM_PROCESSES_ENV_VAR)
    process_id = pick("process_id", PROCESS_ID_ENV_VAR)
    if timeout_s is None:
        timeout_s = float(os.environ.get("SHEEPRL_TPU_BARRIER_TIMEOUT_S", DEFAULT_BARRIER_TIMEOUT_S))

    def init() -> None:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )

    if timeout_s <= 0:
        init()
    else:
        _wait_with_timeout(init, "jax_distributed_initialize", timeout_s)
    _distributed_initialized = True


def build_mesh(
    data: int = -1,
    model: int = 1,
    sequence: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``(data, model, sequence)`` mesh. ``data=-1`` consumes remaining devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = model * sequence
    if data == -1:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by model*sequence={fixed}")
        data = n // fixed
    if data * model * sequence != n:
        raise ValueError(f"mesh {data}x{model}x{sequence} != {n} devices")
    dev_array = np.asarray(devices).reshape(data, model, sequence)
    return Mesh(dev_array, axis_names=("data", "model", "sequence"))


@dataclass
class MeshContext:
    mesh: Mesh
    precision: str = "bf16-mixed"
    seed: int = 42
    _rng_key: Optional[jax.Array] = field(default=None, repr=False)
    _local_rng_key: Optional[jax.Array] = field(default=None, repr=False)
    _rng_buf: list = field(default_factory=list, repr=False)
    _local_rng_buf: list = field(default_factory=list, repr=False)
    _warned_replication: bool = field(default=False, repr=False)

    # -- topology -----------------------------------------------------------
    @property
    def world_size(self) -> int:
        return self.mesh.devices.size

    @property
    def data_parallel_size(self) -> int:
        return self.mesh.shape["data"]

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def is_global_zero(self) -> bool:
        return jax.process_index() == 0

    @property
    def device(self) -> jax.Device:
        return self.mesh.devices.flat[0]

    # -- precision ----------------------------------------------------------
    @property
    def compute_dtype(self) -> jnp.dtype:
        if self.precision in ("bf16-mixed", "bf16-true", "bf16"):
            return jnp.bfloat16
        if self.precision in ("16-mixed", "fp16"):
            return jnp.float16
        return jnp.float32

    @property
    def param_dtype(self) -> jnp.dtype:
        # "-true" stores params in the low-precision dtype as well.
        if self.precision == "bf16-true":
            return jnp.bfloat16
        return jnp.float32

    # -- shardings ----------------------------------------------------------
    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return self.sharding()

    def batch_sharding(self, batch_axis: int = 0) -> NamedSharding:
        """Shard the given axis over 'data', replicate the rest."""
        spec = [None] * batch_axis + ["data"]
        return self.sharding(*spec)

    def put_batch(self, tree: Any, batch_axis: int = 0) -> Any:
        """Host→device transfer with the batch axis sharded over ``data``.

        This is what makes every training loop actually data-parallel (the reference
        gets this implicitly from DDP's per-process batches).

        Single process: the whole per-rank batch is the global batch, sharded over
        the local data axis (replication fallback, with a once-per-run warning, when
        it doesn't divide — e.g. tiny dry-run batches on the 8-device CI mesh).

        Multi process: each rank's batch is its LOCAL CHUNK of the global batch
        (global = world × per-rank, exactly the reference's per-rank DDP batches);
        the global array is assembled with ``make_array_from_process_local_data`` —
        a plain ``device_put`` would require every process to pass identical data.
        The per-rank batch must divide the LOCAL device count; anything else raises
        (a silent per-process fallback would let replicas train on different
        "replicated" data and diverge).
        """
        dp = self.data_parallel_size
        sh = self.batch_sharding(batch_axis)
        rep = self.replicated

        if jax.process_count() > 1:
            if dp < jax.process_count():
                # With no data axis spanning the processes there is nothing to
                # shard the per-rank batches over: a "replicated" global array
                # built from different per-rank data would silently diverge the
                # replicas (JAX does not value-check process-local assembly).
                raise ValueError(
                    f"Multi-process runs need the data mesh axis to span the "
                    f"processes (data={dp} < processes={jax.process_count()}); "
                    f"lower mesh.model/mesh.sequence or add devices."
                )
            if dp % jax.process_count() != 0:
                raise ValueError(
                    f"The data mesh axis ({dp}) must divide evenly across the "
                    f"{jax.process_count()} processes for per-rank batch assembly."
                )
            local_dp = dp // jax.process_count()

            def _put(x):
                x = np.asarray(x)
                if x.ndim > batch_axis and x.shape[batch_axis] % local_dp == 0:
                    return jax.make_array_from_process_local_data(sh, x)
                raise ValueError(
                    f"Multi-process data parallelism needs the per-rank batch axis "
                    f"{batch_axis} (shape {x.shape}) to divide the {local_dp} local "
                    f"data-axis device(s); adjust per_rank_batch_size/num_envs."
                )

            return jax.tree.map(_put, tree)

        leaves = jax.tree.leaves(tree)
        all_divisible = all(
            getattr(x, "ndim", 0) > batch_axis and x.shape[batch_axis] % dp == 0 for x in leaves
        )
        if dp <= 1 or all_divisible:
            # ONE pytree device_put — per-leaf calls would each pay their own
            # host-side dispatch.
            return jax.device_put(tree, sh if dp > 1 else rep)

        def _put(x):
            divisible = x.ndim > batch_axis and x.shape[batch_axis] % dp == 0
            if not divisible:
                self.warn_replication_fallback(
                    f"batch axis {batch_axis} of shape {getattr(x, 'shape', '?')}"
                )
            return jax.device_put(x, sh if divisible else rep)

        return jax.tree.map(_put, tree)

    def warn_replication_fallback(self, what: str) -> None:
        """Emit the 1-chip-scaling warning at most once per context."""
        if self._warned_replication:
            return
        self._warned_replication = True
        import logging

        logging.getLogger(__name__).warning(
            "put_batch: %s does not divide the data mesh axis (data=%d); the batch is "
            "REPLICATED, so training scales like a single chip. Make the batch size a "
            "multiple of the data axis (or shrink mesh.data) to restore data-parallel "
            "scaling.",
            what,
            self.data_parallel_size,
        )

    def replicate(self, tree: Any) -> Any:
        return jax.device_put(tree, self.replicated)

    @property
    def model_parallel_size(self) -> int:
        return self.mesh.shape["model"]

    def shard_params(self, tree: Any, min_dim: int = 128) -> Any:
        """Tensor-parallel parameter placement over the ``model`` mesh axis.

        Every matrix leaf (``ndim >= 2``) whose output dimension divides the axis and
        is at least ``min_dim`` gets its LAST dim sharded over ``model``; everything
        else (biases, scales, small heads) is replicated.  GSPMD then propagates the
        sharding through the jitted train step: matmuls against a column-sharded kernel
        produce column-sharded activations, and the all-reduces land on ICI — no
        per-layer annotations in the model code (SURVEY §2.4's "free with GSPMD").
        With ``model=1`` (the default mesh) this is exactly ``replicate``.
        """
        mp = self.model_parallel_size
        if mp <= 1:
            return self.replicate(tree)

        def _put(x):
            if getattr(x, "ndim", 0) >= 2 and x.shape[-1] >= min_dim and x.shape[-1] % mp == 0:
                spec = [None] * (x.ndim - 1) + ["model"]
                return jax.device_put(x, self.sharding(*spec))
            return jax.device_put(x, self.replicated)

        return jax.tree.map(_put, tree)

    # -- rng ----------------------------------------------------------------
    # Keys are drawn in batches of _RNG_BATCH: jax.random.split is an eager device
    # op, so one split per key would put a dispatch on the critical path of every
    # training-loop iteration.  Amortised, the chain stays deterministic:
    # refill r of a chain yields keys split(chain_r)[1:], chain_{r+1}=split(chain_r)[0].
    _RNG_BATCH = 64

    def _draw(self, chain_attr: str, buf_attr: str, seed_fn) -> jax.Array:
        buf = getattr(self, buf_attr)
        if not buf:
            with span("Time/rng_refill"):  # an eager split and 64 eager slices, once in 64 draws
                chain = getattr(self, chain_attr)
                if chain is None:
                    chain = seed_fn()
                keys = jax.random.split(chain, self._RNG_BATCH + 1)
                setattr(self, chain_attr, keys[0])
                buf = [keys[i] for i in range(self._RNG_BATCH, 0, -1)]  # pop() keeps order
        sub = buf.pop()
        setattr(self, buf_attr, buf)
        return sub

    def rng(self) -> jax.Array:
        """Draw a fresh key off the PROCESS-IDENTICAL chain (seeded with ``seed``
        alone).  Use for parameter initialisation and jitted train-step keys: with
        replicated params, every process must feed the SPMD program the same
        replicated inputs, or the replicas diverge (and ``device_put`` with a
        replicated sharding asserts on the mismatch)."""
        return self._draw("_rng_key", "_rng_buf", lambda: jax.random.PRNGKey(self.seed))

    def local_rng(self) -> jax.Array:
        """Draw a fresh key off the PER-PROCESS chain (``seed + process_index``).
        Use for env-side action sampling and anything that should explore
        differently on each rank (the analogue of the reference's per-rank torch
        seeding)."""
        # fold_in decorrelates this chain from the shared one even on process 0
        # (a bare ``seed + process_index`` would alias the shared chain there).
        return self._draw(
            "_local_rng_key",
            "_local_rng_buf",
            lambda: jax.random.fold_in(jax.random.PRNGKey(self.seed), 0x5EED + jax.process_index()),
        )

    # -- host-object exchange (reference: TorchCollective over gloo) --------
    def broadcast_obj(self, obj: Any) -> Any:
        if jax.process_count() == 1:
            return obj
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(obj)

    def barrier(self) -> None:
        sync_global_devices_with_timeout("sheeprl_tpu_barrier")

    @contextlib.contextmanager
    def default_mesh(self):
        # Mesh is itself a context manager (the ambient mesh for shard_map/pjit).
        with self.mesh:
            yield


def make_mesh_context(cfg: Dict[str, Any]) -> MeshContext:
    """Build the MeshContext from the ``mesh`` config group (analogue of the reference's
    ``fabric`` group, ``configs/fabric/default.yaml``)."""
    mesh_cfg = cfg.get("mesh", {}) or {}
    n_devices = mesh_cfg.get("devices")
    devices = jax.devices()
    if n_devices not in (None, -1, "auto"):
        devices = devices[: int(n_devices)]
    mesh = build_mesh(
        data=mesh_cfg.get("data", -1),
        model=mesh_cfg.get("model", 1),
        sequence=mesh_cfg.get("sequence", 1),
        devices=devices,
    )
    return MeshContext(mesh=mesh, precision=mesh_cfg.get("precision", "bf16-mixed"), seed=cfg.get("seed", 42))
