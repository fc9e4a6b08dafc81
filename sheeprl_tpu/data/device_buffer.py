"""Device-resident replay mirror: keep the replay data in HBM, ship only indices.

The reference samples on the host and ships every batch to the accelerator
(``/root/reference/sheeprl/data/buffers.py`` + ``sample_tensors``).  At DreamerV3's
Atari shapes that is ~12 MB per gradient step of mostly-redundant pixels crossing
PCIe, plus the host gather and the ``device_put`` dispatch that carry them.

TPU-native answer: the replay rows live ON the device.

* every row appended to the host buffer is also scattered into a ``[capacity,
  n_envs, ...]`` device ring via a DONATED jitted update (in-place, no copy of the
  ring) — ~12 KB/env/step uplink instead of ~12 MB/grad-step;
* sampling draws only (env, start) INDEX pairs on the host (same validity logic as
  the host buffer) and gathers the ``[T, B]`` batch inside the jitted train block —
  an HBM gather at memory bandwidth, with no host work per batch;
* the host buffer stays the source of truth for checkpoint/resume; ``load_from``
  rebuilds the mirror after a resume.

**Data parallelism**: with ``mesh.data > 1`` the ring's env axis is sharded over the
``data`` mesh axis — each data shard owns a contiguous block of envs' rows.  Index
sampling is per-shard (batch element ``j`` draws only from the envs its shard owns),
so the in-jit gather is purely shard-local via ``shard_map``: no collective touches
the ring, and the gathered ``[T, B]`` batch comes out sharded over ``data`` exactly
like the host path's ``put_batch(..., batch_axis=1)`` batches.  Scatter writes are
likewise shard-local (full-env masked updates).  This is what lets the flagship fast
path compose with DP on a multi-chip host (the v4-8 north star) instead of falling
back to host sampling.

**Multi-process** (v4-32-class): each process keeps a LOCAL ring over its own
devices' slice of the ``data`` axis (scatter stays process-local and collective-free
— episode ends, and therefore terminal-row scatters, happen at process-divergent
iterations), and the SPMD train block sees a zero-copy GLOBAL view assembled with
``jax.make_array_from_single_device_arrays``.  Index arrays are likewise per-process
sampled and globalized with ``jax.make_array_from_process_local_data`` — value
divergence lives in array *shards*, which is exactly what GSPMD permits, never in
replicated scalars.  See :class:`MultiProcessDeviceReplayMirror`.

The mirror requires the whole buffer to fit in HBM next to the model: ~1.2 GB for
the 100K-transition Atari-100K config — comfortable on any current TPU.  Enabled by
``buffer.device: True`` (``buffer/default.yaml`` ships ``False``: host sampling +
the async prefetcher is what a user gets unless they opt in).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def gather_sequences(
    mirror: Dict[str, jax.Array],
    envs: jax.Array,
    starts: jax.Array,
    sequence_length: int,
    row_shapes: Dict[str, Sequence[int]],
) -> Dict[str, jax.Array]:
    """In-jit gather of ``[T, B, ...]`` sequences from ``[n_envs, cap, flat]`` rings.

    ``envs``/``starts``: ``[B]`` int32; rows wrap modulo capacity (the host-side
    index sampling guarantees wrapped sequences never cross the write cursor).
    ``row_shapes`` restores each key's logical per-row shape after the gather
    (rows are stored FLAT — see :class:`DeviceReplayMirror` for the layout
    rationale).  Inside ``shard_map`` the same code runs on the shard-local ring
    with shard-local env ids.
    """
    out = {}
    for k, buf in mirror.items():
        cap = buf.shape[1]
        t_idx = (starts[:, None] + jnp.arange(sequence_length, dtype=starts.dtype)) % cap  # [B, T]
        picked = buf[envs[:, None], t_idx]  # [B, T, flat]
        seq = jnp.swapaxes(picked, 0, 1)  # [T, B, flat]
        out[k] = seq.reshape(sequence_length, envs.shape[0], *row_shapes[k])
    return out


def _masked_row_update(
    bufs: Dict[str, jax.Array], rows: Dict[str, jax.Array], positions: jax.Array, mask: jax.Array
) -> Dict[str, jax.Array]:
    """``bufs[k][e, positions[e]] = rows[k][e]`` for every env ``e`` with
    ``mask[e]``.  Unmasked envs are skipped by aiming their update OUT OF BOUNDS
    (``mode="drop"``) — a PURE scatter, never reading the ring: a read-blend-write
    formulation defeats the donation aliasing and doubles the ring's HBM footprint
    at compile time.  One aligned update per env also keeps the scatter local to
    the env shard under ``shard_map`` — a sparse scatter over an env subset would
    make GSPMD reshard the ring."""
    out = {}
    for k, buf in bufs.items():
        cap = buf.shape[1]
        env_ar = jnp.arange(buf.shape[0], dtype=positions.dtype)
        pos = jnp.where(mask, positions, cap)  # cap = out of bounds -> dropped
        out[k] = buf.at[env_ar, pos].set(rows[k], mode="drop")
    return out


class DeviceReplayMirror:
    """Device ring mirroring an ``EnvIndependentReplayBuffer``'s rows.

    ``specs``: ``{key: (shape, dtype)}`` per-row (no leading axes).  All write
    positions are tracked by the caller (the host buffer's per-env cursors).

    **Storage layout** (TPU-critical): rows are stored FLAT and env-leading —
    ``[n_envs, capacity, prod(shape)]``.  TPU arrays are tiled on their last two
    dims ((8,128) f32 / (32,128) u8); the naive ``[cap, n_envs, C, H, W]`` layout
    pads 64-wide pixel rows 2× and ``[cap, n_envs, 1]`` scalar rings up to 256×,
    which blows a 6 GB Atari-scale ring past chip HBM at compile time.  With the
    flat layout the last two dims are ``(capacity, flat)`` — both large and
    tile-aligned, ~zero padding.  Gathers reshape back to the logical row shape
    in-jit (free).

    ``mesh``/``dp``: when ``dp > 1`` the leading env axis is sharded over the
    mesh's ``data`` axis (``n_envs % dp == 0`` required); scatter and gather run
    shard-local via ``shard_map``.
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        specs: Dict[str, Tuple[Sequence[int], Any]],
        mesh=None,
        dp: int = 1,
    ):
        self.capacity = int(capacity)
        self.n_envs = int(n_envs)
        self.specs = dict(specs)
        self.dp = int(dp) if mesh is not None else 1
        self.mesh = mesh if self.dp > 1 else None
        if self.dp > 1 and self.n_envs % self.dp != 0:
            raise ValueError(
                f"the data axis ({dp}) must divide n_envs={n_envs} for an env-sharded mirror"
            )
        self.env_sharding = NamedSharding(self.mesh, P("data")) if self.dp > 1 else None
        self._flat = {k: int(np.prod(shape)) for k, (shape, dtype) in specs.items()}
        self._row_shapes = {k: tuple(shape) for k, (shape, dtype) in specs.items()}
        # rings are placed straight into their final (possibly env-sharded) layout
        # from host zeros — building them on-device first would transiently
        # allocate the full unsharded ring on device 0
        self.arrays: Dict[str, jax.Array] = {
            k: self._device(np.zeros((self.n_envs, self.capacity, self._flat[k]), np.dtype(dtype)))
            for k, (shape, dtype) in specs.items()
        }
        self._scatter = self._make_scatter()

    def _device(self, x):
        # always commits to device: a host ndarray left in ``arrays`` would be
        # re-uploaded by every subsequent jitted dispatch
        return jax.device_put(x, self.env_sharding) if self.env_sharding is not None else jax.device_put(x)

    def _make_scatter(self):
        if self.dp <= 1:
            return jax.jit(_masked_row_update, donate_argnums=(0,))
        fn = jax.shard_map(
            _masked_row_update,
            mesh=self.mesh,
            in_specs=(P("data"), P("data"), P("data"), P("data")),
            out_specs=P("data"),
        )
        return jax.jit(fn, donate_argnums=(0,))

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in self.arrays.values())

    def add(self, data: Dict[str, np.ndarray], envs: Sequence[int], positions: Sequence[int]) -> None:
        """Scatter one row per selected env: ``data[k]`` is ``[1, len(envs), ...]``
        (the loops' step_data layout); ``positions[i]`` is env ``envs[i]``'s write
        cursor BEFORE the host add.  The update ships a full ``[n_envs]``-aligned
        row block with a write mask (static shapes, shard-local under dp>1);
        unselected envs are masked no-ops.  Shipping the full block costs host
        memcpy + uplink for every env even on subset writes — the right trade at
        current ``n_envs`` (one static scatter program); a compacted per-bucket
        scatter only pays off if ``n_envs`` grows well past the env-farm sizes
        the presets use."""
        env_sel = np.asarray(envs, np.intp)
        mask = np.zeros(self.n_envs, bool)
        mask[env_sel] = True
        pos_arr = np.zeros(self.n_envs, np.int32)
        pos_arr[env_sel] = np.asarray(positions, np.int64) % self.capacity
        row_tree = {}
        for k in self.arrays:
            _, dtype = self.specs[k]
            rows = np.zeros((self.n_envs, self._flat[k]), dtype)
            rows[env_sel] = np.asarray(data[k])[0].reshape(len(env_sel), self._flat[k])
            row_tree[k] = rows
        self.arrays = self._scatter(self.arrays, row_tree, pos_arr, mask)

    def load_from(self, host_rb) -> None:
        """Rebuild the mirror from an ``EnvIndependentReplayBuffer`` (resume path):
        one bulk transfer per key, placed with the mirror's sharding."""
        for k in self.arrays:
            host = np.zeros(self.arrays[k].shape, self.specs[k][1])
            for e, sub in enumerate(host_rb.buffer):
                arr = np.asarray(sub._buf[k])  # [cap, 1, ...]
                rows = min(arr.shape[0], self.capacity)
                host[e, :rows] = arr[:rows, 0].reshape(rows, self._flat[k])
            self.arrays[k] = self._device(host)

    def load_from_dense(self, host_arrays: Dict[str, np.ndarray]) -> None:
        """Rebuild from dense ``[cap, n_envs, ...]`` host arrays — the resume path
        for loops built on the plain :class:`~sheeprl_tpu.data.buffers.ReplayBuffer`
        (SAC-AE), whose storage is already mirror-shaped."""
        for k in self.arrays:
            src = np.asarray(host_arrays[k])
            rows = min(src.shape[0], self.capacity)
            host = np.zeros(self.arrays[k].shape, self.specs[k][1])
            host[:, :rows] = np.moveaxis(src[:rows].reshape(rows, self.n_envs, self._flat[k]), 0, 1)
            self.arrays[k] = self._device(host)

    def make_gather_fn(self, sequence_length: int, out_sharding=None):
        """The in-jit batch gather for :class:`~sheeprl_tpu.utils.blocks.
        IndexedBlockDispatcher`.  ``dp > 1``: shard-local gather via ``shard_map``
        — batch element ``j`` lives on the shard owning env ``envs[j]`` (the
        sharded sampler guarantees the alignment), and global env ids reduce to
        local ones by ``% E_local`` because each shard owns a contiguous env
        block.  Output ``[T, B, ...]`` is sharded over ``data`` on the batch axis,
        identical to the host path's ``put_batch(..., batch_axis=1)``.

        ``out_sharding``: optional ``[T, B, ...]`` batch sharding of the CONSUMING
        train step, applied to every gathered leaf via ``with_sharding_constraint``.
        Needed when the gather mesh is not the training mesh (e.g. the pure-DP
        mirror mesh feeding a DP×TP train step): the gathered obs batch otherwise
        carries the mirror's sharding into the train program as a constant, and
        GSPMD only discovers the mismatch deep inside the BACKWARD pass (the obs
        target of the reconstruction loss), where it logs an `[SPMD] Involuntary
        full rematerialization` and replicates the tensor as a last resort.  An
        explicit constraint at the gather boundary turns that into one clean
        forward reshard instead."""
        shapes = self._row_shapes
        gather_mesh = self._gather_mesh()

        def constrain(tree):
            if out_sharding is None:
                return tree
            return jax.tree.map(lambda x: jax.lax.with_sharding_constraint(x, out_sharding), tree)

        if gather_mesh is None:
            return lambda m, e, s: constrain(gather_sequences(m, e, s, sequence_length, row_shapes=shapes))
        # envs per shard — same count locally and globally (contiguous env blocks),
        # so global env ids reduce to shard-local rows by the same modulus.
        e_local = self.n_envs // max(self.dp, 1)

        def local_gather(mirror, envs, starts):
            return gather_sequences(mirror, envs % e_local, starts, sequence_length, row_shapes=shapes)

        sharded_gather = jax.shard_map(
            local_gather,
            mesh=gather_mesh,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=P(None, "data"),
        )
        return lambda m, e, s: constrain(sharded_gather(m, e, s))

    def _gather_mesh(self):
        """Mesh the batch gather shard_maps over (None = unsharded single-device
        gather).  The multi-process subclass returns the GLOBAL mesh here while
        scatters stay on the local one."""
        return self.mesh if self.dp > 1 else None

    def make_transition_gather_fn(self):
        """In-jit ``[n, B]`` transition-row gather (SAC-AE's batch shape): returns
        ``closure(mirror_arrays, idxs, envs) -> {key: [n, B, *row_shape]}``.
        Single-chip (the transition mirror is not sharded)."""
        shapes = self._row_shapes

        def gather(mirror, idxs, envs):
            out = {}
            for k, buf in mirror.items():
                picked = buf[envs, idxs]  # [n, B, flat]
                out[k] = picked.reshape(*idxs.shape, *shapes[k])
            return out

        return gather

    def host_rows(self, key: str) -> np.ndarray:
        """Fetch ring ``key`` as ``[cap, n_envs, *row_shape]`` numpy (test/debug
        accessor for the logical layout)."""
        arr = np.asarray(jax.device_get(self.arrays[key]))  # [n_envs, cap, flat]
        return np.moveaxis(arr, 0, 1).reshape(self.capacity, self.n_envs, *self._row_shapes[key])


STAMP_KEY = "_stamp"

#: ring keys eligible for reduced-precision storage (buffer.store_dtype): the
#: wide observation planes.  Actions/rewards/dones are a rounding error of the
#: ring's HBM footprint and stay at their declared dtype.
STORE_DTYPE_KEYS = ("obs", "next_obs")


def resolve_store_dtype(spec) -> Optional[Any]:
    """Map ``buffer.store_dtype`` (``null`` | ``f32`` | ``bf16``) to a dtype, or
    ``None`` for full-precision storage."""
    if spec is None:
        return None
    key = str(spec).lower()
    if key in ("", "none", "null", "f32", "fp32", "float32"):
        return None
    if key in ("bf16", "bfloat16"):
        return jnp.bfloat16
    raise ValueError(f"Unknown buffer.store_dtype {spec!r}; expected null, f32 or bf16")


class DeviceTransitionRing(DeviceReplayMirror):
    """Device-resident uniform-replay ring for FLAT transition batches — the SAC
    family's (sac / sac_decoupled / sac_ae / droq) analogue of the Dreamer loops'
    sequence mirror.

    Differences from the base mirror:

    * rows are whole transitions (obs / next_obs / action / reward / done), so
      sampling is a ``[B]`` row gather, not a ``[T, B]`` sequence gather;
    * index sampling happens **inside the jit** from the train block's carried PRNG
      key (:meth:`sample_indices` / :meth:`make_sample_gather`) — the host ships
      only the ``filled`` row count, so a whole UTD block of gradient steps runs as
      ONE dispatch with zero per-step host work;
    * every scatter also stamps the written rows with the buffer's cumulative
      added-row counter (``STAMP_KEY`` ring), so ``Health/replay_age_{mean,max}``
      are computed in-jit and ride the block's metrics pytree — the host-side
      ``sample_age_metrics`` path never runs on the device path.

    Single-chip by design (the flat ring is not ``shard_map``'d); the shared
    ``device_replay_enabled(..., allow_dp=False)`` gate falls back to host sampling
    under data parallelism or multi-process meshes.

    ``store_dtype`` (``buffer.store_dtype``): optional reduced-precision storage
    for the float observation planes (``obs``/``next_obs``) — bf16 halves the
    ring's HBM footprint; sampled batches cast back to the declared dtype
    INSIDE the jit (one fused convert on the gathered rows, not on the ring).
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        specs: Dict[str, Tuple[Sequence[int], Any]],
        store_dtype: Optional[Any] = None,
    ):
        specs = dict(specs)
        if STAMP_KEY in specs:
            raise ValueError(f"spec key {STAMP_KEY!r} is reserved for the ring's write stamps")
        self._batch_keys = tuple(specs)
        # Sampled batches come back at the key's DECLARED dtype; only the ring
        # storage (and the scan writer's cast) uses store_dtype.
        self._sample_cast: Dict[str, Any] = {}
        if store_dtype is not None:
            for k in STORE_DTYPE_KEYS:
                if k in specs and jnp.issubdtype(jnp.dtype(specs[k][1]), jnp.floating):
                    self._sample_cast[k] = specs[k][1]
                    specs[k] = (specs[k][0], store_dtype)
        self.store_dtype = store_dtype
        specs[STAMP_KEY] = ((1,), jnp.int32)
        super().__init__(capacity, n_envs, specs)

    def add_step(self, data: Dict[str, np.ndarray], position: int, rows_added: int) -> None:
        """Scatter one transition row for EVERY env at ring slot ``position`` (the
        host buffer's write cursor BEFORE its own add), donated in-place.
        ``data[k]`` is ``[1, n_envs, ...]`` (the loops' step_data layout);
        ``rows_added`` is the host buffer's cumulative added-row counter BEFORE the
        add — it becomes the written rows' staleness stamp."""
        pos = np.full(self.n_envs, int(position) % self.capacity, np.int32)
        mask = np.ones(self.n_envs, bool)
        rows = {}
        for k in self._batch_keys:
            rows[k] = np.ascontiguousarray(
                np.asarray(data[k])[0].reshape(self.n_envs, self._flat[k]),
                dtype=np.dtype(self.specs[k][1]),
            )
        rows[STAMP_KEY] = np.full((self.n_envs, 1), int(rows_added), np.int32)
        self.arrays = self._scatter(self.arrays, rows, pos, mask)

    def load_from_transitions(self, host_arrays: Dict[str, np.ndarray], stamps: Optional[np.ndarray] = None) -> None:
        """Rebuild from dense ``[cap, n_envs, ...]`` host arrays (resume path:
        the ``ReplayBuffer`` storage is already ring-shaped).  ``stamps`` is the
        host buffer's per-row stamp vector (``ReplayBuffer.row_stamps``), shared
        across envs — restores sensible ``Health/replay_age_*`` after a resume."""
        for k in self._batch_keys:
            src = np.asarray(host_arrays[k])
            rows = min(src.shape[0], self.capacity)
            host = np.zeros(self.arrays[k].shape, self.specs[k][1])
            host[:, :rows] = np.moveaxis(src[:rows].reshape(rows, self.n_envs, self._flat[k]), 0, 1)
            self.arrays[k] = self._device(host)
        st = np.zeros(self.arrays[STAMP_KEY].shape, np.int32)
        if stamps is not None:
            rows = min(len(stamps), self.capacity)
            st[:, :rows, 0] = np.asarray(stamps[:rows], np.int64)
        self.arrays[STAMP_KEY] = self._device(st)

    def population_arrays(self, size: int) -> Dict[str, jax.Array]:
        """Fresh ring arrays with a LEADING MEMBER AXIS — ``[size, n_envs, cap,
        flat]`` zeros per key — for the population Anakin engine
        (``engine/population.py``): K independent members' replay rings carried
        through one fused scan.  Built directly at the stacked shape (stacking
        K copies of ``self.arrays`` would transiently allocate K extra rings).
        :meth:`make_scan_writer` / :meth:`make_sample_gather` operate on one
        member's slice, so the engine's member transform (``lax.map`` /
        ``vmap``) applies them across the axis unchanged."""
        return {
            k: self._device(
                np.zeros((int(size), self.n_envs, self.capacity, self._flat[k]), np.dtype(self.specs[k][1]))
            )
            for k in self.arrays
        }

    def make_scan_writer(self):
        """Pure in-scan analogue of :meth:`add_step`, for loops that carry the ring
        arrays THROUGH a fused scan instead of scattering from host (the Anakin
        engine, ``sheeprl_tpu/engine/anakin.py``): ``write(arrays, rows,
        rows_added) -> arrays`` writes one transition row for every env at the
        (traced) slot ``rows_added % capacity`` and stamps the rows with
        ``rows_added`` so ``Health/replay_age_*`` keep working off the same
        :meth:`make_sample_gather`.  ``rows[k]`` is ``[n_envs, *row_shape]``;
        ``rows_added`` is the cumulative added-row counter BEFORE the write."""
        batch_keys = self._batch_keys
        flat = self._flat
        specs = self.specs
        cap = self.capacity
        n_envs = self.n_envs

        def write(arrays, rows, rows_added):
            pos = jnp.mod(jnp.asarray(rows_added, jnp.int32), cap)
            out = dict(arrays)
            for k in batch_keys:
                row = rows[k].reshape(n_envs, flat[k]).astype(specs[k][1])
                out[k] = arrays[k].at[:, pos].set(row)
            stamp = jnp.full((n_envs, 1), 0, jnp.int32) + jnp.asarray(rows_added, jnp.int32)
            out[STAMP_KEY] = arrays[STAMP_KEY].at[:, pos].set(stamp)
            return out

        return write

    def sample_indices(self, filled, key, batch_size: int):
        """The exact in-jit uniform index draw the fused train blocks run: ``[B]``
        (env, row) int32 pairs, rows uniform over ``[0, filled)`` and envs uniform
        over ``[0, n_envs)`` — the same distribution as the host buffer's
        ``sample()`` (jittable; deterministic under a fixed key)."""
        k_row, k_env = jax.random.split(key)
        rows = jax.random.randint(k_row, (batch_size,), 0, jnp.maximum(filled, 1), dtype=jnp.int32)
        envs = jax.random.randint(k_env, (batch_size,), 0, self.n_envs, dtype=jnp.int32)
        return envs, rows

    def make_sample_gather(self, batch_size: int):
        """``closure(arrays, filled, rows_added, key) -> (batch, age_metrics)``:
        in-jit uniform sampling + HBM row gather + staleness stats, for use inside
        a scanned train block.  ``batch[k]`` is ``[B, *row_shape]``."""
        shapes = {k: self._row_shapes[k] for k in self._batch_keys}
        batch_keys = self._batch_keys
        sample_cast = dict(self._sample_cast)

        def sample_gather(arrays, filled, rows_added, key):
            envs, rows = self.sample_indices(filled, key, batch_size)
            batch = {}
            for k in batch_keys:
                picked = arrays[k][envs, rows]  # [B, flat]
                if k in sample_cast:  # store_dtype plane: cast the BATCH, not the ring
                    picked = picked.astype(sample_cast[k])
                batch[k] = picked.reshape(batch_size, *shapes[k])
            ages = (rows_added - 1) - arrays[STAMP_KEY][envs, rows, 0]
            age_metrics = {
                "Health/replay_age_mean": jnp.mean(ages).astype(jnp.float32),
                "Health/replay_age_max": jnp.max(ages).astype(jnp.float32),
            }
            return batch, age_metrics

        return sample_gather


def make_transition_ring(ctx, cfg, rb, specs: Dict[str, Tuple[Sequence[int], Any]]):
    """The SAC family's ``buffer.device`` wiring: a :class:`DeviceTransitionRing`
    when the shared gate admits it (single chip, no DP), else ``None`` (the loops
    then keep host sampling + the async prefetcher)."""
    if not device_replay_enabled(ctx, cfg, allow_dp=False):
        return None
    return DeviceTransitionRing(
        rb.buffer_size, rb.n_envs, specs, store_dtype=resolve_store_dtype(cfg.buffer.get("store_dtype"))
    )


def _data_axis_devices(mesh) -> list:
    """Devices along the mesh's ``data`` axis, in axis order (requires the pure-DP
    topology the multi-process mirror supports: ``model == sequence == 1``)."""
    return list(mesh.devices.reshape(-1))


def _local_data_block(mesh):
    """This process's contiguous block of the global ``data`` axis, or ``None`` if
    its devices are not contiguous/aligned (the mirror then cannot map its env block
    onto the axis).  Returns ``(local_devices_in_axis_order, block_start)``."""
    devs = _data_axis_devices(mesh)
    me = jax.process_index()
    idxs = [i for i, d in enumerate(devs) if d.process_index == me]
    if not idxs or idxs != list(range(idxs[0], idxs[0] + len(idxs))):
        return None
    return [devs[i] for i in idxs], idxs[0]


class MultiProcessDeviceReplayMirror(DeviceReplayMirror):
    """Per-process LOCAL ring + zero-copy GLOBAL view for multi-process (multi-host)
    data parallelism.

    Design constraints this satisfies (why the r4 gate existed):

    * **Scatters must not be collective.**  Terminal-row adds fire when an episode
      ends — at different iterations on different processes.  A global SPMD scatter
      would deadlock; here every scatter runs on the process's OWN devices only
      (the base class, over a local ``data`` submesh), so processes scatter freely.
    * **The train block must stay SPMD.**  All processes dispatch the same jitted
      block in lockstep (gradient counts derive from the global policy-step count).
      Its replay inputs carry the per-process divergence as array SHARDS: the ring
      is re-exposed per dispatch as a global ``[world×n_envs, cap, flat]`` array via
      ``jax.make_array_from_single_device_arrays`` (metadata only — no copy, the
      shards ARE the local ring's buffers), and the per-process sampled index
      arrays become batch-sharded global arrays via
      ``jax.make_array_from_process_local_data``.
    * **Gathers never cross processes.**  Batch element ``j`` samples only from the
      env block its shard owns (``sample_index_block`` per-shard sampling +
      rank-offset ids), so the global-mesh ``shard_map`` gather is shard-local —
      identical math to the single-process DP path, just over the global mesh.

    In-place safety: a dispatch's global view references the same HBM buffers the
    next iteration's (donating) scatter overwrites — safe for the same reason the
    single-process path is: per-device program queues execute in dispatch order.
    """

    def __init__(self, capacity: int, n_envs_local: int, specs, global_mesh):
        shape = dict(global_mesh.shape)
        if shape.get("model", 1) > 1 or shape.get("sequence", 1) > 1:
            raise ValueError(
                "MultiProcessDeviceReplayMirror supports pure data parallelism only "
                f"(got mesh {dict(global_mesh.shape)}) — the env ring has no model/"
                "sequence dimension to shard over"
            )
        block = _local_data_block(global_mesh)
        if block is None:
            raise ValueError("process's devices are not a contiguous block of the data axis")
        local_devs, block_start = block
        k = len(local_devs)
        self._global_mesh = global_mesh
        self._world = jax.process_count()
        self._block_start = block_start
        local_mesh = (
            jax.sharding.Mesh(np.asarray(local_devs).reshape(k), axis_names=("data",)) if k > 1 else None
        )
        super().__init__(capacity, n_envs_local, specs, mesh=local_mesh, dp=k)
        self.local_dp = k
        # Global env ids must follow the DATA-AXIS position of this process's
        # device block, not its process index: global_view() places rows by
        # device, so if the axis were not process-ordered, a process_index-based
        # offset would silently gather other processes' rows.
        self.env_offset = block_start * (n_envs_local // k)
        self._view_shardings = {
            key: NamedSharding(global_mesh, P("data", None, None)) for key in specs
        }
        self._index_sharding = NamedSharding(global_mesh, P(None, "data"))

    @property
    def global_envs(self) -> int:
        return self.n_envs * self._world

    def _gather_mesh(self):
        return self._global_mesh

    def global_view(self) -> Dict[str, jax.Array]:
        """The SPMD train block's ring input: global env-sharded arrays whose shards
        are the CURRENT local ring buffers (metadata-only assembly, per dispatch)."""
        out = {}
        for k, arr in self.arrays.items():
            shards = [s.data for s in arr.addressable_shards]
            out[k] = jax.make_array_from_single_device_arrays(
                (self.global_envs, self.capacity, self._flat[k]), self._view_shardings[k], shards
            )
        return out

    def globalize_indices(self, envs: np.ndarray, starts: np.ndarray):
        """Per-process ``[G, B_local]`` int32 index blocks (LOCAL env ids) → global
        ``[G, world×B_local]`` batch-sharded arrays with global env ids."""
        genvs = np.ascontiguousarray(envs + self.env_offset, np.int32)
        gstarts = np.ascontiguousarray(starts, np.int32)
        g, b_local = genvs.shape
        shape = (g, b_local * self._world)
        return (
            jax.make_array_from_process_local_data(self._index_sharding, genvs, shape),
            jax.make_array_from_process_local_data(self._index_sharding, gstarts, shape),
        )


def device_replay_enabled(ctx, cfg, require_sequential: bool = False, allow_dp: bool = True) -> bool:
    """The ``buffer.device`` gate shared by every device-replay consumer.  Every
    fallback logs why, so a requested device buffer never degrades silently.
    Requirements:

    * for DV2, sequential buffers only (the episode buffer stays on host);
    * under data parallelism, ``num_envs`` and the batch size must divide the
      (per-process) ``data`` axis so the env-sharded ring and the per-shard
      sampler line up — or, for loops whose mirror is not sharded
      (``allow_dp=False``, SAC-AE's transition mirror), any ``data > 1`` or
      multi-process topology falls back;
    * multi-process additionally needs a pure-DP mesh (``model == sequence == 1``)
      with each process's devices a contiguous block of the ``data`` axis — the
      :class:`MultiProcessDeviceReplayMirror` topology.
    """
    import logging

    if not bool(cfg.buffer.get("device", False)):
        return False
    log = logging.getLogger(__name__)
    if require_sequential and str(cfg.buffer.get("type", "sequential")).lower() != "sequential":
        log.warning(
            "buffer.device=True supports only buffer.type=sequential (the episode "
            "buffer stays on host); falling back to host sampling."
        )
        return False
    world = jax.process_count()
    if not allow_dp and (ctx.data_parallel_size > 1 or world > 1):
        log.warning(
            "buffer.device=True is single-chip for this algorithm (its mirror is "
            "not sharded); falling back to host-side sampling with the async "
            "prefetcher."
        )
        return False
    if world > 1:
        if ctx.mesh.shape["model"] > 1 or ctx.mesh.shape["sequence"] > 1:
            log.warning(
                "buffer.device=True over multiple processes supports pure data "
                "parallelism only (mesh.model = mesh.sequence = 1); falling back "
                "to host-side sampling."
            )
            return False
        block = _local_data_block(ctx.mesh)
        if block is None:
            log.warning(
                "buffer.device=True needs each process's devices to form a "
                "contiguous block of the data axis; falling back to host-side "
                "sampling."
            )
            return False
        k = len(block[0])
        if cfg.env.num_envs % k != 0 or cfg.algo.per_rank_batch_size % k != 0:
            log.warning(
                "buffer.device=True with %d local devices on the data axis needs "
                "env.num_envs (%d) and algo.per_rank_batch_size (%d) divisible by "
                "it; falling back to host-side sampling.",
                k,
                cfg.env.num_envs,
                cfg.algo.per_rank_batch_size,
            )
            return False
        return True
    dp = ctx.data_parallel_size
    if dp > 1 and (cfg.env.num_envs % dp != 0 or cfg.algo.per_rank_batch_size % dp != 0):
        log.warning(
            "buffer.device=True with mesh.data=%d needs env.num_envs (%d) and "
            "algo.per_rank_batch_size (%d) to divide the data axis; falling back "
            "to host-side sampling.",
            dp,
            cfg.env.num_envs,
            cfg.algo.per_rank_batch_size,
        )
        return False
    return True


def make_rb_add(mirror: Optional[DeviceReplayMirror], rb, rb_lock, num_envs: int):
    """The loops' row-append: host add + device-mirror scatter at each target env's
    pre-add cursor.  The env-subset argument is passed POSITIONALLY — the
    EnvIndependentReplayBuffer and EpisodeBuffer name it differently."""

    def rb_add(data, indices=None, validate_args=False):
        if mirror is not None:
            envs_sel = list(indices) if indices is not None else list(range(num_envs))
            positions = [rb.buffer[e]._pos for e in envs_sel]
            mirror.add(data, envs_sel, positions)
        with rb_lock:
            rb.add(data, indices, validate_args=validate_args)

    return rb_add


def sample_index_block(rb, batch_size: int, sequence_length: int, n: int, dp: int = 1):
    """``n`` gradient steps' worth of (env, start) index pairs as ``[n, B]`` arrays
    for :class:`~sheeprl_tpu.utils.blocks.IndexedBlockDispatcher`.

    ``dp > 1``: the batch is drawn per data shard — element ``j`` (in shard
    ``j // (B//dp)``) samples only from the env block that shard owns, so the
    sharded gather never crosses shards.

    Per-shard sampleability is guaranteed by the prefill gate (``cli.py``
    ``check_configs``: learning_starts must leave EVERY env's sub-buffer a full
    sequence) plus the loops' write pattern (every env appends a row every
    iteration; done-index adds only append EXTRA rows) — so no shard's env block
    can hold fewer rows than the gate checked, including after a resume.
    """
    if dp <= 1:
        idx = [rb.sample_idx(batch_size, sequence_length) for _ in range(n)]
        return np.stack([e for e, _ in idx]), np.stack([s for _, s in idx])
    if batch_size % dp != 0 or rb.n_envs % dp != 0:
        # device_replay_enabled guards the training loops; direct callers (dryrun,
        # tests) must fail loudly rather than leave np.empty tails as garbage ids.
        raise ValueError(
            f"sharded index sampling needs batch_size ({batch_size}) and n_envs "
            f"({rb.n_envs}) divisible by dp ({dp})"
        )
    e_local = rb.n_envs // dp
    b_local = batch_size // dp
    envs = np.empty((n, batch_size), np.intp)
    starts = np.empty((n, batch_size), np.intp)
    for g in range(n):
        for s in range(dp):
            e, st = rb.sample_idx(b_local, sequence_length, env_range=range(s * e_local, (s + 1) * e_local))
            envs[g, s * b_local : (s + 1) * b_local] = e
            starts[g, s * b_local : (s + 1) * b_local] = st
    return envs, starts


def _algo_name(cfg) -> str:
    """Best-effort ``cfg.algo.name`` for perf cost-model registration keys."""
    try:
        return str(cfg.algo.name)
    except Exception:
        return "train"


def make_device_replay(
    ctx,
    cfg,
    rb,
    cnn_keys,
    mlp_keys,
    obs_space,
    act_dim_sum: int,
    step_fn,
    dispatcher_kwargs: Optional[dict] = None,
    require_sequential: bool = False,
):
    """One-stop wiring for the Dreamer-family loops — the single implementation of
    the device-vs-host replay data path.

    Returns ``(dispatcher, mirror, prefetcher, run_block, rb_add)``:

    * device path (``buffer.device=True``, single process): an
      :class:`~sheeprl_tpu.utils.blocks.IndexedBlockDispatcher` gathering from the
      HBM mirror in-jit (env-sharded over ``data`` when ``mesh.data > 1``), fed
      index-only sampling; no prefetcher;
    * host path: a :class:`~sheeprl_tpu.utils.blocks.BlockDispatcher` fed by the
      async double-buffered prefetcher.

    ``run_block(carry, n, start_count, stage_next=True)`` runs one iteration's
    ``n``-step gradient block through whichever path is active and returns the new
    carry — the ONE place the mirror-vs-host dispatch logic lives (the loops just
    call it).

    ``step_fn``/``dispatcher_kwargs`` are the loop's per-step train closure and its
    cadence options (``target_update_freq``, ``count_offset``); call AFTER the
    replay buffer exists, and call ``mirror.load_from(rb)`` after a resume restores
    the host buffer.
    """
    import contextlib

    from sheeprl_tpu.data.prefetch import make_replay_prefetcher
    from sheeprl_tpu.obs import flight_recorder
    from sheeprl_tpu.obs import perf as obs_perf
    from sheeprl_tpu.utils.blocks import BlockDispatcher, IndexedBlockDispatcher
    from sheeprl_tpu.utils.timer import timer

    kwargs = dict(dispatcher_kwargs or {})
    kwargs.setdefault("base_key", ctx.rng())
    batch_size = cfg.algo.per_rank_batch_size
    seq_len = cfg.algo.per_rank_sequence_length

    # Flight recorder (obs/flight_recorder.py): every dispatched gradient block
    # stages its inputs (device-array references — no sync, no copy) so a crash
    # dumps the offending block.  The algorithm's main() registers the replay
    # target; the block cadence needed to re-execute it exactly is recorded here.
    recorder = flight_recorder.get_active()
    base_key = kwargs["base_key"]
    if recorder is not None:
        recorder.arm_replay(
            None,
            block_kwargs={
                "target_update_freq": int(kwargs.get("target_update_freq", 1)),
                "count_offset": int(kwargs.get("count_offset", 1)),
                "max_chunk": int(kwargs.get("max_chunk", 8)),
            },
        )

    if device_replay_enabled(ctx, cfg, require_sequential=require_sequential):
        mirror = make_mirror_for(
            rb,
            cnn_keys,
            mlp_keys,
            obs_space,
            [("actions", act_dim_sum), ("rewards", 1), ("terminated", 1), ("truncated", 1), ("is_first", 1)],
            ctx=ctx,
        )
        multiprocess = isinstance(mirror, MultiProcessDeviceReplayMirror)
        # Pin the gathered batch to the TRAIN mesh's batch sharding: when the
        # mirror's (pure-DP) mesh differs from the training mesh, the reshard
        # happens once at the gather boundary instead of as an involuntary full
        # rematerialization inside the backward pass (see make_gather_fn).
        dispatcher = IndexedBlockDispatcher(
            step_fn,
            gather_fn=mirror.make_gather_fn(seq_len, out_sharding=ctx.sharding(None, "data")),
            globalize=mirror.globalize_indices if multiprocess else None,
            **kwargs,
        )
        dispatcher._block = obs_perf.instrument(cfg, f"{_algo_name(cfg)}/train_block", dispatcher._block)
        prefetcher, rb_lock = None, contextlib.nullcontext()
        dp = mirror.local_dp if multiprocess else mirror.dp

        # The three parts of a block's host time are spans of their own (children of
        # the loop's Time/phase_dispatch): sample, stage, and the jitted call(s)
        # (Time/dispatch_call, in the dispatcher).
        def run_block(carry, n: int, start_count: int, stage_next: bool = True):
            with timer("Time/dispatch_sample"):
                envs_idx, starts_idx = sample_index_block(rb, batch_size, seq_len, n, dp=dp)
            if recorder is not None:
                # Mirror rings are donated per scatter, so row references cannot
                # outlive the dispatch: stage the sampled indices (the dump then
                # carries state + indices; the batch is reconstructible from the
                # host buffer, which stays the source of truth).
                with timer("Time/dispatch_stage"):
                    recorder.stage_step(
                        carry=carry,
                        base_key=base_key,
                        scalars={
                            "start_count": int(start_count),
                            "n_steps": int(n),
                            "envs_idx": np.asarray(envs_idx).tolist(),
                            "starts_idx": np.asarray(starts_idx).tolist(),
                        },
                    )
            arrays = mirror.global_view() if multiprocess else mirror.arrays
            return dispatcher.dispatch(carry, arrays, envs_idx, starts_idx, start_count)

    else:
        mirror = None
        dispatcher = BlockDispatcher(step_fn, **kwargs)
        dispatcher._block = obs_perf.instrument(cfg, f"{_algo_name(cfg)}/train_block", dispatcher._block)
        prefetcher, rb_lock, sample_block = make_replay_prefetcher(rb, ctx, cfg, batch_size, seq_len)

        def run_block(carry, n: int, start_count: int, stage_next: bool = True):
            with timer("Time/dispatch_sample"):
                sample = prefetcher.get(n, stage_next=stage_next) if prefetcher is not None else sample_block(n)
            if recorder is not None:  # device-array references only: no host sync
                with timer("Time/dispatch_stage"):
                    recorder.stage_step(
                        batches=sample,
                        carry=carry,
                        base_key=base_key,
                        scalars={"start_count": int(start_count), "n_steps": len(sample)},
                    )
            return dispatcher.dispatch(carry, sample, start_count)

    # rb_lock stays internal: rb_add (below) and the prefetcher's sampler are the
    # only buffer accessors, so the loops never need to lock rb themselves.
    rb_add = make_rb_add(mirror, rb, rb_lock, rb.n_envs)
    return dispatcher, mirror, prefetcher, run_block, rb_add


def make_mirror_for(rb, cnn_keys, mlp_keys, obs_space, extra_float_keys, ctx=None) -> DeviceReplayMirror:
    """Build a mirror matching the Dreamer loops' row layout (``_obs_row``): pixel
    keys are stored ``[C_total, H, W]`` uint8 (decoded to float on device inside
    the train step), vector keys flat float32, scalar keys float32 ``[dim]``.
    With a ``ctx`` whose mesh has ``data > 1``, the ring is env-sharded over it."""
    specs: Dict[str, Tuple[Sequence[int], Any]] = {}
    for k in cnn_keys:
        shape = obs_space[k].shape
        specs[k] = ((int(np.prod(shape[:-2])), *shape[-2:]), jnp.uint8)
    for k in mlp_keys:
        specs[k] = ((int(np.prod(obs_space[k].shape)),), jnp.float32)
    for k, dim in extra_float_keys:
        specs[k] = ((int(dim),), jnp.float32)
    if ctx is not None and jax.process_count() > 1:
        return MultiProcessDeviceReplayMirror(rb.buffer_size, rb.n_envs, specs, global_mesh=ctx.mesh)
    mesh = ctx.mesh if ctx is not None and ctx.data_parallel_size > 1 else None
    dp = ctx.data_parallel_size if ctx is not None else 1
    return DeviceReplayMirror(rb.buffer_size, rb.n_envs, specs, mesh=mesh, dp=dp)
