"""Checkpoint save/restore with end-to-end integrity.

Reference behavior (``sheeprl/utils/callback.py:14-148`` + ``cli.py:23-58``): periodic
checkpoints of model/optimizer/aux state plus optional replay-buffer state, ``keep_last``
GC, and config-compatibility rules on resume.

TPU-native design: device pytrees (params, optimizer states, moments) are serialised
with ``flax.serialization`` to msgpack; host-side python state (Ratio, counters, buffer
state dicts) is pickled alongside.  Everything lands in one directory per checkpoint so
GC is an rmtree.

Integrity model (``howto/fault_tolerance.md``): a checkpoint a resume decision rests on
must be *provably* intact —

* every file rank 0 writes is fsynced and sha256-summed into ``manifest.pkl``
  (``format: 2``); the tmp directory and its parent are fsynced around the publish
  rename, so a checkpoint either exists completely or not at all, even across a
  power cut (rename-then-crash cannot leave a half-written published dir);
* per-rank shards (written after the publish barrier by the other ranks) carry
  ``.sha256`` sidecars instead — they cannot be in rank 0's manifest;
* ``load()`` verifies checksums before deserializing and, on any damage, *falls back*
  to the newest earlier checkpoint that verifies (``Fault/checkpoint_fallbacks``
  counts the events) instead of crashing the resume on garbage bytes;
* manager init sweeps orphaned ``.tmp_ckpt_*`` dirs left by a killed writer;
* multi-host barriers time out (``SHEEPRL_TPU_BARRIER_TIMEOUT_S``) with an actionable
  error instead of hanging forever on a dead peer.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import shutil
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from flax import serialization

from sheeprl_tpu.utils.packed import Packed

PROTECTED_RESUME_KEYS = ("env", "algo", "buffer", "checkpoint", "distribution", "exp_name", "seed")

#: Manifest format written by this version: 2 = per-file sha256 checksums.
MANIFEST_FORMAT = 2


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed verification: missing/truncated/bit-flipped files or an
    unreadable manifest.  ``load(..., fallback=True)`` catches this internally and
    falls back to the newest earlier valid checkpoint; it escapes only when no
    valid checkpoint remains."""


def _is_device_tree(value: Any) -> bool:
    # Leaves must be actual arrays, not merely dtype-carrying objects: gymnasium
    # spaces expose .dtype too, and a statics dict of spaces (flight-recorder
    # dumps) must take the pickle path, not msgpack.
    leaves = jax.tree.leaves(value)
    return len(leaves) > 0 and all(isinstance(leaf, (np.ndarray, np.generic, jax.Array)) for leaf in leaves)


def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _fsync_write(path: Path, data: bytes) -> str:
    """Write ``data`` durably (flush + fsync) and return its sha256 hex digest."""
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    return _sha256(data)


def _fsync_dir(path: Path) -> None:
    """fsync a directory so the entries (and the publish rename) hit the journal."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # platforms without directory fds: best effort
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _write_rank_shard(path: Path, value: Any) -> None:
    """Per-rank shard + ``.sha256`` sidecar (these files post-date rank 0's manifest)."""
    digest = _fsync_write(path, pickle.dumps(value))
    _fsync_write(Path(str(path) + ".sha256"), digest.encode())


class CheckpointManager:
    def __init__(self, ckpt_dir: os.PathLike, keep_last: Optional[int] = 5):
        self.ckpt_dir = Path(ckpt_dir)
        self.keep_last = keep_last
        self._sweep_orphan_tmp()

    # Host-local state saved by EVERY process under a rank suffix.  The reference
    # gathers per-rank replay buffers to rank-0 over gloo (callback.py:42-51); on TPU
    # pods the shared filesystem IS the gather — each host writes its own shard and
    # reads it back on resume, with zero DCN traffic.
    PER_RANK_KEYS = ("rb",)

    def _sweep_orphan_tmp(self) -> None:
        """Remove ``.tmp_ckpt_*`` dirs orphaned by a previous killed writer.

        Safe by construction: a tmp dir is invisible to resume (only the publish
        rename makes a checkpoint real), so anything still named ``.tmp_ckpt_*``
        when a manager starts is garbage from a crashed process.  Only rank 0
        sweeps — it is the only rank that ever writes tmp dirs."""
        if not self.ckpt_dir.exists():
            return
        try:
            if jax.process_index() != 0:
                return
        except Exception:
            pass  # no backend yet: single-process by definition
        orphans = [p for p in self.ckpt_dir.iterdir() if p.is_dir() and p.name.startswith(".tmp_ckpt_")]
        for orphan in orphans:
            shutil.rmtree(orphan, ignore_errors=True)
        if orphans:
            from sheeprl_tpu.fault import counters as _fault_counters
            from sheeprl_tpu.obs import flight_recorder

            _fault_counters.bump("Fault/orphan_tmp_swept", len(orphans))
            flight_recorder.record_event(
                "orphan_tmp_swept", dir=str(self.ckpt_dir), count=len(orphans)
            )
            warnings.warn(
                f"swept {len(orphans)} orphaned .tmp_ckpt_* dir(s) in {self.ckpt_dir} "
                "(leftovers of a checkpoint writer that died mid-save)"
            )

    @staticmethod
    def _barrier(name: str) -> None:
        if jax.process_count() > 1:
            from sheeprl_tpu.parallel.mesh import sync_global_devices_with_timeout

            sync_global_devices_with_timeout(name)

    def save(self, step: int, state: Dict[str, Any], sync: bool = True) -> Path:
        """``state`` maps names to either device pytrees or picklable host objects.
        Entries named in ``PER_RANK_KEYS`` are written by every process
        (``<name>.rank<k>.pkl``); everything else by process 0 only.

        Multi-host protocol: rank 0 builds the directory and atomically renames it
        into place, a global barrier publishes it, THEN the other ranks drop their
        shards in — no writer ever races the rename.

        ``sync=False`` is the crash-dump mode (``obs/flight_recorder.py``): no
        barriers, rank 0 writes everything it has and non-zero ranks write nothing —
        a post-mortem dump must never wait on peer processes that may already be
        dead."""
        out = self.ckpt_dir / f"ckpt_{step}"
        rank = jax.process_index()
        if rank != 0 and not sync:
            return out
        if rank != 0:
            per_rank = {k: v for k, v in state.items() if k in self.PER_RANK_KEYS}
            self._barrier(f"ckpt_{step}_published")  # rank 0 has renamed tmp -> out
            for name, value in per_rank.items():
                _write_rank_shard(out / f"{name}.rank{rank}.pkl", value)
            self._barrier(f"ckpt_{step}_shards")
            return out
        tmp = self.ckpt_dir / f".tmp_ckpt_{step}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        manifest: Dict[str, str] = {}
        checksums: Dict[str, str] = {}
        for name, value in state.items():
            if name in self.PER_RANK_KEYS:
                _write_rank_shard(tmp / f"{name}.rank0.pkl", value)
                manifest[name] = "per_rank"
            elif _is_device_tree(value):
                host_value = jax.device_get(value)
                if isinstance(host_value, Packed):  # written as the tree it packs: the format on disk is the tree's
                    host_value = host_value.unpack()
                fname = f"{name}.msgpack"
                checksums[fname] = _fsync_write(tmp / fname, serialization.to_bytes(host_value))
                manifest[name] = "msgpack"
                # Template for structure restoration.
                tname = f"{name}.template.pkl"
                checksums[tname] = _fsync_write(
                    tmp / tname, pickle.dumps(jax.tree.map(lambda x: None, host_value))
                )
            else:
                fname = f"{name}.pkl"
                checksums[fname] = _fsync_write(tmp / fname, pickle.dumps(value))
                manifest[name] = "pickle"
        _fsync_write(
            tmp / "manifest.pkl",
            pickle.dumps(
                {
                    "step": step,
                    "entries": manifest,
                    "checksums": checksums,
                    "format": MANIFEST_FORMAT,
                }
            ),
        )
        _fsync_dir(tmp)  # the entries themselves
        if out.exists():
            shutil.rmtree(out)
        tmp.rename(out)
        _fsync_dir(self.ckpt_dir)  # the rename: publish survives a power cut
        if sync:
            self._barrier(f"ckpt_{step}_published")
            self._barrier(f"ckpt_{step}_shards")  # all ranks' shards are on disk
        self._gc()
        return out

    def _gc(self) -> None:
        if not self.keep_last:
            return
        ckpts = self.list_checkpoints()
        for old in ckpts[: -self.keep_last]:
            shutil.rmtree(old, ignore_errors=True)

    def list_checkpoints(self) -> List[Path]:
        if not self.ckpt_dir.exists():
            return []
        ckpts = [p for p in self.ckpt_dir.iterdir() if p.is_dir() and p.name.startswith("ckpt_")]
        return sorted(ckpts, key=lambda p: int(p.name.split("_")[1]))

    # ------------------------------------------------------------------ integrity
    @staticmethod
    def _read_manifest(ckpt_path: Path) -> Dict[str, Any]:
        try:
            with open(ckpt_path / "manifest.pkl", "rb") as f:
                manifest = pickle.load(f)
        except (OSError, pickle.UnpicklingError, EOFError, AttributeError, ValueError) as e:
            raise CheckpointCorruptError(f"{ckpt_path}: unreadable manifest.pkl: {e}") from e
        if not isinstance(manifest, dict) or "entries" not in manifest:
            raise CheckpointCorruptError(f"{ckpt_path}: malformed manifest.pkl")
        return manifest

    @classmethod
    def verify(cls, ckpt_path: os.PathLike) -> bool:
        """True iff the checkpoint's manifest reads and every checksum matches."""
        try:
            cls._verify(Path(ckpt_path))
            return True
        except CheckpointCorruptError:
            return False

    @classmethod
    def _verify(cls, ckpt_path: Path) -> Dict[str, Any]:
        """Verify and return the manifest; raises :class:`CheckpointCorruptError`.

        Legacy (format 1) manifests have no checksums — only file existence is
        checkable; the deserialization wrappers in :meth:`load` still catch their
        bit rot, just without the fallback-before-parse guarantee."""
        manifest = cls._read_manifest(ckpt_path)
        for name, kind in manifest["entries"].items():
            if kind == "msgpack":
                expected = [f"{name}.msgpack", f"{name}.template.pkl"]
            elif kind == "per_rank":
                expected = []  # rank shards verify against their sidecars below
            else:
                expected = [f"{name}.pkl"]
            for fname in expected:
                if not (ckpt_path / fname).is_file():
                    raise CheckpointCorruptError(f"{ckpt_path}: missing {fname}")
        for fname, digest in (manifest.get("checksums") or {}).items():
            fpath = ckpt_path / fname
            if not fpath.is_file():
                raise CheckpointCorruptError(f"{ckpt_path}: missing {fname}")
            if _sha256(fpath.read_bytes()) != digest:
                raise CheckpointCorruptError(f"{ckpt_path}: checksum mismatch on {fname}")
        for sidecar in ckpt_path.glob("*.rank*.pkl.sha256"):
            shard = ckpt_path / sidecar.name[: -len(".sha256")]
            if not shard.is_file():
                raise CheckpointCorruptError(f"{ckpt_path}: missing shard {shard.name}")
            if _sha256(shard.read_bytes()) != sidecar.read_text().strip():
                raise CheckpointCorruptError(f"{ckpt_path}: checksum mismatch on {shard.name}")
        return manifest

    @classmethod
    def latest_valid(cls, ckpt_dir: os.PathLike) -> Optional[Path]:
        """Newest checkpoint under ``ckpt_dir`` that verifies; None when there is none.
        The supervisor and the autoresume path use this for resume discovery."""
        ckpt_dir = Path(ckpt_dir)
        if not ckpt_dir.exists():
            return None
        ckpts = sorted(
            (p for p in ckpt_dir.iterdir() if p.is_dir() and p.name.startswith("ckpt_")),
            key=lambda p: int(p.name.split("_")[1]),
            reverse=True,
        )
        for ckpt in ckpts:
            if cls.verify(ckpt):
                return ckpt
        return None

    # ------------------------------------------------------------------ load
    @classmethod
    def load(
        cls,
        ckpt_path: os.PathLike,
        templates: Optional[Dict[str, Any]] = None,
        fallback: bool = True,
    ) -> Dict[str, Any]:
        """Load a checkpoint directory. ``templates`` provides target pytrees for
        msgpack entries (required to restore dtypes/shapes as jax arrays).

        Verifies checksums first; on corruption (or a deserialization failure) with
        ``fallback=True``, walks earlier sibling ``ckpt_*`` dirs newest-first and
        loads the first one that verifies — losing a checkpoint interval beats
        losing the run.  Raises :class:`CheckpointCorruptError` when nothing valid
        remains (or with ``fallback=False``)."""
        ckpt_path = Path(ckpt_path)
        try:
            return cls._load_one(ckpt_path, templates)
        except CheckpointCorruptError as primary:
            if not fallback:
                raise
            candidates = sorted(
                (
                    p
                    for p in ckpt_path.parent.iterdir()
                    if p.is_dir() and p.name.startswith("ckpt_") and p != ckpt_path
                ),
                key=lambda p: int(p.name.split("_")[1]),
                reverse=True,
            ) if ckpt_path.parent.exists() else []
            for candidate in candidates:
                try:
                    state = cls._load_one(candidate, templates)
                except CheckpointCorruptError:
                    continue
                from sheeprl_tpu.fault import counters as _fault_counters
                from sheeprl_tpu.obs import flight_recorder

                _fault_counters.bump("Fault/checkpoint_fallbacks")
                flight_recorder.record_event(
                    "checkpoint_fallback", corrupt=str(ckpt_path), loaded=str(candidate)
                )
                warnings.warn(
                    f"checkpoint {ckpt_path} is corrupt ({primary}); "
                    f"fell back to {candidate} (step {state['_step']})"
                )
                return state
            raise CheckpointCorruptError(
                f"{ckpt_path} is corrupt and no earlier valid checkpoint exists in "
                f"{ckpt_path.parent}"
            ) from primary

    @classmethod
    def _load_one(cls, ckpt_path: Path, templates: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        manifest = cls._verify(ckpt_path)
        state: Dict[str, Any] = {"_step": manifest["step"]}
        for name, kind in manifest["entries"].items():
            try:
                if kind == "msgpack":
                    raw = (ckpt_path / f"{name}.msgpack").read_bytes()
                    if templates and name in templates:
                        state[name] = serialization.from_bytes(templates[name], raw)
                    else:
                        state[name] = serialization.msgpack_restore(raw)
                elif kind == "per_rank":
                    # Each process restores its own shard; fall back to rank 0's when
                    # the world size changed between save and resume.
                    shard = ckpt_path / f"{name}.rank{jax.process_index()}.pkl"
                    if not shard.is_file():
                        shard = ckpt_path / f"{name}.rank0.pkl"
                    with open(shard, "rb") as f:
                        state[name] = pickle.load(f)
                else:
                    with open(ckpt_path / f"{name}.pkl", "rb") as f:
                        state[name] = pickle.load(f)
            except CheckpointCorruptError:
                raise
            except Exception as e:
                # Checksummed bytes that still fail to parse (legacy format-1 rot, or
                # a template mismatch) — surface as corruption so fallback can act.
                raise CheckpointCorruptError(
                    f"{ckpt_path}: entry {name!r} ({kind}) failed to deserialize: {e}"
                ) from e
        return state


def validate_resume_config(old_cfg: Dict[str, Any], new_cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Merge a checkpoint's config into the current one, protecting the keys the
    reference refuses to change on resume (``cli.py:48-52``)."""
    merged = dict(new_cfg)
    for key in PROTECTED_RESUME_KEYS:
        if key in old_cfg:
            merged[key] = old_cfg[key]
    return merged
