"""Which child process holds the accelerator: decided per role, printed at spawn,
refused when over-subscribed.

A TPU chip belongs to one process at a time: a second process that initialises
JAX on it fails or hangs inside libtpu.  The process managers (the Sebulba
launcher, the serve fleet manager, the benchmark drivers) therefore never hand a
child the device by inheritance — each child's environment is built here, either
:func:`cpu_env` (``JAX_PLATFORMS=cpu``, by stated placement) or
:func:`accelerator_env` (the manager's own platform setting, optionally pinned to
one chip of a multi-chip host), and :func:`check_chip_budget` refuses a topology
that needs more chip-holding children than the host has chips.

Stdlib only, and it never imports JAX: a manager that touched JAX would hold the
chip its children need.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Mapping, Optional

PLATFORMS_ENV_VAR = "JAX_PLATFORMS"


class ChipBudgetError(RuntimeError):
    """More chip-holding children were requested than the host has chips."""


def local_chip_count() -> int:
    """TPU chips this host gives its processes, counted as the device nodes
    libtpu opens — ``/dev/accel<N>`` (up to v4) or ``/dev/vfio/<N>`` (v5e and
    newer) — without initialising any runtime.  Not the PCI bus: a VM can list
    chips there that it was not handed (observed: four v5e functions on the bus,
    one ``/dev/vfio`` group, one JAX device)."""
    return len(glob.glob("/dev/accel[0-9]*")) + len(glob.glob("/dev/vfio/[0-9]*"))


def wants_accelerator(env: Mapping[str, str]) -> bool:
    """True when a child started with ``env`` would claim an accelerator: JAX
    picks one by default, so only an explicit cpu-only ``JAX_PLATFORMS`` says no."""
    platforms = [p.strip().lower() for p in env.get(PLATFORMS_ENV_VAR, "").split(",") if p.strip()]
    return platforms != ["cpu"]


def cpu_env(env: Mapping[str, str]) -> Dict[str, str]:
    """``env`` for a child placed on the CPU backend."""
    return {**env, PLATFORMS_ENV_VAR: "cpu"}


def accelerator_env(env: Mapping[str, str], chip: Optional[int] = None) -> Dict[str, str]:
    """``env`` for a child that holds the accelerator.  The platform setting is
    the manager's own (unset = JAX's default, the TPU where there is one); with
    ``chip`` the child sees exactly that one chip of a multi-chip host (libtpu's
    own variables for it), so several one-chip children can share the host."""
    out = dict(env)
    if chip is not None:
        out["TPU_VISIBLE_CHIPS"] = str(chip)
        out["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        out["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return out


def describe(env: Mapping[str, str]) -> str:
    """One phrase for the spawn log line: where this child's JAX will run."""
    if not wants_accelerator(env):
        return "cpu (JAX_PLATFORMS=cpu)"
    setting = env.get(PLATFORMS_ENV_VAR) or "unset: JAX's default backend"
    pin = f", TPU_VISIBLE_CHIPS={env['TPU_VISIBLE_CHIPS']}" if env.get("TPU_VISIBLE_CHIPS") else ""
    return f"accelerator (JAX_PLATFORMS {setting}{pin})"


def check_chip_budget(holders: int, what: str, env: Optional[Mapping[str, str]] = None) -> int:
    """Refuse, before anything is spawned, a topology whose chip-holding children
    outnumber the host's chips.  Returns the chip count (0 on a host without
    TPUs, where JAX's default backend is the CPU and nothing can be
    over-subscribed)."""
    env = os.environ if env is None else env
    if not wants_accelerator(env):
        return 0
    chips = local_chip_count()
    if chips and holders > chips:
        raise ChipBudgetError(
            f"{what} needs {holders} chip-holding processes but this host has {chips} "
            f"TPU chip(s); a chip belongs to one process at a time. Lower the count, "
            f"or set JAX_PLATFORMS=cpu to place them all on the CPU backend."
        )
    return chips
