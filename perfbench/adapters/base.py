"""What the harness asks of a family's adapter.

A *family* is one algorithm's program path (DreamerV3's replay loop, a PPO update, ...).
The harness (``perfbench/harness.py``) owns the window, the clock, the warm-up rule,
the capture, the memory peak, the JSON line and the verdict; everything it has to
know of a family it asks of the object that the configuration's file names under
``adapter`` (``"package.module:Class"``), constructed as ``Class(sizes, seed,
reference)`` with the configuration's ``sizes`` as run, ``--seed`` and the module
its ``reference`` names.  Nothing is inherited and nothing is registered: an adapter
is any class with these members, in a file of its own, beside

* ``traffic_overrides`` in the configuration's file: the program's overrides that a
  traffic mix becomes, as templates filled from the traffic file's keys
  (``"env.num_envs={num_envs}"``) and from ``sizes`` (``"{sizes.actions}"``);
* a generator, named by the configuration's ``env.wrapper._target_`` override, that
  pays the clock its three dues (``perfbench/envs/clock.py``);
* a plain reference that shares no code with the program;
* the cell's limits, one for each number of ``check.compare`` that it holds.

The shape common to every *training* family, and so what the readings look like:
losses by name over the first three steps, per-leaf norms of the first gradient as the
optimizer got it, per-leaf norms of the parameters' change after the three
(``check.compare`` turns them into ``loss_gap.<name>``, ``grad_gap[.<group>]`` and
``change_gap``).  A *step* is whatever the family's timed call advances the state by
in one go, counted by ``grad_steps``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Protocol, Tuple


class Adapter(Protocol):
    #: gradient steps and timed calls (blocks of steps) dispatched so far; the window's
    #: ``grad_steps_per_s`` and the warm-up rule (four steps, two blocks) read them
    grad_steps: int
    blocks: int
    #: the benchmark's own host spans around calls into the program, by label: objects
    #: with ``snapshot() -> {"seconds", "calls"}``; may be empty
    spans: Dict[str, Any]
    #: ``(label, t0, t1)`` on ``perf_counter``'s clock, appended only while
    #: ``keep_intervals`` is true (the harness sets it around a capture)
    intervals: List[Tuple[str, float, float]]
    keep_intervals: bool

    def install(self) -> None:
        """Put the seams in place before ``sheeprl_tpu.cli.run``: the benchmark's weights
        from the seed, and the recorder around the timed call."""

    def uninstall(self) -> None:
        """Take them out again, whatever happened in between."""

    def captured(self) -> bool:
        """The first three steps are recorded (or cannot be: ``program_readings`` raises why)."""

    def drain(self) -> None:
        """Wait until every step dispatched so far has left the device."""

    def program_readings(self) -> Dict[str, Any]:
        """Host copies of what the program produced: ``{"steps": [{"loss": {name: x},
        "reported": {...}, ...} * 3], "grad_norms": [leaves], "change_norms": [leaves]}``."""

    def rows(self) -> Any:
        """What the generator kept of the traffic it emitted, as the reference reads it."""

    def reference_readings(self, rows: Any, program: Dict[str, Any]) -> Dict[str, Any]:
        """The plain reference following the program's three steps over ``rows``:
        ``{"loss": [{name: x} * 3], "grad_norms": [leaves], "change_norms": [leaves]}``."""

    def compared(self) -> Dict[str, Any]:
        """``{"losses": (names of ``loss`` to compare), "groups": {name: {"leaves":
        [indices], "by": "worst" | "pooled"}}}`` for ``check.compare``."""

    def coverage(self, reference: Dict[str, Any]) -> Dict[str, Any]:
        """What the compared steps exercised, for the run's log (never a verdict)."""

    def facts(self) -> Dict[str, Any]:
        """Static facts of the run for its log line (sizes of what the program reserved,
        rows kept): printed, never a metric."""
