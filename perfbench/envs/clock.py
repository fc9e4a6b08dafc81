"""The benchmark's clock: what the harness and a traffic generator share.

The harness times an iteration from the environment's side and reads nothing from
inside the program to do so.  What a generator (an environment class of any family,
named by its configuration's ``env.wrapper._target_``) owes the clock:

1. it registers at construction: ``clock.ENVS.append(self)``;
2. the env of rank 0 calls ``clock.HOOK(self)``, where one is set, at the start of every
   ``step()``: the harness stamps ``perf_counter()`` there, opens and closes the window,
   and ends the run by raising from it;
3. it counts its ``steps`` and the ``seconds`` of its own cost in attributes of those names.

Besides, while ``LOG_ROWS`` is true it keeps what its family's reference needs of the
rows it emitted (the harness clears the flag once the compared steps are captured),
and while ``KEEP_INTERVALS`` is true (a traced span) it leaves ``("env_step", t0, t1)``
on ``perf_counter``'s clock in ``INTERVALS``.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

#: every live env of this process, in construction order (SyncVectorEnv: index = env id)
ENVS: List[Any] = []
#: set by the harness; called by env 0 at the start of each step()
HOOK: Optional[Callable[[Any], None]] = None
#: rows are kept while this is true
LOG_ROWS = True
KEEP_INTERVALS = False
INTERVALS: List[Tuple[str, float, float]] = []


def reset_registry() -> None:
    global HOOK, LOG_ROWS, KEEP_INTERVALS
    ENVS.clear()
    INTERVALS.clear()
    HOOK = None
    LOG_ROWS = True
    KEEP_INTERVALS = False
