"""``tools/readings.py`` for a cell whose reference computes a state-space recurrence
(``nemotron3nano30b_1of16``): the same run and the same upper readings, and one control more.

``python3 -m perfbench.tools.ssm_readings --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Over the run's own rows, indices and keys, the reference is put in the program's place as
``tools/readings.py`` puts it (``control_fp8``, ``fault_half_batch``), and once more with
every product in float32 but the recurrence's state rounded to bfloat16 after every token
(``control_bf16_state``: what carrying the state in the compute dtype would give).  Each is
held against the cell's limits and its ``correct`` printed; ``PERF.md`` records which limits
each fails: the fp8 control and the half batch fail some, the state in bfloat16 none (the
cell holds the carried state's dtype instead, ``adapters/ssm_policy.py``).  The fault that
drops the state a Mamba block carries from one rollout into the next is the CPU tests'
(``tests/test_perfbench/test_ssm_decoder_cell.py``).
"""

from __future__ import annotations

import sys

from perfbench.tools import readings


def main(argv=None) -> int:
    readings.VARIANTS = {**readings.VARIANTS, "control_bf16_state": {"quant": "bf16_state"}}
    return readings.main(argv)


if __name__ == "__main__":
    sys.exit(main())
