"""``dv3_XL``'s plain reference, named beside the configuration's file.

The reference itself is the DreamerV3 family's, ``perfbench/reference/dreamer_v3.py``
(every size is an argument, so one file serves every DreamerV3 configuration); this
module is its name for ``dv3_XL``, which ``configs/dv3_XL.json`` gives under
``reference``.  It is here because the builder's instructions say of a configuration
that it "is its file of sizes, with its plain reference beside it", and the ledger
has listed ``dv3_XL`` under ``not_compared`` although the reference covers it
(PERF.md, section 7: what the next ledger line says decides whether this stays).
"""

from perfbench.reference.dreamer_v3 import *  # noqa: F401,F403
