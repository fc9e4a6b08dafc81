"""``sequence_policy.SequencePolicyAdapter`` for a decoder policy whose update reports how
much of its latent caches its attention visited (``moonlight16b_1of8``): the same seams, the
same comparison, and one thing kept more.  While the capture runs, what each update
dispatched in it reported of itself is kept (``capture_reports``: the update's own metrics,
references to device scalars, fetched by the one reader that wants them), so that
``readers/latent_decoder.py::latent_attention_roofline`` counts the key blocks that the
captured updates visited, not those of some other update: the share grows with the caches'
fill all through a run.  Outside the capture nothing is kept and nothing is fetched.
"""

from __future__ import annotations

from typing import Any, Dict, List

from perfbench.adapters.sequence_policy import SequencePolicyAdapter


class LatentPolicyAdapter(SequencePolicyAdapter):
    def __init__(self, sizes: Dict[str, Any], seed: int, reference):
        super().__init__(sizes, seed, reference)
        self.capture_reports: List[Dict[str, Any]] = []

    def call_update(self, train_fn, *args):
        out = train_fn(*args)
        if self.keep_intervals:  # the harness sets it for the capture's span
            self.capture_reports.append(out[2])
        return out
