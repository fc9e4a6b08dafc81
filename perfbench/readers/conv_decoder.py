"""Per-layer metrics of a decoder policy with convolution mixers, a dense feed-forward and
a biased router (``lfm2_8b_a1b_1of4``), read by the program's own names: the scopes
``policy/conv`` and ``policy/dense_ffn`` inside its jitted update, and the update's counter
``MoE/bias_moved_share``.  The reduction is ``readers/decoder.py``'s; a program without the
scope or the counter (an older commit, another model) gives every reader here ``None``.
"""

from __future__ import annotations

from perfbench.readers import decoder


def conv_device_ms(run):
    """The gated short convolutions (in-projection, gates, taps, out-projection), a gradient step."""
    return decoder._update_scope_ms(run, "policy/conv")


def dense_ffn_device_ms(run):
    """The leading layers' dense feed-forward, a gradient step."""
    return decoder._update_scope_ms(run, "policy/dense_ffn")


def router_bias_moved_share(run):
    """Of the tokens that met a router, the share (in %) whose chosen experts the selection
    bias changed, over the compared updates: 0 says the bias did nothing."""
    values = decoder._reported(run, "MoE/bias_moved_share")
    return 100.0 * sum(values) / len(values) if values else None
