"""Hot ops of the framework: the LayerNorm-GRU gate math (``gru``), exact ring
attention over the ``sequence`` mesh axis and grouped-query attention of a chunk
against a carried cache (``ring_attention``: a chunk of queries, and one query a row whose
query rows a key head fill a bfloat16 tile, go through ``blockwise_attention``, a Pallas
kernel pair that keeps the scores on the chip and visits only the key blocks a row has
filled: the one hand-written kernel on a hot path; other one-query calls form their
scores whole), the RSSM scan's weight gradients formed once after
the loop (``scan_wgrad``), and an experimental fully-fused Pallas RSSM step that nothing
calls (``rssm_step``).
"""
