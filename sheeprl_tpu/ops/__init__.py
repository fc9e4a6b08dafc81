"""Hot ops of the framework: the LayerNorm-GRU gate math (``gru``), exact ring
attention over the ``sequence`` mesh axis and grouped-query attention of a chunk
against a carried cache (``ring_attention``), and an experimental fully-fused Pallas
RSSM step that nothing calls (``rssm_step``).
"""
