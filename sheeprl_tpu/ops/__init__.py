"""Hot ops of the framework: the LayerNorm-GRU gate math (``gru``), exact ring
attention over the ``sequence`` mesh axis (``ring_attention``), and an
experimental fully-fused Pallas RSSM step that nothing calls (``rssm_step``).
"""
