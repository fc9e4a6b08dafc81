"""What the Dreamer family's entry points share: their host loop (``loop.py``)."""
