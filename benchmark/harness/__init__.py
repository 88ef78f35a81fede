"""The benchmark harness: one cell of ``BENCHMARK.json``, run once.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a data file found by name (``configs/``, ``traffic/``, ``metrics/``
with its reader under ``readers/``); the code here is the general part.
"""
