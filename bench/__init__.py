"""Chip benchmark of the served DiT path (``python -m bench.run``).

Each configuration, traffic mix and metric lives in a file of its own
(``configs/``, ``traffic/``, ``metrics/``), found by the name that
``BENCHMARK.json`` at the checkout root gives it.
"""
