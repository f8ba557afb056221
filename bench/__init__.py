"""The on-chip benchmark of the FastCache serving engine.

``BENCHMARK.json`` at the root names its cells; ``bench/run.py`` runs one
cell once.  Each configuration, model family, traffic mix and metric is a
file of its own (``bench/configs``, ``bench/families``, ``bench/mixes``,
``bench/metrics``), found by name (``bench/spec.py``).
"""
