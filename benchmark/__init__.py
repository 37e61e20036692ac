"""The benchmark: one command runs one cell of ``BENCHMARK.json`` once.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``configs/``, ``traffic/``,
``metrics/`` (+ ``readers/``), found by the name ``BENCHMARK.json`` gives it;
``run.py`` knows two kinds of cell, ``train`` and ``serve``, and no cell.
"""
