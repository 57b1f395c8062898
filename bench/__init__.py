"""Chip benchmark of this repository: one data-driven harness (``run.py``).

Cells, configurations, traffic mixes and per-layer metrics are files found
by the names in ``BENCHMARK.json``; see ``PERF.md`` for what each measures.
"""
