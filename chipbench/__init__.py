"""Chip benchmark of the streaming engine: one cell, one seed, one run.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` builds the cell's engine, warms it up, measures, checks the
results against a plain reference and prints one JSON result line.  Cells,
configurations, traffic mixes and per-layer metrics are data: the harness
finds each by its name in ``BENCHMARK.json`` (see :mod:`chipbench.registry`).
"""
