"""The chip benchmark's harness: everything of one cell is found by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration,
traffic mix and metrics.  A configuration is a file under ``configs/``, a
traffic mix a data file under ``traffic/`` that names its driver in
``chipbench/drivers/``, and a per-layer metric a reader under ``metrics/``.
"""
