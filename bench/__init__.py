"""The benchmark: one harness (``run.py``) that runs the cells named in
``BENCHMARK.json`` from their data files."""
