"""Benchmark of the TRIM design-space explorer on a TPU: one DSE query,
run back to back.  `bench/run.py` is the entry point; BENCHMARK.json at
the repository root names the cells."""
