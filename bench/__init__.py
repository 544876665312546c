"""The benchmark: one cell of BENCHMARK.json per run of ``bench/run.py``."""
