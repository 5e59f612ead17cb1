"""PERMANOVA benchmark: cells named in BENCHMARK.json, run by bench/run.py."""
