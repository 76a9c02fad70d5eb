"""Benchmark harness: see bench/run.py and PERF.md."""
