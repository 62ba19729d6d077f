"""Chip benchmark of the served int8 CNN path (see BENCHMARK.json, PERF.md)."""
