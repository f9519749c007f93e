"""Chip benchmark of the kNN-graph index (see BENCHMARK.json and PERF.md)."""
