"""Chip benchmark of the DCNN serving stack (see BENCHMARK.json and PERF.md)."""
