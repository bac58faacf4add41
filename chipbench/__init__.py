"""Chip benchmark: one cell of BENCHMARK.json per run, on the device."""
