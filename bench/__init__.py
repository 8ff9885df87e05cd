"""Chip benchmark of ISGD training: one command, cells found by name in BENCHMARK.json."""
