"""Chip benchmark of the serving path: see BENCHMARK.json at the root."""
