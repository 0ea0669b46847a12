"""Chip benchmark of the compressed serving path (see BENCHMARK.json)."""
