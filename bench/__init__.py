"""Chip benchmark of the Themis ZeRO-2 train step (see BENCHMARK.json)."""
