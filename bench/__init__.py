"""Chip benchmark of PICASSO training: cells, metrics and the correctness
check, driven by ``BENCHMARK.json`` at the checkout root."""
