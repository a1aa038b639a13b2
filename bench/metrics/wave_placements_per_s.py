"""Tenant placements answered by admission waves over the whole window,
per second (``placements_per_s`` of the admission cells)."""
from bench.metrics.placements_per_s import read  # noqa: F401
