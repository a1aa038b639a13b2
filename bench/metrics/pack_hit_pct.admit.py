"""``pack_hit_pct`` of the admission cells: layouts reused per wave."""
from bench.metrics.pack_hit_pct import read  # noqa: F401
