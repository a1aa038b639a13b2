"""``device_idle_pct`` of the admission cells."""
from bench.metrics.device_idle_pct import read  # noqa: F401
