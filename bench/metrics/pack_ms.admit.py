"""``pack_ms`` of the admission cells: host packing per wave."""
from bench.metrics.pack_ms import read  # noqa: F401
