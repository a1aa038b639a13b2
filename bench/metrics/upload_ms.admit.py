"""``upload_ms`` of the admission cells: host-to-device transfer per
wave."""
from bench.metrics.upload_ms import read  # noqa: F401
