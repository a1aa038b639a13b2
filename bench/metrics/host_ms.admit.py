"""``host_ms`` of the admission cells: per wave, the call's span on the
host clock less the part in which the device ran anything."""
from bench.metrics.host_ms import read  # noqa: F401
