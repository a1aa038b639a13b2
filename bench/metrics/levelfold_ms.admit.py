"""``levelfold_ms`` of the admission cells: device time per wave of the
level-fold kernel inside the penalty loop."""
from bench.metrics.levelfold_ms import read  # noqa: F401
