"""95th percentile of the admission waves' latency (call until every
tenant's program is installed), over every wave of the window."""
from bench.metrics.decision_p95_ms import read  # noqa: F401
