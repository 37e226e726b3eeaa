"""90th-percentile latency, ms, over every query due in the window."""
from readers import latency_p90_ms as read  # noqa: F401
