"""Device busy ms per engine tick of a whole-graph PageRank, from the trace."""
from readers import tick_busy_ms as read  # noqa: F401
