"""Host ms per service step in which the device sat idle, from the trace."""
from readers import step_host_gap_ms as read  # noqa: F401
