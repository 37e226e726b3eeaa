"""Device busy ms per service step, from the trace."""
from readers import step_busy_ms as read  # noqa: F401
