"""Engine ticks per answered PPR query, mean over the window."""
from readers import mean_ticks as read  # noqa: F401
