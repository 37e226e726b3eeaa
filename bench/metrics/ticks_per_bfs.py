"""Engine ticks per whole-graph BFS (RunResult.metrics.ticks), mean over the window."""
from readers import mean_ticks as read  # noqa: F401
