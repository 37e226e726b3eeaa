"""Engine ticks per whole-graph PageRank (RunResult.metrics.ticks), mean over the window."""
from readers import mean_ticks as read  # noqa: F401
