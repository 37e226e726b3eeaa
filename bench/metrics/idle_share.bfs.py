"""Device idle share of the traced window, percent, BFS cell."""
from readers import idle_share_pct as read  # noqa: F401
