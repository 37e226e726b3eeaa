"""Device idle share of the traced window, percent, PageRank cell."""
from readers import idle_share_pct as read  # noqa: F401
