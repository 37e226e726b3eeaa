"""Set-up seconds: process start to the window's start (graph, upload, warm-up, compile or cache load)."""
from readers import setup_s as read  # noqa: F401
