"""Answered PPR queries per second over the window."""
from readers import answers_per_s as read  # noqa: F401
