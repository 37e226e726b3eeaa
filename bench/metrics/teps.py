"""Graph500 TEPS over the window: traversed input tuples per second."""
from readers import teps as read  # noqa: F401
