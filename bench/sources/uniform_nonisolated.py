"""Sources uniform over the vertices with at least one edge other than a
self-loop, with replacement."""
from __future__ import annotations


def stream(params, inputs, rng):
    while True:
        yield from (int(v) for v in rng.choice(inputs.nonisolated, 1024))
