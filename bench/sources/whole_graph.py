"""No source: ``-1`` forever. A whole-graph kernel such as PageRank
starts from every vertex at once and has no source, but the harness keys
each answer by its query's ``source``, so the kind's query carries this
placeholder, which the program never reads."""
from __future__ import annotations

import itertools


def stream(params, inputs, rng):
    return itertools.repeat(-1)
