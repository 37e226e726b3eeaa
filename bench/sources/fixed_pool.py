"""A fixed pool of ``size`` distinct sources, drawn once, uniformly over
the vertices with at least one edge other than a self-loop, from the
mix's ``pool_seed``. The run's seed only orders them: each pass over the
pool is a fresh permutation drawn from it.

Every seed thus asks about the same sources in another order, so the
seed changes the order of the work and not the work: a window that
takes about ``size`` queries does nearly the same work whatever its
seed, as Graph 500 fixes its search keys once per graph. A pool larger
than the vertices with an edge (a rehearsal's tiny graph) holds them
all."""
from __future__ import annotations

import numpy as np


def stream(params, inputs, rng):
    size = min(int(params["size"]), inputs.nonisolated.size)
    pool = np.random.default_rng(int(params["pool_seed"])).choice(
        inputs.nonisolated, size, replace=False)
    while True:
        yield from (int(v) for v in rng.permutation(pool))
