"""Sources drawn in proportion to their degree (tuple endpoints other
than self-loops), with replacement: hubs are asked about most."""
from __future__ import annotations

import numpy as np


def stream(params, inputs, rng):
    w = inputs.endpoints[inputs.nonisolated].astype(np.float64)
    p = w / w.sum()
    while True:
        yield from (int(v) for v in rng.choice(inputs.nonisolated, 1024,
                                               p=p))
