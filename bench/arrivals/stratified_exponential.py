"""Open-loop arrivals at ``rate_qps``, exponential gaps taken at fixed
quantiles rather than drawn: ``round(rate * seconds)`` gaps at the
exponential distribution's quantiles, shuffled by the seed and scaled to
fill the window. Every seed offers the same gaps in another order, so
the seed changes when a burst comes, not how much load there is."""
from __future__ import annotations

import numpy as np


def due(params, seconds, rng):
    n = max(1, round(float(params["rate_qps"]) * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    rng.shuffle(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())
