"""Bursty open-loop arrivals: ``on_s`` seconds at ``rate_qps``, then
``off_s`` seconds with none, repeated through the window; inside each
burst the gaps are those of ``stratified_exponential``."""
from __future__ import annotations

import numpy as np

from traffic import plugin


def due(params, seconds, rng):
    burst = plugin("arrivals", "stratified_exponential").due
    on, off = float(params["on_s"]), float(params["off_s"])
    out, start = [], 0.0
    while start < seconds:
        out.append(start + burst(params, min(on, seconds - start), rng))
        start += on + off
    return np.concatenate(out)
