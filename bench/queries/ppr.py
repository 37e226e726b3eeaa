"""Personalized PageRank by forward push (Andersen, Chung, Lang), alpha
from the configuration, ``r_max`` from the spec: a served ``PPR``.

Compared: the largest excess of an answer's implied residual over
forward push's stopping rule (``reference.ppr_violation``,
``ppr_push_violation``). Control: forward push with ``p`` and ``r``
stored in bfloat16 instead of the configuration's float32.
"""
from __future__ import annotations

import functools

import reference

#: limit of ``ppr_push_violation``, set from the program's readings and
#: the bfloat16 control's (PERF.md, "How correct is decided")
LIMIT = 2e-5


def make(spec, config):
    from repro.algorithms import PPR

    return functools.partial(PPR, alpha=config["alpha"],
                             r_max=spec["r_max"])


def check(plain, kept, spec, config, inputs):
    worst = max((reference.ppr_violation(plain, src, config["alpha"],
                                         spec["r_max"], p)
                 for _, src, p in kept), default=0.0)
    return {"ppr_push_violation": (worst, LIMIT)}


def control(plain, sources, spec, config):
    import ml_dtypes

    alpha, r_max = config["alpha"], spec["r_max"]
    worst = max(reference.ppr_violation(
        plain, s, alpha, r_max,
        reference.ppr_push(plain, s, alpha, r_max, ml_dtypes.bfloat16))
        for s in sources)
    return {"ppr_push_violation": (worst, LIMIT)}
