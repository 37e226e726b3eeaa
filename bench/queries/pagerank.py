"""Whole-graph PageRank by forward push from the uniform start (ACGraph
footnote 1; GAP's PR kernel), alpha from the configuration, ``r_max``
from the spec: ``GraphSession.run(PageRank(alpha, r_max))``.

Compared (``pagerank_reference.py``), on every answer:

* ``pagerank_push_violation``: the largest excess of the residual that
  push's invariant implies over the stopping rule ``0 <= r(v) <= r_max
  deg(v)``, relative to the terms of ``r(v)``, as PPR's
  ``ppr_push_violation`` with the uniform start in place of ``e_s``;
* ``pagerank_l1_error``: ``||p - p*||_1`` against the fixed point ``p*``
  (float64, once per run), whose limit ``r_max * sum(deg)`` is the
  guarantee itself, with no tolerance added.

Each compared answer also gets the input tuple count, Graph 500's edge
count for a search that reaches every vertex, so ``teps`` reads input
tuples per second of whole PageRank runs. Control: forward push with
``p`` and ``r`` stored in bfloat16 instead of the configuration's
float32.
"""
# No ``from __future__ import annotations``: ``dataclasses`` resolves a
# string annotation through ``sys.modules``, which does not hold a query
# kind loaded from its file by ``traffic.plugin``.
import dataclasses

import pagerank_reference as ref

#: limit of ``pagerank_push_violation``, set from the program's readings
#: and the bfloat16 control's (PERF.md, "How correct is decided")
LIMIT = 2e-5


def make(spec, config):
    from repro.algorithms import PageRank

    @dataclasses.dataclass(frozen=True)
    class SourcedPageRank(PageRank):
        """``PageRank`` with the placeholder ``source`` the harness keys
        answers by; the program never reads it, and ``build`` is
        PageRank's own."""

        source: int = -1

    def query(src):
        return SourcedPageRank(alpha=config["alpha"], r_max=spec["r_max"],
                               source=src)
    return query


def _compare(plain, answers, spec, config):
    alpha, r_max = config["alpha"], spec["r_max"]
    u = ref.uniform(plain.n)
    p_star = ref.fixed_point(plain, u, alpha)
    worst = max((ref.violation(plain, u, alpha, r_max, p)
                 for p in answers), default=0.0)
    l1 = max((ref.l1_error(p, p_star) for p in answers), default=0.0)
    return {"pagerank_push_violation": (worst, LIMIT),
            "pagerank_l1_error": (l1, ref.guarantee(plain, r_max))}


def check(plain, kept, spec, config, inputs):
    for a, _, _ in kept:
        a.info["edges"] = int(inputs.t_src.size)
    return _compare(plain, [p for _, _, p in kept], spec, config)


def control(plain, sources, spec, config):
    import ml_dtypes

    p = ref.push(plain, ref.uniform(plain.n), config["alpha"],
                 spec["r_max"], ml_dtypes.bfloat16)
    return _compare(plain, [p], spec, config)
