"""Plain references of the benchmark's queries and the comparisons that
decide ``correct`` (each query kind's ``bench/queries/<kind>.py`` calls
them with its limit). Numpy only; nothing here imports the program.

BFS: exact hop distances, compared vertex by vertex.

PPR (forward push, Andersen-Chung-Lang): an answer ``p`` defines the
residual it leaves behind through the push invariant
``p - (1 - alpha) P^T p = alpha (e_s - r)``, which every push keeps
exactly. A sound answer's residual satisfies the stopping rule
``0 <= r(v) <= r_max * deg(v)`` at every vertex, so both sides of that
rule are compared, each as a share of ``r_max * deg(v)``.
"""
from __future__ import annotations

import numpy as np

from graph500 import PlainGraph

#: distance of an unreached vertex (the program's ``INF32``)
UNREACHED = 2 ** 30


def bfs(g: PlainGraph, root: int, max_levels: int | None = None
        ) -> np.ndarray:
    """Level-synchronous BFS over the CSR, vectorised per level;
    ``max_levels`` stops it early (used only as the control)."""
    dis = np.full(g.n, UNREACHED, np.int64)
    seen = np.zeros(g.n, bool)
    dis[root], seen[root] = 0, True
    frontier = np.array([root], np.int64)
    level = 0
    while frontier.size and (max_levels is None or level < max_levels):
        level += 1
        starts = g.indptr[frontier]
        cnt = g.indptr[frontier + 1] - starts
        first = np.repeat(starts - (np.cumsum(cnt) - cnt), cnt)
        nbrs = g.indices[first + np.arange(int(cnt.sum()))]
        new = np.zeros(g.n, bool)
        new[nbrs] = True
        frontier = np.flatnonzero(new & ~seen)
        seen[frontier] = True
        dis[frontier] = level
    return dis


def traversed_tuples(src: np.ndarray, dis: np.ndarray) -> int:
    """Graph500's edge count for TEPS: input tuples inside the traversed
    component, each undirected tuple once."""
    return int(np.count_nonzero(dis[src] < UNREACHED))


def ppr_push(g: PlainGraph, source: int, alpha: float, r_max: float,
             dtype=np.float32) -> np.ndarray:
    """Forward push, every active vertex at once per round, with ``p``
    and ``r`` stored in ``dtype`` (sums in float64, rounded once per
    round). Returns ``p``."""
    deg = g.degrees
    thr = r_max * deg
    p = np.zeros(g.n, dtype)
    r = np.zeros(g.n, dtype)
    r[source] = 1
    while True:
        act = np.flatnonzero(r.astype(np.float64) > thr)
        if not act.size:
            return p
        ra = r[act].astype(np.float64)
        p[act] = (p[act].astype(np.float64) + alpha * ra).astype(dtype)
        r[act] = 0
        starts, cnt = g.indptr[act], deg[act]
        first = np.repeat(starts - (np.cumsum(cnt) - cnt), cnt)
        nbrs = g.indices[first + np.arange(int(cnt.sum()))]
        inc = np.bincount(nbrs, weights=np.repeat((1 - alpha) * ra / cnt,
                                                  cnt), minlength=g.n)
        r = (r.astype(np.float64) + inc).astype(dtype)


def ppr_violation(g: PlainGraph, source: int, alpha: float, r_max: float,
                  p) -> float:
    """How far answer ``p`` lies outside forward push's guarantee.

    The residual ``p`` implies, ``r = e_s - (p - (1 - alpha) P^T p) /
    alpha``, has to meet the stopping rule ``0 <= r(v) <= r_max deg(v)``.
    Returns the largest excess at any vertex, as a share of the terms
    that make up ``r(v)``, so float32 rounding reads about 1e-7 whatever
    ``r_max`` is."""
    p = np.asarray(p, np.float64)
    deg = g.degrees
    spread = (1 - alpha) * np.bincount(
        g.indices, weights=(p / np.maximum(deg, 1))[g.edge_src],
        minlength=g.n)
    r = -(p - spread) / alpha
    r[source] += 1.0
    excess = np.maximum(np.maximum(-r, r - r_max * deg), 0.0)
    terms = (p + spread) / alpha
    terms[source] += 1.0
    bad = excess > 0
    return float(np.max(excess[bad] / terms[bad], initial=0.0))
