"""Plain reference of whole-graph PageRank by forward push, and the two
comparisons that decide ``correct`` for it. Numpy only; nothing here
imports the program.

Forward push (Andersen, Chung, Lang) from a start vector ``u``, uniform
``1/n`` over every vertex for PageRank (ACGraph footnote 1): an answer
``p`` defines the residual ``r`` it leaves behind through the push
invariant ``p - (1 - alpha) P^T p = alpha (u - r)``, which every push
keeps exactly. Push stops once ``0 <= r(v) <= r_max * deg(v)`` at every
vertex; a vertex with no edge absorbs ``alpha * r`` and drops the rest,
as GAP's PR kernel leaves dangling mass undistributed.

Then ``p* - p = alpha (I - (1 - alpha) P^T)^-1 r`` for the fixed point
``p*`` of ``p = alpha u + (1 - alpha) P^T p`` (dangling mass dropped), and
since ``P^T`` does not grow a nonnegative vector's sum,
``||p - p*||_1 <= ||r||_1 <= r_max * sum(deg)``: a guarantee on the
answer's distance to PageRank that holds with no tolerance added.
"""
from __future__ import annotations

import numpy as np

from graph500 import PlainGraph


def uniform(n: int) -> np.ndarray:
    """PageRank's start: ``1/n`` at every vertex, isolated ones too."""
    return np.full(n, 1.0 / n)


def _spread(g: PlainGraph, x: np.ndarray) -> np.ndarray:
    """``P^T x``: each vertex's value split evenly over its neighbours;
    a vertex with no edge passes nothing on."""
    deg = g.degrees
    return np.bincount(g.indices, weights=(x / np.maximum(deg, 1))[
        g.edge_src], minlength=g.n)


def push(g: PlainGraph, start: np.ndarray, alpha: float, r_max: float,
         dtype=np.float32) -> np.ndarray:
    """Forward push from ``start``, every active vertex at once per round,
    with ``p`` and ``r`` stored in ``dtype`` (sums in float64, rounded
    once per round). Returns ``p``."""
    deg = g.degrees
    thr = r_max * deg
    p = np.zeros(g.n, dtype)
    r = np.asarray(start, np.float64).astype(dtype)
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
        inc = np.bincount(nbrs, weights=np.repeat(
            (1 - alpha) * ra / np.maximum(cnt, 1), cnt), minlength=g.n)
        r = (r.astype(np.float64) + inc).astype(dtype)


def fixed_point(g: PlainGraph, start: np.ndarray, alpha: float,
                tol: float = 1e-13) -> np.ndarray:
    """``p*``, float64, by power iteration of ``p = alpha u + (1 - alpha)
    P^T p`` from ``p = alpha u`` until the L1 change is below ``tol``."""
    u = np.asarray(start, np.float64)
    p = alpha * u
    while True:
        nxt = alpha * u + (1 - alpha) * _spread(g, p)
        if np.abs(nxt - p).sum() < tol:
            return nxt
        p = nxt


def violation(g: PlainGraph, start: np.ndarray, alpha: float,
              r_max: float, p) -> float:
    """How far answer ``p`` lies outside forward push's guarantee.

    The residual ``p`` implies, ``r = u - (p - (1 - alpha) P^T p) /
    alpha``, has to meet the stopping rule ``0 <= r(v) <= r_max deg(v)``.
    Returns the largest excess at any vertex, as a share of the terms
    that make up ``r(v)``, so float32 rounding reads about 1e-7 whatever
    ``r_max`` is (``reference.ppr_violation`` with ``u`` for ``e_s``)."""
    p = np.asarray(p, np.float64)
    u = np.asarray(start, np.float64)
    spread = (1 - alpha) * _spread(g, p)
    r = u - (p - spread) / alpha
    excess = np.maximum(np.maximum(-r, r - r_max * g.degrees), 0.0)
    terms = u + (p + spread) / alpha
    bad = excess > 0
    return float(np.max(excess[bad] / terms[bad], initial=0.0))


def l1_error(p, p_star: np.ndarray) -> float:
    """``||p - p*||_1``, in float64."""
    return float(np.abs(np.asarray(p, np.float64) - p_star).sum())


def guarantee(g: PlainGraph, r_max: float) -> float:
    """The bound ``r_max * sum(deg)`` on ``||p - p*||_1``."""
    return float(r_max * g.degrees.sum())
