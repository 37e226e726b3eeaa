"""Shared fixtures and pure-python oracles for the test suite.

NOTE: XLA_FLAGS device-count forcing is intentionally NOT set here — smoke
tests and benchmarks must see the single real CPU device. Only
``launch/dryrun.py`` forces 512 placeholder devices.
"""
from __future__ import annotations

import collections
import os
import pathlib
import sys

import numpy as np
import pytest

from repro.storage.csr import CSRGraph, from_edges, symmetrize
from repro.storage.rmat import rmat_graph

# the benchmark's numpy references (``bench/``), appended so that none of
# its module names shadows another
sys.path.append(str(pathlib.Path(__file__).resolve().parents[1] / "bench"))


# ----------------------------------------------------------------------
# collection guard: the property suite must not silently vanish in CI
# ----------------------------------------------------------------------
# `pip install -e '.[test]'` is the documented default dev install (see
# README "Running the tests"); without the extra, test_property.py
# self-skips via importorskip("hypothesis"). That is fine on a laptop
# but a silent coverage hole in CI, so when CI (or
# REPRO_REQUIRE_HYPOTHESIS=1) is set, a collection that yields zero
# property tests fails the run loudly instead of reporting green.

def pytest_collection_modifyitems(config, items):
    if not (os.environ.get("CI")
            or os.environ.get("REPRO_REQUIRE_HYPOTHESIS")):
        return
    prop = [it for it in items
            if os.path.basename(str(it.fspath)) == "test_property.py"]
    if not prop:
        raise pytest.UsageError(
            "CI collected 0 tests from test_property.py — hypothesis "
            "is missing, so the property suite silently self-skipped. "
            "Install the test extra: pip install -e '.[test]'")


# ----------------------------------------------------------------------
# graph builders
# ----------------------------------------------------------------------

def small_graph(n: int = 200, m: int = 1200, seed: int = 0,
                symmetric: bool = False) -> CSRGraph:
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    g = from_edges(n, src, dst)
    return symmetrize(g) if symmetric else g


@pytest.fixture(scope="session")
def rmat_small() -> CSRGraph:
    return rmat_graph(scale=10, avg_degree=8, seed=1)


@pytest.fixture(scope="session")
def rmat_small_sym(rmat_small) -> CSRGraph:
    return symmetrize(rmat_small)


# ----------------------------------------------------------------------
# oracles
# ----------------------------------------------------------------------

def oracle_bfs(g: CSRGraph, src: int) -> np.ndarray:
    INF = 2 ** 30
    dis = np.full(g.num_vertices, INF, dtype=np.int64)
    dis[src] = 0
    q = collections.deque([src])
    while q:
        u = q.popleft()
        for v in g.neighbors(u):
            if dis[v] > dis[u] + 1:
                dis[v] = dis[u] + 1
                q.append(v)
    return dis


def oracle_wcc(g: CSRGraph) -> np.ndarray:
    """Union-find on a symmetrized graph; labels = min orig id in comp."""
    parent = np.arange(g.num_vertices)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    src = np.repeat(np.arange(g.num_vertices), np.diff(g.indptr))
    for u, v in zip(src, g.indices):
        ru, rv = find(u), find(int(v))
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.array([find(i) for i in range(g.num_vertices)])


def oracle_kcore(g: CSRGraph, k: int) -> np.ndarray:
    """Peeling on a symmetrized graph; True = in k-core."""
    deg = g.degrees().copy()
    removed = np.zeros(g.num_vertices, dtype=bool)
    q = collections.deque(np.where(deg < k)[0].tolist())
    in_q = deg < k
    while q:
        u = q.popleft()
        if removed[u]:
            continue
        removed[u] = True
        for v in g.neighbors(u):
            v = int(v)
            if not removed[v]:
                deg[v] -= 1
                if deg[v] < k and not in_q[v]:
                    in_q[v] = True
                    q.append(v)
    return ~removed


def oracle_ppr(g: CSRGraph, r0: np.ndarray, alpha: float, r_max: float
               ) -> tuple[np.ndarray, np.ndarray]:
    """Sequential forward push with the same dangling-absorb semantics."""
    deg = g.degrees()
    p = np.zeros(g.num_vertices, dtype=np.float64)
    r = r0.astype(np.float64).copy()
    active = collections.deque(np.where(r > r_max * deg)[0].tolist())
    in_q = r > r_max * deg
    while active:
        u = active.popleft()
        in_q[u] = False
        ru = r[u]
        if ru <= r_max * deg[u] and not (deg[u] == 0 and ru > 0):
            continue
        p[u] += alpha * ru
        r[u] = 0.0
        if deg[u] > 0:
            share = (1 - alpha) * ru / deg[u]
            for v in g.neighbors(u):
                v = int(v)
                r[v] += share
                if r[v] > r_max * deg[v] and not in_q[v]:
                    in_q[v] = True
                    active.append(v)
    return p, r


#: float32 rounding of an answer's push invariant, as a share of the
#: terms of ``r(v)`` (``pagerank_reference.violation``): each push adds
#: a rounding of 2**-24 to ``p(v)``; sound answers read 7e-8 to 3.2e-7
#: on Kronecker graphs of scales 8 to 16
PAGERANK_F32_TOL = 2e-6


def check_pagerank(plain, p, alpha: float, r_max: float) -> float:
    """Hold whole-graph PageRank answer ``p`` to the benchmark's plain
    reference on ``plain`` (a ``graph500.PlainGraph``): the push
    invariant to float32 rounding, and ``||p - p*||_1`` within the
    guarantee ``r_max * sum(deg)``, with no tolerance. Returns the L1
    error."""
    import pagerank_reference as ref

    u = ref.uniform(plain.n)
    assert ref.violation(plain, u, alpha, r_max, p) <= PAGERANK_F32_TOL
    l1 = ref.l1_error(p, ref.fixed_point(plain, u, alpha))
    assert l1 <= ref.guarantee(plain, r_max)
    return l1


def plain_of(g: CSRGraph):
    """``g``'s own CSR as the references' ``PlainGraph``: directed,
    degrees out-degrees."""
    from graph500 import PlainGraph

    return PlainGraph(g.indptr.astype(np.int64), g.indices.astype(np.int64))


def check_is_mis(g: CSRGraph, mis: np.ndarray) -> None:
    """Independence + maximality on a symmetrized graph."""
    mis = np.asarray(mis, dtype=bool)
    for u in range(g.num_vertices):
        nbrs = g.neighbors(u)
        if mis[u]:
            assert not mis[nbrs].any(), f"MIS not independent at {u}"
        else:
            assert mis[nbrs].any() or len(nbrs) == 0 or mis[u], \
                f"MIS not maximal at {u}"
    # isolated non-member vertices violate maximality
    deg = g.degrees()
    assert mis[(deg == 0)].all(), "isolated vertices must join the MIS"
