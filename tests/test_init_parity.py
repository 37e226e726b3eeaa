"""Every stock query's ``init`` against the host-numpy setup it replaced.

``Algorithm.init`` is written in ``jax.numpy`` so that the service can
compile it into its ``admit`` program; ``GraphSession`` calls it on the
device tables. Both must give, bit for bit, the frontier and state the
former host setup gave. The numpy below is that setup, kept here as the
reference; it reads the context's tables as host arrays and the float64
start test numpy applied to an int32 degree table.
"""
import functools

import jax
import numpy as np
import pytest

from repro.algorithms import BFS, KCore, PPR, PageRank, WCC, ppr_batch
from repro.core import EngineConfig, GraphSession
from repro.storage.rmat import rmat_graph

INF32 = np.int32(2 ** 30)


@functools.lru_cache(maxsize=None)
def _session(scale: int) -> GraphSession:
    # Graph 500 Kronecker graph (A/B/C 0.57/0.19/0.19, edgefactor 16)
    g = rmat_graph(scale, seed=1, symmetric=True)
    return GraphSession(g, EngineConfig(bucketing=0), block_edges=64)


def _host(ctx):
    return (np.asarray(ctx.degrees).astype(np.int32),
            np.asarray(ctx.is_real))


def _host_bfs(ctx, source):
    src = ctx.engine_id(source)
    dis0 = np.full(ctx.V, INF32, dtype=np.int32)
    dis0[src] = 0
    front0 = np.zeros(ctx.V, dtype=bool)
    front0[src] = True
    return front0, {"dis": dis0}


def _host_push(ctx, r_max, r0):
    degrees, is_real = _host(ctx)
    r0 = r0.astype(np.float32)
    front0 = (r0 > r_max * degrees) & is_real
    return front0, {"p": np.zeros(ctx.V, np.float32), "r": r0}


def _host_ppr(ctx, source, r_max):
    r0 = np.zeros(ctx.V, dtype=np.float32)
    r0[ctx.engine_id(source)] = 1.0
    return _host_push(ctx, r_max, r0)


def _host_pagerank(ctx, r_max):
    r0 = np.zeros(ctx.V, dtype=np.float32)
    r0[ctx.v2id] = 1.0 / ctx.orig_num_vertices
    return _host_push(ctx, r_max, r0)


def _host_wcc(ctx):
    return (np.ones(ctx.V, dtype=bool),
            {"label": np.arange(ctx.V, dtype=np.int32)})


def _host_kcore(ctx, k):
    degrees, is_real = _host(ctx)
    deg0 = degrees.astype(np.int32).copy()
    return (deg0 < k) & is_real, {"deg": deg0}


def _host_ppr_batch(ctx, sources, r_max):
    degrees, is_real = _host(ctx)
    Q = len(sources)
    srcs = np.array([ctx.engine_id(s) for s in sources])
    r0 = np.zeros((Q, ctx.V), dtype=np.float32)
    r0[np.arange(Q), srcs] = 1.0
    front0 = (r0 > r_max * degrees[None, :]) & is_real[None, :]
    return front0, {"p": np.zeros((Q, ctx.V), np.float32), "r": r0}


def _cases(kind, ctx):
    """``(query, host reference)`` pairs, at start thresholds that sit
    on a degree: ``r_max = r0 / d`` for a degree ``d`` the graph has."""
    degrees, _ = _host(ctx)
    deg = degrees[ctx.v2id]                 # by original id
    by_deg = np.argsort(deg, kind="stable")
    hub = int(by_deg[-1])
    # an isolated vertex, a vertex of degree 1 and a middling one
    sources = (int(by_deg[0]), int(np.flatnonzero(deg == 1)[0]),
               int(by_deg[len(by_deg) * 3 // 4]), hub)
    u = np.float32(1.0 / ctx.orig_num_vertices)
    if kind == "BFS":
        return [(BFS(s), functools.partial(_host_bfs, source=s))
                for s in sources]
    if kind == "PPR":
        return [(PPR(s, r_max=r), functools.partial(
                    _host_ppr, source=s, r_max=r))
                for s in sources
                for r in (1e-4, 1.0 / 3, 1.0 / max(int(deg[s]), 1))]
    if kind == "PageRank":
        return [(PageRank(r_max=r), functools.partial(
                    _host_pagerank, r_max=r))
                for r in (1e-7, float(u) / 3, float(u) / int(deg[hub]))]
    if kind == "WCC":
        return [(WCC(), _host_wcc)]
    if kind == "KCore":
        return [(KCore(k), functools.partial(_host_kcore, k=k))
                for k in (1, 3, 16)]
    raise AssertionError(kind)


def _assert_same(got, want):
    front, state = got
    front0, state0 = want
    assert front.dtype == front0.dtype and front.shape == front0.shape
    assert np.array_equal(np.asarray(front), front0)
    assert state.keys() == state0.keys()
    for k in state0:
        assert state[k].dtype == state0[k].dtype, k
        assert np.array_equal(np.asarray(state[k]), state0[k]), k


@pytest.mark.parametrize("scale", [8, 10])
@pytest.mark.parametrize("kind", ["BFS", "PPR", "PPRBatch", "PageRank",
                                  "WCC", "KCore"])
def test_init_matches_host_setup(kind, scale):
    """The hook as ``GraphSession`` runs it (on the device tables) and
    as the service's programs run it (traced, the tables an argument)
    give the host setup's arrays bit for bit."""
    sess = _session(scale)
    eng, ctx = sess.engine, sess.ctx
    if kind == "PPRBatch":
        deg = _host(ctx)[0][ctx.v2id]
        sources = (0, int(np.argmax(deg)), 5, 0)
        for r_max in (1e-4, 1.0 / int(deg.max())):
            batch = ppr_batch(sources, r_max=r_max)
            _assert_same(batch.init_batch(batch.build_batch(), ctx),
                         _host_ppr_batch(ctx, sources, r_max))
        return
    for query, host in _cases(kind, ctx):
        algo = query.build()
        src = algo.init_data(ctx)
        want = host(ctx)
        _assert_same(algo.init(ctx, src), want)
        traced = jax.jit(lambda t, s: algo.init(eng.algo_ctx(t), s))
        _assert_same(traced(eng.tables, src), want)
