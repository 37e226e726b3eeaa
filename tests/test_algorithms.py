"""Integration tests: the paper algorithms on the async engine vs
pure-python oracles, in both async and sync (Sec. 4.3) modes, through
the ``GraphSession`` query API (the deprecated ``run_*`` wrappers were
removed after their one-PR-cycle grace period)."""
import numpy as np
import pytest

from repro.algorithms import BFS, KCore, MIS, PPR, PageRank, WCC
from repro.core.engine import Engine, EngineConfig
from repro.core.session import GraphSession
from repro.storage.csr import symmetrize
from repro.storage.hybrid import build_hybrid

from conftest import (check_is_mis, check_pagerank, oracle_bfs, oracle_kcore,
                      oracle_ppr, oracle_wcc, plain_of, small_graph)


def make_session(g, sync=False, **kw):
    # bucketing=0: results are bit-identical either way (enforced by
    # test_bucketing); the global tile keeps per-test compile times down
    cfg = EngineConfig(lanes=4, prefetch=4, queue_depth=8, pool_slots=24,
                       chunk_size=64, sync=sync, bucketing=0, **kw)
    return GraphSession(g, cfg, block_edges=64)


@pytest.mark.parametrize("sync", [False, True])
@pytest.mark.parametrize("seed", [0, 1])
def test_bfs_matches_oracle(sync, seed):
    g = small_graph(n=250, m=1500, seed=seed)
    res = make_session(g, sync=sync).run(BFS(3))
    want = oracle_bfs(g, 3)
    assert np.array_equal(res.result.astype(np.int64), want)
    assert res.metrics.ticks > 0
    assert res.metrics.vertices_processed > 0


def test_bfs_unreachable():
    # two disconnected stars
    g = small_graph(n=40, m=120, seed=7)
    res = make_session(g).run(BFS(0))
    want = oracle_bfs(g, 0)
    assert np.array_equal(res.result.astype(np.int64), want)


@pytest.mark.parametrize("sync", [False, True])
def test_wcc_matches_oracle(sync):
    g = small_graph(n=300, m=900, seed=2, symmetric=True)
    res = make_session(g, sync=sync).run(WCC())
    assert np.array_equal(res.result, oracle_wcc(g))
    assert res.metrics.edges_scanned > 0


@pytest.mark.parametrize("k", [3, 5])
def test_kcore_matches_oracle(k):
    g = small_graph(n=250, m=2500, seed=3, symmetric=True)
    res = make_session(g).run(KCore(k))
    assert np.array_equal(res.result, oracle_kcore(g, k))


def test_ppr_matches_oracle():
    g = small_graph(n=200, m=1600, seed=4)
    alpha, r_max = 0.15, 1e-4
    res = make_session(g).run(PPR(5, alpha=alpha, r_max=r_max))
    p = res.result
    r0 = np.zeros(g.num_vertices)
    r0[5] = 1.0
    p_want, r_want = oracle_ppr(g, r0, alpha, r_max)
    # both are valid forward-push fixpoints; estimates agree within the
    # total residual bound
    assert np.all(p >= -1e-7)
    np.testing.assert_allclose(p.sum(), p_want.sum(), atol=r_max * 200 * 10)
    np.testing.assert_allclose(p, p_want, atol=5e-3)


def test_ppr_two_alphas_one_engine():
    """Regression (compile-cache aliasing): the cache must key on the
    Algorithm *instance*, not its name — two PPR configs run on one
    session used to silently reuse the first compiled closure and
    return the first alpha's estimates for both."""
    g = small_graph(n=200, m=1600, seed=4)
    sess = make_session(g)
    r_max = 1e-4
    r0 = np.zeros(g.num_vertices)
    r0[5] = 1.0
    for alpha in (0.15, 0.6):
        res = sess.run(PPR(5, alpha=alpha, r_max=r_max))
        p_want, _ = oracle_ppr(g, r0, alpha, r_max)
        np.testing.assert_allclose(res.result, p_want, atol=5e-3)
    assert sess.num_compiled == 2


def test_compile_cache_reuses_equal_params():
    """Repeated runs of an equal-parameter query on one session must
    hit the compile cache (no per-call re-jit / unbounded growth)."""
    g = small_graph(n=100, m=500, seed=11)
    sess = make_session(g)
    sess.run_many([BFS(0), BFS(0), BFS(0),
                   PPR(0, alpha=0.15, r_max=1e-4),
                   PPR(0, alpha=0.15, r_max=1e-4)])
    assert sess.num_compiled == 2  # one bfs entry + one ppr entry


def test_pagerank_converges():
    g = small_graph(n=150, m=1200, seed=5)
    res = make_session(g).run(PageRank(r_max=1e-5))
    assert res.result.sum() <= 1.0 + 1e-5
    check_pagerank(plain_of(g), res.result, 0.15, 1e-5)
    assert res.metrics.ticks > 0


def test_mis_valid():
    g = small_graph(n=200, m=800, seed=6, symmetric=True)
    res = make_session(g).run(MIS(seed=0))
    check_is_mis(g, res.result)
    assert res.metrics.barriers == 0  # phases barrier at the host level


def test_async_engine_reuse_reduces_io():
    """The online worklist must reuse resident blocks (paper Sec. 4.2):
    async I/O volume <= sync I/O volume on the same WCC workload."""
    g = small_graph(n=400, m=2400, seed=8, symmetric=True)
    m_async = make_session(g, sync=False).run(WCC()).metrics
    m_sync = make_session(g, sync=True).run(WCC()).metrics
    assert m_async.io_blocks <= m_sync.io_blocks
    assert m_sync.barriers > 0


def test_kcore_zero_io_for_mini_only_graph():
    """A graph with only mini vertices (deg <= 2) lives in memory: the
    hybrid storage must serve it without any disk I/O (paper Sec. 5.2)."""
    # ring graph: every vertex has degree 2 (symmetric)
    n = 64
    src = np.arange(n)
    dst = (src + 1) % n
    from repro.storage.csr import from_edges
    g = symmetrize(from_edges(n, src, dst))
    sess = make_session(g)
    assert sess.hg.num_blocks == 1  # no large vertices -> 1 empty block
    res = sess.run(KCore(2))
    assert res.result.all()
    assert res.metrics.io_blocks == 0


def test_early_stop_engine_runs():
    g = small_graph(n=200, m=1000, seed=9)
    hg = build_hybrid(g, block_edges=64)
    eng = Engine(hg, EngineConfig(early_stop=2, pool_slots=16,
                                  chunk_size=64, bucketing=0))
    res = GraphSession.from_engine(eng).run(BFS(0))
    assert np.array_equal(res.result.astype(np.int64), oracle_bfs(g, 0))


def test_priority_cached_policy():
    g = small_graph(n=200, m=1000, seed=10)
    hg = build_hybrid(g, block_edges=64)
    eng = Engine(hg, EngineConfig(cached_policy="priority", pool_slots=16,
                                  chunk_size=64, bucketing=0))
    res = GraphSession.from_engine(eng).run(BFS(0))
    assert np.array_equal(res.result.astype(np.int64), oracle_bfs(g, 0))


def test_deprecated_wrappers_are_gone():
    """ROADMAP: the run_* / asyncRun / syncRun delegates were removed
    after one PR cycle — the query API is the only entry point."""
    import repro.algorithms as algos
    import repro.core as core
    for name in ("run_bfs", "run_wcc", "run_kcore", "run_ppr",
                 "run_pagerank", "run_mis"):
        assert not hasattr(algos, name)
    for name in ("asyncRun", "syncRun"):
        assert not hasattr(core, name)
