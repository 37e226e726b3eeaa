"""Whole-graph PageRank through ``GraphSession.run`` against the
benchmark's plain reference (``bench/pagerank_reference.py``) on seeded
Graph 500 Kronecker graphs, built as the benchmark builds them: the push
invariant to float32 rounding, ``||p - p*||_1`` within ``r_max *
sum(deg)``, and each isolated vertex holding its share ``alpha / n``."""
import numpy as np
import pytest

from graph500 import graph500_tuples, plain_graph
from repro.algorithms import PageRank
from repro.core import EngineConfig, GraphSession
from repro.storage.csr import from_edges

from conftest import check_pagerank

ALPHA = 0.15


def kronecker(scale: int):
    src, dst = graph500_tuples(scale, 16, 0.57, 0.19, 0.19,
                               np.random.default_rng(1))
    return 1 << scale, src, dst


@pytest.mark.parametrize("refresh", ["incremental", "full"])
@pytest.mark.parametrize("r_max", [1e-6, 2.13e-8])
@pytest.mark.parametrize("scale", [8, 10])
def test_pagerank_meets_the_reference(scale, r_max, refresh):
    n, src, dst = kronecker(scale)
    sess = GraphSession(from_edges(n, np.concatenate([src, dst]),
                                   np.concatenate([dst, src])),
                        EngineConfig(refresh=refresh))
    p = sess.run(PageRank(alpha=ALPHA, r_max=r_max)).result
    plain = plain_graph(n, src, dst)
    assert check_pagerank(plain, p, ALPHA, r_max) > 0
    isolated = plain.degrees == 0
    assert isolated.any()
    np.testing.assert_allclose(p[isolated], ALPHA / n, rtol=1e-6)
