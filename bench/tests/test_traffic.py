"""The traffic generator's source samplers: a fixed pool gives every seed
the same sources in another order."""
import collections

import numpy as np
import pytest

import traffic


class Inputs:
    def __init__(self, n):
        self.nonisolated = np.arange(0, 2 * n, 2)
        self.endpoints = np.ones(2 * n, np.int64)


def drawn(mix, inputs, seed, k):
    s = traffic.Stream(mix, inputs, np.random.default_rng(seed))
    return [src for _, (_, src) in zip(range(k), s)]


POOL = {"driver": "closed", "clients": 2, "queries": [{"kind": "ppr"}],
        "sources": {"sampler": "fixed_pool", "size": 50, "pool_seed": 7}}


def test_fixed_pool_same_sources_in_another_order_each_pass():
    inp = Inputs(1000)
    a, b = drawn(POOL, inp, 1, 150), drawn(POOL, inp, 2, 150)
    assert a != b
    passes_a = [sorted(a[i:i + 50]) for i in range(0, 150, 50)]
    passes_b = [sorted(b[i:i + 50]) for i in range(0, 150, 50)]
    assert passes_a[0] == passes_a[1] == passes_a[2] == passes_b[0]
    assert passes_b[0] == passes_b[1] == passes_b[2]
    assert len(set(a)) == 50 and set(a) <= set(inp.nonisolated.tolist())
    assert drawn(POOL, inp, 1, 150) == a          # the seed fixes the order


def test_fixed_pool_larger_than_the_graph_takes_every_vertex():
    inp = Inputs(20)
    a = drawn(POOL, inp, 3, 40)
    assert sorted(a[:20]) == sorted(a[20:]) == inp.nonisolated.tolist()


@pytest.mark.parametrize("sampler", ["uniform_nonisolated",
                                     "degree_weighted"])
def test_samplers_draw_vertices_with_edges(sampler):
    mix = dict(POOL, sources={"sampler": sampler})
    inp = Inputs(100)
    got = drawn(mix, inp, 4, 500)
    assert set(got) <= set(inp.nonisolated.tolist())
    assert len(collections.Counter(got)) > 50


def test_unknown_sampler_is_refused():
    with pytest.raises(ValueError, match="no sources named"):
        traffic.validate(dict(POOL, sources={"sampler": "nope"}))
