"""Graph500 Kronecker edge tuples from a seed, and the plain CSR the
references read.

The generator follows the Graph 500 specification's Kronecker
generator (initiator A/B/C/D, ``edgefactor * 2**scale`` tuples): at each
of ``scale`` levels every tuple picks one quadrant of the adjacency
matrix. As the specification asks, the vertex ids are then relabelled by
a random permutation and the tuples shuffled, both from the same seed:
unpermuted, the hubs sit together at ids with many zero bits. The
tuples are the benchmark's
input; the system under test builds its graph from them, and the
references build their own CSR from the same tuples, independent of the
program.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def kronecker_tuples(scale: int, edgefactor: int, a: float, b: float,
                     c: float, rng: np.random.Generator
                     ) -> tuple[np.ndarray, np.ndarray]:
    """``edgefactor * 2**scale`` directed tuples ``(src, dst)``, int64.
    Quadrants: A top left, B top right, C bottom left, D the rest."""
    m = edgefactor << scale
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    a32, ab, abc = (np.float32(x) for x in (a, a + b, a + b + c))
    for _ in range(scale):
        r = rng.random(m, dtype=np.float32)
        down = r >= ab
        right = (r >= a32) != down   # B, or D below
        right ^= r >= abc
        src <<= 1
        src |= down
        dst <<= 1
        dst |= right
    return src.astype(np.int64), dst.astype(np.int64)


def graph500_tuples(scale: int, edgefactor: int, a: float, b: float,
                    c: float, rng: np.random.Generator
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Kronecker tuples with the vertex ids permuted and the tuples
    shuffled, all drawn from ``rng``."""
    src, dst = kronecker_tuples(scale, edgefactor, a, b, c, rng)
    label = rng.permutation(1 << scale)
    order = rng.permutation(src.size)
    return label[src[order]], label[dst[order]]


@dataclasses.dataclass(frozen=True)
class PlainGraph:
    """Simple undirected graph in CSR form: each tuple in both
    directions, self-loops dropped, duplicate edges merged."""

    indptr: np.ndarray   # int64[n + 1]
    indices: np.ndarray  # int64[edges], sorted within each row

    @property
    def n(self) -> int:
        return self.indptr.size - 1

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    @property
    def edge_src(self) -> np.ndarray:
        return np.repeat(np.arange(self.n), self.degrees)


def plain_graph(n: int, src: np.ndarray, dst: np.ndarray) -> PlainGraph:
    u = np.concatenate([src, dst])
    v = np.concatenate([dst, src])
    keep = u != v
    key = np.unique(u[keep] * n + v[keep])
    u, v = key // n, key % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(u, minlength=n), out=indptr[1:])
    return PlainGraph(indptr, v)
