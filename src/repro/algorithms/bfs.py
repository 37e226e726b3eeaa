"""Breadth-First Search on ACGraph (paper Alg. 2).

apply(u) returns dis[u]; propagation relaxes dis[v] <- min(dis[v], msg+1)
via an atomic CAS loop in the paper — here the batched min-combiner, which
is the same commutative monoid. A vertex activates when its distance
improves; its scheduling priority is -dis (smaller distance first), the
paper's "vertex distance as the priority metric".

``BFS(source)`` is the query-object entry point
(``session.run(BFS(0)).result`` = distances in ORIGINAL vertex ids,
``INF32`` = unreached).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.api import AlgoContext, Algorithm, Query, QueryBatch, \
    StateT

INF32 = np.int32(2 ** 30)


def bfs_batch(sources) -> QueryBatch:
    """Multi-source BFS as one :class:`QueryBatch`: N single-source
    queries co-executed on the concurrent plane (one compiled tick,
    shared block pulls). ``session.run(bfs_batch([0, 7, 42]))`` returns
    per-source distance arrays bit-identical to solo ``BFS(s)`` runs."""
    return QueryBatch(tuple(BFS(int(s)) for s in sources))


def bfs_algorithm() -> Algorithm:
    """Bare engine-facing spec (no init/extract); kept for executor-level
    tests and power users driving ``engine.run`` directly."""
    return Algorithm(
        name="bfs",
        key="dis",
        combine="min",
        apply=lambda st, vids, mask, deg: jnp.where(
            mask, st["dis"][vids], INF32),
        edge_value=lambda msg: jnp.where(msg < INF32, msg + 1, INF32),
        activated=lambda old, new, deg: new < old,
        priority=lambda st, deg: (-st["dis"]).astype(jnp.int32),
        # windowed form of the same expression, for the incremental
        # refresh (evaluates only the lane-window vertices, not all V)
        priority_at=lambda st, vids, deg: (-st["dis"][vids]).astype(
            jnp.int32),
        on_process=None,
    )


@dataclasses.dataclass(frozen=True)
class BFS(Query):
    """Single-source BFS; ``result`` = int32 distances indexed by
    ORIGINAL vertex id (``INF32`` = unreached)."""

    source: int

    def build(self) -> Algorithm:
        def init(ctx: AlgoContext, src):
            front0 = jnp.arange(ctx.V) == src
            return front0, {"dis": jnp.where(front0, jnp.int32(0), INF32)}

        def extract(state: StateT, ctx: AlgoContext):
            return np.asarray(state["dis"])[ctx.v2id]

        return dataclasses.replace(bfs_algorithm(), init=init,
                                   extract=extract, source=self.source)
