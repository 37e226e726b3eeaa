"""Single-source personalized PageRank via Forward Push (paper Sec. 6.1),
with PageRank as the uniform-distribution special case (footnote 1).

Forward Push (Andersen et al.): processing an active vertex u converts
alpha * r[u] into estimate p[u] and distributes (1-alpha) * r[u] evenly
over out-neighbors; v activates when r[v] > r_max * deg(v). Dangling
vertices (deg 0) absorb alpha * r and drop the remainder (documented
determinization; conserves sum(p) + sum(r) <= 1).

The scheduling priority is the scaled residual — pushing large residuals
first accelerates convergence, the asynchronous analogue of prioritized
sequential push.

``PPR(source, alpha, r_max)`` / ``PageRank(alpha, r_max)`` are the
query-object entry points.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.api import AlgoContext, Algorithm, Query, QueryBatch, \
    StateT


def ppr_algorithm(alpha: float = 0.15, r_max: float = 1e-6) -> Algorithm:
    def apply(st, vids, mask, deg):
        r = st["r"][vids]
        share = jnp.where((deg > 0) & mask,
                          (1.0 - alpha) * r / jnp.maximum(deg, 1), 0.0)
        return share.astype(jnp.float32)

    def on_process(st, mask):
        r = st["r"]
        p = st["p"] + jnp.where(mask, alpha * r, 0.0)
        return {"p": p.astype(jnp.float32),
                "r": jnp.where(mask, 0.0, r).astype(jnp.float32)}

    def activated(old, new, deg):
        thr = r_max * deg.astype(jnp.float32)
        return (new > thr) & (old <= thr) & (new > 0)

    def priority(st, deg):
        # scaled residual density; higher residual scheduled first
        dens = st["r"] / jnp.maximum(deg.astype(jnp.float32), 1.0)
        return jnp.clip(dens * 1e9, 0, 2 ** 30).astype(jnp.int32)

    def priority_at(st, vids, deg):
        # windowed form of priority(): same elementwise f32 ops over
        # the gathered rows only, so values match bit-for-bit
        dens = st["r"][vids] / jnp.maximum(deg.astype(jnp.float32), 1.0)
        return jnp.clip(dens * 1e9, 0, 2 ** 30).astype(jnp.int32)

    return Algorithm(name="ppr", key="r", combine="add", apply=apply,
                     edge_value=lambda msg: msg, activated=activated,
                     priority=priority, priority_at=priority_at,
                     on_process=on_process,
                     params=(alpha, r_max))


def _push_spec(alpha: float, r_max: float, make_r0) -> Algorithm:
    """Forward-push spec with init/extract hooks; ``make_r0(ctx)`` builds
    the initial residual distribution in the engine vertex domain."""

    def init(ctx: AlgoContext):
        r0 = make_r0(ctx).astype(np.float32)
        front0 = (r0 > r_max * ctx.degrees) & ctx.is_real
        return front0, {"p": np.zeros(ctx.V, np.float32), "r": r0}

    def extract(state: StateT, ctx: AlgoContext):
        return np.asarray(state["p"])[ctx.v2id]

    return dataclasses.replace(ppr_algorithm(alpha, r_max), init=init,
                               extract=extract)


@dataclasses.dataclass(frozen=True)
class PPR(Query):
    """Single-source personalized PageRank; ``result`` = float32
    estimates ``p`` indexed by ORIGINAL vertex id (residuals stay in
    ``state['r']``)."""

    source: int
    alpha: float = 0.15
    r_max: float = 1e-6

    def build(self) -> Algorithm:
        source = self.source

        def make_r0(ctx: AlgoContext):
            r0 = np.zeros(ctx.V, dtype=np.float32)
            r0[ctx.engine_id(source)] = 1.0
            return r0

        return _push_spec(self.alpha, self.r_max, make_r0)


@dataclasses.dataclass(frozen=True)
class PPRBatch(QueryBatch):
    """N-personalization PPR — the paper's inherently per-user workload
    — with a *vectorized* batched init: the [Q, V] residual matrix is
    built in one shot instead of stacking Q per-query inits. The arrays
    are element-identical to the auto-lifted hooks (same dtypes, same
    threshold test), so results keep the solo-equivalence contract.
    Build with :func:`ppr_batch`.
    """

    def init_batch(self, algos, ctx: AlgoContext):
        Q = len(self.queries)
        srcs = np.array([ctx.engine_id(q.source) for q in self.queries])
        r0 = np.zeros((Q, ctx.V), dtype=np.float32)
        r0[np.arange(Q), srcs] = 1.0
        r_max = self.queries[0].r_max
        front0 = (r0 > r_max * ctx.degrees[None, :]) & ctx.is_real[None, :]
        return front0, {"p": np.zeros((Q, ctx.V), np.float32), "r": r0}


def ppr_batch(sources, alpha: float = 0.15,
              r_max: float = 1e-6) -> PPRBatch:
    """N personalized-PageRank queries (shared ``alpha``/``r_max``, one
    source per user) as a single batch for the concurrent plane."""
    return PPRBatch(tuple(PPR(int(s), alpha=alpha, r_max=r_max)
                          for s in sources))


@dataclasses.dataclass(frozen=True)
class PageRank(Query):
    """PageRank = PPR with uniform initial distribution (footnote 1);
    ``result`` = estimates indexed by ORIGINAL vertex id.

    The uniform start puts ``1 / n`` on every original vertex, isolated
    ones included: each of those keeps ``alpha / n`` and drops the rest.
    ``r_max`` is absolute, as in PPR: push stops once every residual
    ``r(v) <= r_max * deg(v)``, so the residual mass left, and with it
    the bound ``||p - p*||_1 <= r_max * sum(deg)`` on the distance to
    the exact PageRank ``p*`` (dangling mass dropped), grows with the
    graph's edge count. A guarantee
    on that mass sets ``r_max = mass / sum(deg)``."""

    alpha: float = 0.15
    r_max: float = 1e-7

    def build(self) -> Algorithm:
        def make_r0(ctx: AlgoContext):
            r0 = np.zeros(ctx.V, dtype=np.float32)
            r0[ctx.v2id] = 1.0 / ctx.orig_num_vertices
            return r0

        return _push_spec(self.alpha, self.r_max, make_r0)
