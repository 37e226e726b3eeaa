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

import jax
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


def _last_active_degree(r0: float, r_max: float) -> int:
    """Largest int32 degree ``d`` at which a vertex holding residual
    ``r0`` starts active, or -1 if none does.

    The start rule is ``r0 > r_max * d`` evaluated in float64, as numpy
    evaluates it for an int32 degree table. The test is monotone in
    ``d``, so a start frontier reads ``degrees <= d`` in integers on
    the device, with the float64 products taken here on the host once.
    """
    active = lambda d: float(r0) > float(r_max) * d
    lo, hi = 0, 2 ** 31 - 1
    if not active(lo):
        return -1
    if active(hi):
        return hi
    while hi - lo > 1:           # active(lo), not active(hi)
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if active(mid) else (lo, mid)
    return lo


def _push_spec(alpha: float, r_max: float, **hooks) -> Algorithm:
    """Forward-push spec with ``init`` (among ``hooks``) and the
    ``extract`` both starts share."""

    def extract(state: StateT, ctx: AlgoContext):
        return np.asarray(state["p"])[ctx.v2id]

    return dataclasses.replace(ppr_algorithm(alpha, r_max),
                               extract=extract, **hooks)


@dataclasses.dataclass(frozen=True)
class PPR(Query):
    """Single-source personalized PageRank; ``result`` = float32
    estimates ``p`` indexed by ORIGINAL vertex id (residuals stay in
    ``state['r']``)."""

    source: int
    alpha: float = 0.15
    r_max: float = 1e-6

    def build(self) -> Algorithm:
        last = _last_active_degree(1.0, self.r_max)

        def init(ctx: AlgoContext, src):
            hit = jnp.arange(ctx.V) == src
            r0 = jnp.where(hit, jnp.float32(1), jnp.float32(0))
            front0 = hit & (ctx.degrees <= last) & ctx.is_real
            return front0, {"p": jnp.zeros(ctx.V, jnp.float32), "r": r0}

        return _push_spec(self.alpha, self.r_max, init=init,
                          source=self.source)


@dataclasses.dataclass(frozen=True)
class PPRBatch(QueryBatch):
    """N-personalization PPR — the paper's inherently per-user workload
    — with a *vectorized* batched init: the members' one ``init``
    mapped over their sources at once instead of stacking Q separate
    calls. The arrays are element-identical to the auto-lifted hooks,
    so results keep the solo-equivalence contract. Build with
    :func:`ppr_batch`.
    """

    def init_batch(self, algos, ctx: AlgoContext):
        srcs = jnp.stack([a.init_data(ctx) for a in algos])
        return jax.vmap(lambda s: algos[0].init(ctx, s))(srcs)


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
        r_max = self.r_max

        def init(ctx: AlgoContext, src):
            # the start value as float32 on the host: a division on the
            # device may round 1 / n another way
            u = np.float32(1.0 / ctx.orig_num_vertices)
            r0 = jnp.where(ctx.is_real, u, jnp.float32(0))
            last = _last_active_degree(u, r_max)
            front0 = ctx.is_real & (ctx.degrees <= last)
            return front0, {"p": jnp.zeros(ctx.V, jnp.float32), "r": r0}

        # another name than PPR's: the two starts are two init hooks,
        # and the service compiles a group's init into its programs
        return _push_spec(self.alpha, self.r_max, init=init,
                          name="pagerank")
