"""Weakly Connected Components via Label Propagation (paper Sec. 2.1).

Every vertex starts with a unique label (its reordered id) and the minimum
label propagates. Priority = -label (smallest label first), the paper's
work-inflation killer: within a component only pushes from the minimum
label are ultimately useful, so scheduling min-label blocks first avoids
redundant edge accesses (Sec. 3.1 "Work Inflation").

Input graphs must be symmetrized (undirected semantics), as in the paper's
preprocessing. ``WCC()`` is the query-object entry point.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.api import AlgoContext, Algorithm, Query, StateT

INF32 = np.int32(2 ** 30)


def wcc_algorithm() -> Algorithm:
    """Bare engine-facing spec (no init/extract)."""
    return Algorithm(
        name="wcc",
        key="label",
        combine="min",
        apply=lambda st, vids, mask, deg: jnp.where(
            mask, st["label"][vids], INF32),
        edge_value=lambda msg: msg,
        activated=lambda old, new, deg: new < old,
        priority=lambda st, deg: (-st["label"]).astype(jnp.int32),
        # windowed form of the same expression, for the incremental
        # refresh (evaluates only the lane-window vertices, not all V)
        priority_at=lambda st, vids, deg: (-st["label"][vids]).astype(
            jnp.int32),
        on_process=None,
    )


@dataclasses.dataclass(frozen=True)
class WCC(Query):
    """Connected components on a symmetrized graph; ``result`` =
    component labels indexed by ORIGINAL vertex id, canonicalized to the
    minimum original id in each component."""

    def build(self) -> Algorithm:
        def init(ctx: AlgoContext, src):
            label0 = jnp.arange(ctx.V, dtype=jnp.int32)
            front0 = jnp.ones(ctx.V, dtype=bool)  # all vertices active
            return front0, {"label": label0}

        def extract(state: StateT, ctx: AlgoContext):
            new_labels = np.asarray(state["label"])[ctx.v2id]
            # canonicalize: min original id carrying each reordered label
            canon = np.full(ctx.V, np.iinfo(np.int64).max, dtype=np.int64)
            np.minimum.at(canon, new_labels,
                          np.arange(ctx.orig_num_vertices))
            return canon[new_labels]

        return dataclasses.replace(wcc_algorithm(), init=init,
                                   extract=extract)
