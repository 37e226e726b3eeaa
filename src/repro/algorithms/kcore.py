"""k-core decomposition membership (paper Alg. 3).

foreachVertex seeds the worklist with vertices of degree < k; propagation
is an atomic fetchSub(1) on the neighbor's degree, activating it exactly
when the value crosses k -> k-1. In the batched engine the crossing test
``old >= k and new < k`` fires exactly once per vertex because degrees
decrease monotonically — the same exactly-once guarantee the paper proves
via fetchSub atomicity.

Input graphs must be symmetrized. ``KCore(k)`` is the query-object entry
point.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.api import AlgoContext, Algorithm, Query, StateT


def kcore_algorithm(k: int) -> Algorithm:
    """Bare engine-facing spec (no init/extract)."""
    return Algorithm(
        name=f"kcore_{k}",
        key="deg",
        combine="add",
        apply=lambda st, vids, mask, deg: jnp.where(mask, 1, 0
                                                    ).astype(jnp.int32),
        edge_value=lambda msg: jnp.full_like(msg, -1),
        activated=lambda old, new, deg: (old >= k) & (new < k),
        priority=lambda st, deg: jnp.zeros_like(st["deg"]),
        priority_at=lambda st, vids, deg: jnp.zeros_like(
            st["deg"][vids]),
        on_process=None,
        # combine="add", but schedule-independent all the same: every
        # removed vertex sends a constant -1 over each edge exactly once
        # (the crossing test fires once per vertex), so the final
        # degrees are deg0 - #removed-neighbors under ANY pull order —
        # integer peeling is confluent. Opts k-core into the aggregated
        # batch plane, which the combine=="min" default would refuse
        schedule_independent=True,
    )


@dataclasses.dataclass(frozen=True)
class KCore(Query):
    """k-core membership on a symmetrized graph; ``result`` =
    bool[orig_num_vertices] (True = vertex is in the k-core)."""

    k: int

    def build(self) -> Algorithm:
        k = self.k

        def init(ctx: AlgoContext, src):
            # current-degree state over the engine id space
            deg0 = jnp.asarray(ctx.degrees, jnp.int32)
            # foreachVertex: activate vertices with initial degree < k
            front0 = (deg0 < k) & ctx.is_real
            return front0, {"deg": deg0}

        def extract(state: StateT, ctx: AlgoContext):
            return (np.asarray(state["deg"]) >= k)[ctx.v2id]

        return dataclasses.replace(kcore_algorithm(k), init=init,
                                   extract=extract)
