"""Maximal Independent Set — Blelloch's Algorithm 2 (paper Sec. 4.3, 6.4).

MIS *requires* global synchronization for correctness: each round, live
vertices with no lower-labeled live neighbor join the set; then they and
their neighbors die. We run each round as two engine passes with a host
barrier between them — exactly the paper's synchronous mode (a fresh
worklist per phase; Sec. 4.3 "synchronous execution is a special case of
asynchronous execution"). Within a phase the min/any combiners are
commutative, so the engine's asynchrony is safe.

Determinism: labels are a fixed random permutation (fixed seed), matching
the paper's fixed-seed comparability setup.

Input graphs must be symmetrized. ``MIS(seed)`` is the query-object
entry point — it overrides ``Query.execute`` because of the host-level
barrier loop.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.api import Algorithm, Query
from repro.core.engine import Metrics

INF32 = np.int32(2 ** 30)


def _push_min_labels() -> Algorithm:
    return Algorithm(
        name="mis_phase1", key="minl", combine="min",
        apply=lambda st, vids, mask, deg: jnp.where(
            mask, st["label"][vids], INF32),
        edge_value=lambda msg: msg,
        activated=lambda old, new, deg: jnp.zeros_like(old, dtype=bool),
        priority=lambda st, deg: jnp.zeros_like(st["minl"]),
        on_process=None)


def _push_death_marks() -> Algorithm:
    return Algorithm(
        name="mis_phase2", key="mark", combine="add",
        apply=lambda st, vids, mask, deg: jnp.where(mask, 1, 0
                                                    ).astype(jnp.int32),
        edge_value=lambda msg: msg,
        activated=lambda old, new, deg: jnp.zeros_like(old, dtype=bool),
        priority=lambda st, deg: jnp.zeros_like(st["mark"]),
        on_process=None)


@dataclasses.dataclass(frozen=True)
class MIS(Query):
    """Maximal independent set on a symmetrized graph; ``result`` =
    bool[orig_num_vertices] membership, ``metrics`` summed over every
    phase of every round. Overrides ``execute`` — the round structure
    needs host barriers between engine passes."""

    seed: int = 0

    def execute(self, session):
        engine, ctx = session.engine, session.ctx
        V = ctx.V
        rng = np.random.default_rng(self.seed)
        label = np.full(V, INF32, dtype=np.int32)
        is_real = np.asarray(ctx.is_real)
        real_ids = np.where(is_real)[0]
        label[real_ids] = rng.permutation(
            real_ids.shape[0]).astype(np.int32)

        live = is_real.copy()
        in_mis = np.zeros(V, dtype=bool)
        total: Metrics | None = None
        phase_traces: list = []
        while live.any():
            # phase 1: live vertices advertise labels (min over live nbrs)
            st1, m1, t1 = engine.run(
                _push_min_labels(), live,
                {"minl": np.full(V, INF32, np.int32), "label": label})
            minl = np.asarray(st1["minl"])
            new_mis = live & (label < minl)
            assert new_mis.any(), "MIS round must make progress"
            in_mis |= new_mis
            # phase 2 (after barrier): winners kill their neighborhoods
            st2, m2, t2 = engine.run(
                _push_death_marks(), new_mis,
                {"mark": np.zeros(V, np.int32), "label": label})
            mark = np.asarray(st2["mark"])
            live = live & ~new_mis & (mark == 0)
            total = m1 + m2 if total is None else total + m1 + m2
            phase_traces += [t1, t2]
        # multi-pass query: the RunResult trace contract (a dict iff
        # cfg.trace) is kept by nesting the per-engine-pass traces
        trace = {"phases": phase_traces} if engine.cfg.trace else None
        return session._wrap(self, in_mis[ctx.v2id],
                             {"in_mis": in_mis, "label": label},
                             total, trace)
