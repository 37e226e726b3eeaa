"""GraphSession: the single stable query surface over the engine.

The paper's user API (Sec. 4.6) is ``foreachVertex`` + ``asyncRun`` /
``syncRun``; systems like GraphMP and GraphD keep the vertex-program /
runner split behind one engine facade so user code never handles
frontiers, reordered vertex ids, or engine tables. ``GraphSession`` is
that facade here:

    session = GraphSession(graph, EngineConfig(pool_slots=64))
    res = session.run(BFS(source=0))          # -> RunResult
    res.result                                # distances, ORIGINAL ids
    res.metrics.io_blocks                     # exact engine counters
    res.modeled_runtime                       # SSD-model wall clock

A session owns the :class:`~repro.core.engine.Engine` (and therefore its
compile cache — ``run_many`` over queries with equal ``(name, params)``
reuses one compiled tick), the tick-domain
:class:`~repro.io_sim.device.DeviceModel` embedded in the config, and an
attached :class:`~repro.io_sim.ssd_model.SSDModel` that converts the
run's counters into ``RunResult.modeled_runtime``.

Every run returns a :class:`RunResult` with a fixed shape — callers
never branch on ``cfg.trace`` to learn a tuple arity, and never index
``state`` by reordered ids: ``result`` is already in original vertex
ids via the algorithm's ``extract`` hook.

**Concurrent queries (PR 5):** ``session.run(QueryBatch([...]))``
co-executes N homogeneous queries in one engine loop and returns a
:class:`BatchResult` — per-query ``RunResult``s bit-identical to solo
runs, with physical I/O deduplicated across the batch
(``metrics.io_blocks_shared``). ``run_many`` remains the sequential
baseline (back-to-back runs, no cross-query sharing). For mixed
workloads use :class:`~repro.core.service.GraphService`, which groups
submissions into batches by compiled-tick key and drains them.

**Aggregated batches (PR 6):** with
``EngineConfig(batch_mode="aggregated")`` the session routes
schedule-independent batches (BFS/WCC/KCore) to the engine's merged
plane — one pull order and one executor pass per block for the whole
batch, optionally one shared-capacity pool
(``pool_mode="shared"``) — and transparently falls back to the
per-query plane for add-combiner algorithms (PPR/PageRank), whose
results are schedule-dependent. ``BatchResult.batch_mode`` records
the plane that actually ran.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterable, Sequence

from repro.core.api import (AlgoContext, Algorithm, Query, QueryBatch,
                            aggregation_eligible)
from repro.core.engine import Engine, EngineConfig, Metrics, batch_totals
from repro.io_sim.ssd_model import SSDModel
from repro.storage.csr import CSRGraph
from repro.storage.hybrid import HybridGraph, build_hybrid


@dataclasses.dataclass
class RunResult:
    """Structured result of one query run.

    Replaces the ad-hoc per-wrapper tuple shapes (``(dis, m)`` vs
    ``(state, metrics, trace)`` vs ``(p, r, metrics)``) with one spelling.
    """

    query: Query                  # the query object that produced this
    result: Any                   # user-facing result, ORIGINAL vertex ids
    state: dict                   # raw final vertex state (engine domain)
    metrics: Metrics              # exact engine counters
    trace: dict | None            # per-tick pipeline trace iff cfg.trace
    modeled_runtime: float | None  # SSDModel wall-clock; None if no model
    config: EngineConfig          # SNAPSHOT of the config this ran under
    #                               (sweep/fork provenance; never aliases
    #                               the engine's live cfg attribute)


@dataclasses.dataclass
class BatchResult:
    """Result of one :class:`~repro.core.api.QueryBatch` co-execution.

    ``results[i]`` is the i-th member query's :class:`RunResult`. Under
    ``batch_mode="per_query"`` it is bit-identical (result, state,
    non-I/O counters) to a solo ``session.run`` of that query; under
    ``batch_mode="aggregated"`` (PR 6) it is *equivalent* — same fixed
    point and extract output, but the schedule (and therefore the
    schedule counters) is the batch's ONE merged pull order, shared by
    every member. ``metrics`` is the batch aggregate
    (:func:`~repro.core.engine.batch_totals`): on the per-query plane
    the per-query Metrics summed — ``io_blocks`` counts every
    physically-read block ONCE across the batch, ``io_blocks_shared``
    the submissions served from another query's resident copy, and
    ``io_blocks + io_blocks_shared`` equals the sum of the members'
    solo I/O, so the gap IS the cross-query worklist's saving. On the
    aggregated plane the shared-schedule counters are taken once (not
    summed Q-fold) and only the per-query work counters are summed.
    (Aggregate ``ticks`` sums per-query tick counts; the batch's
    wall-clock critical path is ``max`` over members.)

    ``batch_mode`` records the plane the batch ACTUALLY ran on:
    ``"per_query"`` may appear under an aggregated config when the
    algorithm is not schedule-independent (PPR/PageRank) and the
    session transparently fell back.
    """

    query: Query                  # the QueryBatch
    results: list[RunResult]
    metrics: Metrics
    config: EngineConfig          # snapshot, as in RunResult
    batch_mode: str = "per_query"  # effective execution plane

    def __iter__(self):
        return iter(self.results)

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, i) -> RunResult:
        return self.results[i]


class GraphSession:
    """Owns one graph + engine and runs :class:`Query` objects on it."""

    def __init__(self, graph: CSRGraph | HybridGraph,
                 cfg: EngineConfig | None = None, *,
                 ssd: SSDModel | None = None, delta_deg: int = 2,
                 partitioner: str = "lplf", block_edges: int | None = None,
                 _engine: Engine | None = None):
        """``graph`` may be a raw :class:`CSRGraph` (partitioned here via
        ``build_hybrid(delta_deg, partitioner, block_edges)``) or an
        already-built :class:`HybridGraph` (the build kwargs are then
        ignored). ``ssd`` attaches a performance model so every
        :class:`RunResult` carries ``modeled_runtime``. ``_engine`` is
        the :meth:`from_engine` adoption path."""
        if _engine is not None:
            self.hg = _engine.hg
            self.engine = _engine
        else:
            if isinstance(graph, HybridGraph):
                self.hg = graph
            else:
                kw = {} if block_edges is None \
                    else {"block_edges": block_edges}
                self.hg = build_hybrid(graph, delta_deg=delta_deg,
                                       partitioner=partitioner, **kw)
            self.engine = Engine(self.hg, cfg)
        self.ssd = ssd
        self._ctx: AlgoContext | None = None

    @classmethod
    def from_engine(cls, engine: Engine, *,
                    ssd: SSDModel | None = None) -> "GraphSession":
        """Wrap an existing engine (power users who hand-tune
        :class:`Engine` construction)."""
        return cls(engine.hg, ssd=ssd, _engine=engine)

    # ------------------------------------------------------------------
    @property
    def cfg(self) -> EngineConfig:
        return self.engine.cfg

    @property
    def device(self):
        """Tick-domain device model driving the I/O schedule."""
        return self.engine.device

    @property
    def ctx(self) -> AlgoContext:
        """The algorithm-facing view of this graph (built once): the
        engine's device tables and the host's id map."""
        if self._ctx is None:
            eng = self.engine
            self._ctx = dataclasses.replace(eng.algo_ctx(eng.tables),
                                            v2id=self.hg.v2id)
        return self._ctx

    @property
    def num_compiled(self) -> int:
        """Compile-cache entries (one per distinct (name, params, cfg))."""
        return len(self.engine._compiled)

    # ------------------------------------------------------------------
    def run(self, query: Query) -> RunResult:
        """Execute one query to convergence."""
        return query.execute(self)

    def run_many(self, queries: Iterable[Query]) -> list[RunResult]:
        """Run queries back-to-back on the shared engine: equal
        ``(name, params)`` queries reuse one compiled tick."""
        return [self.run(q) for q in queries]

    def fork(self, cfg: EngineConfig) -> "GraphSession":
        """Fresh engine over this session's (already-built) graph, same
        attached SSD model — the unit of a config grid. ``sweep`` and
        the benchmark harness's timed sweeps share this path."""
        return GraphSession.from_engine(Engine(self.hg, cfg),
                                        ssd=self.ssd)

    def sweep(self, query: Query,
              configs: Sequence[EngineConfig]) -> list[RunResult]:
        """Benchmark-style config grid: run ``query`` once per config on
        this session's graph (fresh engine per config; ``RunResult.config``
        records which point each result belongs to)."""
        return [self.fork(cfg).run(query) for cfg in configs]

    # ------------------------------------------------------------------
    def _run_spec(self, query: Query, algo: Algorithm) -> RunResult:
        """Single-pass execution of a self-describing Algorithm."""
        assert algo.init is not None, \
            f"algorithm {algo.name!r} has no init hook; use engine.run"
        frontier, state = algo.init(self.ctx, algo.init_data(self.ctx))
        out_state, metrics, trace = self.engine.run(algo, frontier, state)
        result = algo.extract(out_state, self.ctx) \
            if algo.extract is not None else out_state
        return self._wrap(query, result, out_state, metrics, trace)

    def _wrap(self, query: Query, result, state: dict, metrics: Metrics,
              trace: dict | None) -> RunResult:
        """Assemble a RunResult (multi-pass queries call this directly)."""
        modeled = self.ssd.modeled_runtime(metrics) \
            if self.ssd is not None else None
        # snapshot, not the live self.engine.cfg reference. EngineConfig
        # is frozen today, so the direct reference was safe in practice;
        # the copy pins sweep/fork provenance against cfg ever growing
        # mutable or cached state (cheap: one frozen-dataclass copy)
        return RunResult(query=query, result=result, state=state,
                         metrics=metrics, trace=trace,
                         modeled_runtime=modeled,
                         config=dataclasses.replace(self.engine.cfg))

    # ------------------------------------------------------------------
    def _run_batch(self, batch: QueryBatch,
                   algos: list[Algorithm] | None = None) -> BatchResult:
        """Co-execute a homogeneous QueryBatch on the engine's
        Q-stacked plane (one compiled tick, shared physical I/O).
        ``algos`` lets a caller that already built and validated the
        members' algorithms (``GraphService`` grouping) skip the
        rebuild; user-formed batches go through ``build_batch`` and
        its homogeneity checks."""
        if algos is None:
            algos = batch.build_batch()
        # effective-plane routing (PR 6): an aggregated config applies
        # only to schedule-independent algorithms; add-combiner batches
        # (PPR/PageRank) transparently fall back to the per-query plane
        # rather than erroring — BatchResult.batch_mode records which
        # plane actually ran
        mode = self.engine.cfg.batch_mode
        if mode == "aggregated" and not aggregation_eligible(algos[0]):
            mode = "per_query"
        fronts, states = batch.init_batch(algos, self.ctx)
        out_states, metrics, traces = self.engine.run_batch(
            algos[0], fronts, states, batch_mode=mode)
        extracted = batch.extract_batch(algos, out_states, self.ctx)
        results = [
            self._wrap(q, extracted[i],
                       {k: v[i] for k, v in out_states.items()},
                       metrics[i],
                       traces[i] if traces is not None else None)
            for i, q in enumerate(batch.queries)]
        return BatchResult(query=batch, results=results,
                           metrics=batch_totals(metrics, mode),
                           config=dataclasses.replace(self.engine.cfg),
                           batch_mode=mode)
