"""User-facing algorithm API (paper Sec. 4.6).

The paper exposes ``foreachVertex`` / ``asyncRun`` / ``syncRun`` with
user-defined ``apply`` and ``propagation`` callbacks executed under
sequential consistency (Sec. 4.4): correctness requires only that state
updates are commutative atomic read-modify-writes. In the vectorized JAX
engine those updates are expressed as a *combiner* (``min`` or ``add``
scatter-reduce), which is exactly the class of CAS/fetch-sub loops used by
every algorithm in the paper — see DESIGN.md for the equivalence argument.

An :class:`Algorithm` bundles:

  * ``state``        initial vertex-state pytree (dict of [V'] arrays),
  * ``key``          which state array receives the scatter-combine,
  * ``combine``      'min' or 'add',
  * ``apply``        per-source message (Alg. 1 line 7), called with
                     ``(state, vids, mask, degs)``,
  * ``edge_value``   per-edge candidate from the message (propagation),
  * ``on_process``   state mutation for processed sources (e.g. PPR's
                     residual consumption), called with
                     ``(state, processed)`` before the scatter,
  * ``activated``    activation predicate from (old, new) key values —
                     the batched equivalent of ``propagation`` returning a
                     positive priority (Alg. 1 lines 13-15),
  * ``priority``     per-vertex scheduling priority (higher = sooner),
  * ``init``         builds the initial ``(frontier, state)`` from an
                     :class:`AlgoContext` and the query's init data (a
                     single-source query's ``source``, as an engine id)
                     in ``jax.numpy``, so that it runs inside a compiled
                     program — the algorithm owns its setup instead of
                     callers poking at engine internals,
  * ``extract``      reads the converged state back out in ORIGINAL
                     vertex ids (the user-facing result domain).

A self-describing Algorithm (``init`` + ``extract`` present) can be run
end-to-end by :class:`~repro.core.session.GraphSession`; user code
constructs a :class:`Query` object (``BFS(source)``, ``WCC()``, ...)
and never touches frontiers, reordered ids, or degree tables.

**Concurrent queries (PR 5):** a :class:`QueryBatch` bundles N
homogeneous queries — equal ``(name, params)``, e.g. multi-source BFS
or N-personalization PPR — for co-execution on the engine's
Q-stacked plane, where one block pull serves every query active in the
block. The batched init/extract hooks (:meth:`QueryBatch.init_batch` /
:meth:`QueryBatch.extract_batch`) default to *auto-lifting* the
members' single-query hooks along a leading Q axis (:func:`lift_init` /
:func:`lift_extract`); subclasses override them for vectorized setup
(see ``repro.algorithms.ppr.PPRBatch``). The per-vertex ``priority``
hook is auto-lifted inside the engine itself — it is applied to each
query's state slice in the Q-scan, so algorithms need no batched
variant.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp
import numpy as np

StateT = dict  # str -> jnp.ndarray of shape [V'] (+ scalars)


@dataclasses.dataclass(frozen=True)
class AlgoContext:
    """Everything an algorithm needs to set up and read out a run.

    All arrays live in the *engine* vertex domain (reordered entities
    followed by mini vertices, size ``V``); ``v2id`` maps original
    vertex ids into that domain so ``extract`` hooks can return results
    indexed by original id. ``GraphSession.ctx`` holds the engine's
    device tables themselves (``degrees``, ``is_real``) and the host's
    ``v2id``; inside a compiled program (:meth:`Engine.algo_ctx
    <repro.core.engine.Engine.algo_ctx>`) the two tables are the
    program's table argument and ``v2id`` is ``None``, so an ``init``
    hook reads ``V``, ``degrees``, ``is_real`` and ``orig_num_vertices``
    only. User code never reads ``engine.V`` / ``hg.v2id`` directly.
    """

    V: int                       # engine vertex-domain size (incl. virtual)
    degrees: Any                 # int32[V] out-degree (0 for virtual)
    is_real: Any                 # bool[V]  False for virtual duplicates
    v2id: np.ndarray | None      # int64[orig_num_vertices] -> engine id
    orig_num_vertices: int       # |V| of the input graph

    def engine_id(self, vertex: int) -> int:
        """Map an ORIGINAL vertex id to its engine id (asserts real)."""
        vid = int(self.v2id[vertex])
        assert vid >= 0, f"vertex {vertex} has no engine id"
        return vid


class QueryState:
    """Lifecycle of a submitted query handle.

    ``PENDING`` — submitted, waiting for capacity (queued);
    ``RUNNING`` — admitted into a live batch row (continuous service;
    the drain-style :class:`~repro.core.service.GraphService` jumps
    straight from PENDING to a terminal state);
    ``DONE`` — retired with a result;
    ``FAILED`` — rejected or errored (the handle carries the error).

    Plain string constants, not an enum: handle states print/compare
    as their names and serialize into benchmark JSON unchanged.
    """

    PENDING = "pending"
    RUNNING = "running"
    DONE = "done"
    FAILED = "failed"


class Query:
    """A first-class, reusable description of one graph computation.

    Subclasses (``BFS``, ``PPR``, ``WCC``, ...) are small frozen
    dataclasses holding user parameters; :meth:`build` turns them into a
    self-describing :class:`Algorithm` (init/extract hooks bound over
    the parameters). ``GraphSession.run(query)`` drives the default
    single-pass :meth:`execute`; multi-pass queries with host barriers
    (``MIS``) override ``execute`` instead.
    """

    def build(self) -> "Algorithm":
        raise NotImplementedError

    def execute(self, session) -> Any:  # -> repro.core.session.RunResult
        return session._run_spec(self, self.build())


@dataclasses.dataclass(frozen=True)
class Algorithm:
    name: str
    #: state array receiving the scatter-combine
    key: str
    #: 'min' or 'add'
    combine: str
    #: (state, vids[int32 L,Vm], mask[bool L,Vm], degs[int32 L,Vm])
    #: -> msgs [L,Vm] (key dtype)
    apply: Callable[[StateT, jnp.ndarray, jnp.ndarray, jnp.ndarray],
                    jnp.ndarray]
    #: (msg_per_edge) -> candidate value per edge
    edge_value: Callable[[jnp.ndarray], jnp.ndarray]
    #: (old_key[V'], new_key[V'], deg[V']) -> activated bool[V']
    activated: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    #: (state, deg[V']) -> int32 priority [V'] (higher scheduled first)
    priority: Callable[[StateT, jnp.ndarray], jnp.ndarray]
    #: optional consumption step: (state, processed bool[V']) -> state
    on_process: Callable[[StateT, jnp.ndarray], StateT] | None = None
    #: optional *windowed* priority: (state, vids[int32 ...],
    #: deg[...]) -> int32 priorities at exactly those vertex ids
    #: (``deg`` is the degree table gathered at ``vids``). When
    #: present, the incremental worklist refresh re-evaluates priority
    #: only inside the pulled lanes' vertex/edge windows (the only rows
    #: a tick can change) instead of recomputing ``priority`` over all
    #: V vertices every tick. Must satisfy ``priority_at(state, vids,
    #: deg[vids]) == priority(state, deg)[vids]`` elementwise — the
    #: ``check_refresh`` witness compares the maintained per-vertex
    #: priorities against the full reduction every tick
    priority_at: Callable[[StateT, jnp.ndarray, jnp.ndarray],
                          jnp.ndarray] | None = None
    #: schedule-independence declaration for the aggregated batch plane
    #: (``EngineConfig.batch_mode='aggregated'``). ``None`` derives the
    #: default: monotone min-combiner relaxations without an
    #: ``on_process`` mutation converge to one fixed point under ANY
    #: pull order (the GraphMP/DFOGraph shared-scan argument), so they
    #: are eligible; everything else is not. An algorithm whose add
    #: combiner is nevertheless exact-and-once (integer constant
    #: messages fired by a monotone crossing predicate, e.g. k-core's
    #: fetchSub) opts in explicitly with ``True``; a min-combiner whose
    #: hooks smuggle in schedule dependence opts out with ``False``
    schedule_independent: bool | None = None
    #: every value the callbacks close over (e.g. PPR's alpha/r_max) must
    #: appear here (or be folded into ``name``): the engine's compile
    #: cache keys on ``(name, params, cfg)``, so omitting a parameter
    #: silently reuses another instance's compiled tick. ``init`` is a
    #: callback too: the service compiles it into its ``admit``
    #: program, so two setups (PPR's point start, PageRank's uniform
    #: one) need two names
    params: tuple = ()
    #: (ctx, src) -> (frontier bool[V], state dict) — algorithm-owned
    #: setup, written in ``jax.numpy`` so that it traces: the service
    #: runs it inside its compiled ``admit`` (``ctx`` then holds the
    #: program's table arguments), ``GraphSession`` calls it on the
    #: device tables. ``src`` is the init data :meth:`init_data` gives:
    #: the source's engine id as a traced int32 scalar, or ``None`` for
    #: a whole-graph query. It is outside the compile-cache key, so
    #: queries differing only in it (BFS sources) share one compilation
    init: Callable[[AlgoContext, Any], tuple[Any, StateT]] | None = None
    #: (state, ctx) -> user-facing result in ORIGINAL vertex ids
    extract: Callable[[StateT, AlgoContext], Any] | None = None
    #: ORIGINAL id of the vertex a single-source query starts from;
    #: ``None`` declares a whole-graph query, whose ``init`` takes no data
    source: int | None = None

    def init_data(self, ctx: AlgoContext) -> np.int32 | None:
        """``init``'s second argument: the source's engine id as one
        int32, or ``None`` for a whole-graph query — all that reaches
        the device to start a query."""
        if self.source is None:
            return None
        return np.int32(ctx.engine_id(self.source))

    def neutral(self, dtype) -> jnp.ndarray:
        if self.combine == "min":
            return jnp.array(jnp.iinfo(dtype).max if
                             jnp.issubdtype(dtype, jnp.integer)
                             else jnp.inf, dtype=dtype)
        if self.combine == "add":
            return jnp.array(0, dtype=dtype)
        raise ValueError(f"unknown combiner {self.combine}")


# ----------------------------------------------------------------------
# concurrent query plane: QueryBatch + batched-hook auto-lifting
# ----------------------------------------------------------------------

def aggregation_eligible(algo: Algorithm) -> bool:
    """Can a batch of this algorithm run on the AGGREGATED plane?

    The aggregated plane executes one merged pull order for all Q
    queries, so per-query schedules differ from solo runs by design;
    only algorithms whose fixed point is *schedule-independent* may use
    it. The default test is ``combine == 'min' and on_process is None``
    — asynchronous monotone relaxation (BFS/WCC) reaches the same fixed
    point under any block order. ``Algorithm.schedule_independent``
    overrides in either direction (k-core's integer fetchSub opts in;
    see the field docstring). PPR/PageRank's f32 forward push is
    schedule-dependent even in exact arithmetic and stays on the
    per-query plane — :class:`~repro.core.session.GraphSession` falls
    back transparently, :meth:`~repro.core.engine.Engine.run_batch`
    refuses loudly.
    """
    if algo.schedule_independent is not None:
        return bool(algo.schedule_independent)
    return algo.combine == "min" and algo.on_process is None

def lift_init(algos: list[Algorithm],
              ctx: AlgoContext) -> tuple[Any, StateT]:
    """Auto-lift per-query ``init`` hooks into the batched init surface.

    Runs every algorithm's own ``init(ctx, init_data)`` and stacks the
    results along a leading Q axis: ``(frontier bool[Q, V], state {k:
    [Q, V]})`` — exactly the per-query arrays a solo run would start
    from, so the batch plane's solo-equivalence contract starts from
    identical inputs.
    """
    pairs = [a.init(ctx, a.init_data(ctx)) for a in algos]
    keys = set(pairs[0][1])
    if any(set(s) != keys for _, s in pairs):
        raise ValueError("batch members disagree on state keys")
    front = jnp.stack([f for f, _ in pairs])
    state = {k: jnp.stack([s[k] for _, s in pairs]) for k in pairs[0][1]}
    return front, state


def lift_extract(algos: list[Algorithm], states: StateT,
                 ctx: AlgoContext) -> list:
    """Auto-lift per-query ``extract`` hooks over [Q, V]-stacked state:
    each algorithm reads its own row, returning per-query results in
    ORIGINAL vertex ids (the solo extract applied to the solo-identical
    state slice)."""
    return [a.extract({k: v[i] for k, v in states.items()}, ctx)
            for i, a in enumerate(algos)]


@dataclasses.dataclass(frozen=True)
class QueryBatch(Query):
    """N homogeneous queries co-executed on the engine's concurrent
    plane (one compiled tick, one loop, cross-query shared I/O).

    Homogeneous means equal ``(name, params)`` — multi-source BFS, or N
    PPR personalizations sharing ``(alpha, r_max)`` (the paper's
    per-user workload). Queries differing only in init data batch
    together because the init data is outside the engine's compile key.
    Heterogeneous submissions belong on
    :class:`~repro.core.service.GraphService`, which groups by key and
    drains one batch per group.

    ``session.run(QueryBatch([...]))`` returns a
    :class:`~repro.core.session.BatchResult`: per-query ``RunResult``s
    (bit-identical to solo runs) plus aggregate metrics whose
    ``io_blocks`` counts each physically-read block once.

    **Routing (PR 6):** under ``EngineConfig.batch_mode='aggregated'``
    a batch whose algorithm is :func:`aggregation_eligible`
    (schedule-independent min-combiner fixed points: BFS/WCC/KCore)
    runs on the aggregated plane — ONE merged pull order and one
    executor pass per pulled block serving all Q queries, same fixed
    point but not the solo schedule. Ineligible batches (``add``
    combiners: PPR/PageRank) transparently fall back to the per-query
    plane, keeping their bit-identical-to-solo contract;
    ``BatchResult.batch_mode`` records which plane actually ran.
    """

    queries: tuple[Query, ...]

    def __post_init__(self):
        object.__setattr__(self, "queries", tuple(self.queries))
        if not self.queries:
            raise ValueError("QueryBatch needs at least one query")

    def build_batch(self) -> list[Algorithm]:
        """Build every member's Algorithm and enforce homogeneity."""
        algos = []
        for q in self.queries:
            if type(q).execute is not Query.execute:
                raise ValueError(
                    f"{type(q).__name__} overrides Query.execute "
                    "(multi-pass / host barriers) and cannot join a "
                    "QueryBatch; run it solo or submit it to a "
                    "GraphService, which drains it outside the batch")
            algos.append(q.build())
        a0 = algos[0]
        for q, a in zip(self.queries, algos):
            if (a.name, a.params) != (a0.name, a0.params):
                raise ValueError(
                    "QueryBatch members must share one compiled tick "
                    f"(equal (name, params)); got {(a0.name, a0.params)}"
                    f" vs {(a.name, a.params)} from {q!r}. Batch "
                    "per-parameter groups separately (GraphService "
                    "does this automatically)")
            if a.init is None or a.extract is None:
                raise ValueError(
                    f"algorithm {a.name!r} is not self-describing "
                    "(init/extract hooks required for batching)")
        return algos

    # ---- batched hooks (override for vectorized setup/readout) -------
    def init_batch(self, algos: list[Algorithm],
                   ctx: AlgoContext) -> tuple[Any, StateT]:
        """Batched init: default auto-lifts the single-query hooks."""
        return lift_init(algos, ctx)

    def extract_batch(self, algos: list[Algorithm], states: StateT,
                      ctx: AlgoContext) -> list:
        """Batched extract: default auto-lifts the single-query hooks."""
        return lift_extract(algos, states, ctx)

    def execute(self, session):  # -> repro.core.session.BatchResult
        return session._run_batch(self)
