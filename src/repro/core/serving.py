"""ContinuousService: an always-on serving loop with mid-flight admission.

``GraphService.drain()`` is a batch window: submissions queue until the
caller drains, every grouped query starts together, and the service is
idle between windows. A serving deployment has neither luxury — queries
arrive while others are half-done, and the p99 a user sees includes the
time their query sat waiting for a window to open. ``ContinuousService``
closes that gap with an open-ended host loop that NEVER drains:

    svc = ContinuousService(graph, EngineConfig(pool_slots=64))
    h0 = svc.submit(BFS(source=7))     # admitted at the next tick
    svc.step(); svc.step()             # ... traffic keeps arriving ...
    h1 = svc.submit(BFS(source=3))     # joins h0's RUNNING batch
    svc.run_until_idle()               # or keep stepping forever
    h1.result().result                 # bit-identical to a solo run

Three mechanisms, one loop:

**Mid-flight admission.** Queries with one compiled-tick key
``(name, params)`` share a *group*: a Q-capacity engine carry whose rows
are independent in-flight queries. A new query joins a RUNNING group at
the next tick boundary via :meth:`Engine.service_fns`'s ``admit`` — its
row becomes the solo tick-0 carry verbatim (per-query plane), so every
tick it subsequently takes is the solo tick body on the solo carry and
the result is bit-identical to ``session.run`` *no matter when it was
admitted*. The row's start is built inside that program: ``admit`` runs
the algorithm's ``init`` on the device tables and the query's init data
(:meth:`Algorithm.init_data`, the source's engine id), so admitting a
query sends the device one int32, not a V-long frontier and state
(``stats()["admit_host_bytes"]`` counts what admissions and resets
send). On the aggregated plane only the per-query leaves are
replaced and the newcomer's frontier blocks are re-activated against the
shared schedule's cross-query refcount — the running pull order absorbs
the new worklist without restarting. Retirement is the reverse edge:
the moment a row's liveness flag drops, the device slices out the row's
state and counters (``service_fns``'s ``extract``), the host copies that
one row and builds a full :class:`~repro.core.session.RunResult`,
resolves the handle, and kills the row with ``retire``, one dispatch
that makes the dead row on the device — the service keeps ticking
throughout.

**Capacity ladder.** The compiled step is shaped ``[Q, ...]``, so Q is a
compile-time constant. Capacities move on a power-of-two ladder
(``service_fns`` caches per capacity): admission beyond the current
capacity doubles it, retirement below half of it halves it. A resize is
an eager tree op — a fresh ``carry0()`` of newQ dead rows, made on the
device, with the live rows gathered
in (aggregated: only :attr:`Engine.AGG_PER_QUERY_KEYS` leaves move; the
ONE shared control plane, including block states and pool occupancy,
carries through untouched) — so each capacity compiles exactly once and
steady-state traffic at a given capacity never recompiles. Note the
aggregated plane under ``pool_mode='per_query'`` budgets ``Q x
pool_slots``: shrinking the ladder shrinks the budget, and a transiently
over-budget ``used_slots`` simply stalls new preloads until retirements
release slots — the counting-semaphore pool makes that safe.

**Heterogeneous co-execution.** Different algorithms cannot share one
compiled tick, but they CAN share the host loop and the device budget:
every :meth:`step` advances each live group one engine tick in rotating
order, so a long PPR and a burst of BFS queries make progress in the
same service-tick window. ``ServeConfig.service_pool_slots`` caps the
summed pool occupancy the loop will schedule past (a cross-group
residency budget; at least one group always advances so pending work
never hits an idle barrier), and ``max_groups_per_tick`` bounds how many
groups advance per tick (the rotation keeps it fair).

**Latency SLOs.** The service clock counts :meth:`step` calls; each
handle is stamped at submit / admit / retire, making
``retire_tick - submit_tick`` the modeled end-to-end latency in ticks
(queue wait + execution). :meth:`stats` reports p50/p99 and — when the
session has an :class:`~repro.io_sim.SSDModel` — seconds and modeled
qps via ``tick_seconds``. ``idle_barrier_ticks`` counts ticks where
work was pending but nothing advanced; the loop's contract is that it
stays 0 (asserted by ``benchmarks/bench_service.py``'s CI gate).

Multi-pass queries that override ``Query.execute`` (``MIS``) need host
barriers between engine passes and cannot join the continuous loop;
``submit`` rejects them — route those through ``GraphService.drain``.

**Profiler spans.** A :meth:`~ContinuousService.step` records host spans
(``jax.profiler.TraceAnnotation``, free while no trace runs) on the
profiler's clock: ``serve.admit`` (with ``serve.resize`` inside when the
ladder moves), ``serve.advance`` holding ``serve.dispatch`` (the compiled
step's call) and ``serve.flags`` (the blocking read of the liveness
flags), and ``serve.retire`` holding ``serve.extract`` (a retired row's
slice on the device, its one copy to the host, and its result) and
``serve.kill`` (the row's reset), so a trace puts each stretch of device
idle down to the host code that spent it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import numpy as np

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.api import (Algorithm, Query, QueryBatch, QueryState,
                            aggregation_eligible)
from repro.core.engine import (TRACE_LEN, Engine, Metrics,
                               raise_if_stalled)
from repro.core.service import QueryHandle
from repro.core.session import GraphSession, RunResult


def _ladder(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the capacity ladder rung."""
    cap = 1
    while cap < n:
        cap *= 2
    return cap


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Host-loop knobs (the SLO levers; engine knobs stay in
    :class:`~repro.core.engine.EngineConfig`).

    ``max_capacity`` bounds a group's row count — arrivals beyond it
    queue (admission latency becomes visible in ``p99``), which is the
    knob trading compile footprint + per-tick cost against queue wait.
    ``service_pool_slots`` is the cross-group residency budget for
    heterogeneous co-execution (0 = unlimited); ``max_groups_per_tick``
    rations the host loop itself (0 = advance every live group).
    ``shrink=False`` pins capacities at their high-water mark, trading
    memory for zero down-ladder churn under bursty traffic.
    """

    max_capacity: int = 16
    initial_capacity: int = 2
    shrink: bool = True
    service_pool_slots: int = 0
    max_groups_per_tick: int = 0

    def __post_init__(self):
        if self.initial_capacity < 1 or self.max_capacity < 1:
            raise ValueError("capacities must be >= 1")
        if self.initial_capacity > self.max_capacity:
            raise ValueError(
                f"initial_capacity={self.initial_capacity} exceeds "
                f"max_capacity={self.max_capacity}")


class _Group:
    """One compiled-tick cohort: a Q-capacity carry whose rows are
    independent in-flight queries of one ``(name, params)`` key."""

    __slots__ = ("key", "algo", "mode", "capacity", "carry", "rows",
                 "algos", "pending", "used_slots", "fns")

    def __init__(self, key, algo: Algorithm, mode: str):
        self.key = key
        self.algo = algo          # representative (first admitted)
        self.mode = mode
        self.capacity = 0
        self.carry = None
        self.rows: list[QueryHandle | None] = []
        self.algos: list[Algorithm | None] = []  # each row's built algo
        self.pending = np.zeros(0, bool)   # last step's liveness
        self.used_slots = 0                # last step's pool occupancy
        self.fns: dict | None = None

    @property
    def live(self) -> int:
        return sum(h is not None for h in self.rows)

    def free_slot(self) -> int | None:
        for q, h in enumerate(self.rows):
            if h is None:
                return q
        return None


class ContinuousService:
    """Always-on query service over one :class:`GraphSession`.

    Accepts a ready session or the same graph+config construction
    arguments as :class:`GraphSession` / :class:`GraphService`. The
    plane each group runs on follows the session config exactly as
    batch runs do: ``batch_mode='aggregated'`` puts schedule-independent
    groups on the merged plane, everything else on the per-query plane.
    """

    def __init__(self, graph_or_session: Any = None, cfg=None,
                 serve: ServeConfig | None = None, **kw):
        if isinstance(graph_or_session, GraphSession):
            if cfg is not None or kw:
                raise ValueError(
                    "pass either a ready GraphSession or graph+config "
                    "arguments, not both")
            self.session = graph_or_session
        else:
            self.session = GraphSession(graph_or_session, cfg, **kw)
        self.serve = serve if serve is not None else ServeConfig()
        #: service clock — one unit per :meth:`step` (== one engine tick
        #: per advanced group); handle ``*_tick`` stamps live on it
        self.clock = 0
        self._groups: dict[tuple, _Group] = {}
        self._queue: list[tuple[QueryHandle, Algorithm]] = []
        self._undrained: list[QueryHandle] = []
        self._latencies: list[int] = []       # retire - submit, ticks
        self._queue_waits: list[int] = []     # admit - submit, ticks
        # ---- counters surfaced by stats() ----------------------------
        self.submitted = 0
        self.completed = 0
        self.midflight_admissions = 0
        self.idle_barrier_ticks = 0       # contract: stays 0
        self.throttled_group_ticks = 0
        self.resizes = 0
        self.peak_capacity = 0
        self.peak_service_slots = 0
        self.retire_host_bytes = 0        # bytes retirement copied out
        self.admit_host_bytes = 0         # bytes admission/reset sent in

    # ------------------------------------------------------------------
    @property
    def pending(self) -> int:
        """Queries submitted but not yet retired (queued + running)."""
        return len(self._queue) + sum(g.live for g in
                                      self._groups.values())

    def submit(self, query: Query) -> QueryHandle:
        """Enqueue one query for admission at the next tick boundary."""
        if isinstance(query, QueryBatch):
            raise ValueError(
                "submit the member queries individually; the service "
                "groups equal-key queries into running batches itself")
        if type(query).execute is not Query.execute:
            raise ValueError(
                f"{type(query).__name__} overrides Query.execute "
                "(multi-pass, host barriers between engine passes) and "
                "cannot join the continuous loop; run it through "
                "GraphService.drain or session.run")
        algo = query.build()
        if algo.init is None or algo.extract is None:
            raise ValueError(
                f"algorithm {algo.name!r} is not self-describing "
                "(needs init and extract hooks) — run it via engine.run")
        handle = QueryHandle(query)
        handle.submit_tick = self.clock
        self._queue.append((handle, algo))
        self._undrained.append(handle)
        self.submitted += 1
        return handle

    # ------------------------------------------------------------------
    def step(self) -> list[QueryHandle]:
        """One service tick: admit what fits, advance every live group
        one engine tick (rotating order, budget permitting), retire
        converged rows. Returns the handles retired this tick."""
        busy = any(g.live for g in self._groups.values())
        self._admit_queued(busy)
        retired = self._advance()
        self.clock += 1
        out = []
        with TraceAnnotation("serve.retire"):
            for g in list(self._groups.values()):
                out.extend(self._retire_rows(g, retired.get(g.key, ())))
                if self.serve.shrink:
                    self._maybe_shrink(g)
        return out

    def run_until_idle(self, max_ticks: int = 1_000_000) -> None:
        """Step until no query is queued or running."""
        start = self.clock
        while self.pending:
            if self.clock - start >= max_ticks:
                raise RuntimeError(
                    f"service not idle after {max_ticks} ticks "
                    f"({self.pending} queries still pending)")
            self.step()

    def drain(self) -> list[RunResult]:
        """Migration shim for ``GraphService.drain()``: run until idle
        and return results for every query submitted since the last
        drain, in submission order. Unlike the drain-style service,
        queries submitted *during* the run (from admission callbacks or
        other threads stepping the loop) still join mid-flight."""
        order = list(self._undrained)
        self._undrained = []
        self.run_until_idle()
        return [h.result() for h in order]

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def _group_for(self, algo: Algorithm) -> _Group:
        key = (algo.name, algo.params)
        g = self._groups.get(key)
        if g is None:
            cfg = self.session.cfg
            mode = "aggregated" if (cfg.batch_mode == "aggregated"
                                    and aggregation_eligible(algo)) \
                else "per_query"
            g = _Group(key, algo, mode)
            self._groups[key] = g
        return g

    @functools.partial(jax.profiler.annotate_function, name="serve.admit")
    def _admit_queued(self, busy: bool) -> None:
        still = []
        for handle, algo in self._queue:
            g = self._group_for(algo)
            if g.carry is None:
                # ladder rungs are powers of two clipped to the user's
                # max — a non-pow2 max_capacity is honored exactly
                cap = min(_ladder(self.serve.initial_capacity),
                          self.serve.max_capacity)
                self._resize(g, [], cap, algo)
            slot = g.free_slot()
            if slot is None:
                if g.capacity < self.serve.max_capacity:
                    self._resize(g, list(range(g.capacity)),
                                 min(g.capacity * 2,
                                     self.serve.max_capacity),
                                 algo)
                    slot = g.free_slot()
                else:
                    still.append((handle, algo))  # capacity SLO: queue
                    continue
            # ``busy`` is the service state BEFORE this boundary: a
            # cohort admitted into an idle service starts together and
            # is not mid-flight; joining work already running is
            self._admit(g, slot, handle, algo, busy)
        self._queue = still

    def _admit(self, g: _Group, slot: int, handle: QueryHandle,
               algo: Algorithm, busy: bool) -> None:
        src = algo.init_data(self.session.ctx)
        self.admit_host_bytes += 0 if src is None else src.nbytes
        g.carry = g.fns["admit"](g.carry, slot, src)
        g.rows[slot] = handle
        g.algos[slot] = algo
        g.pending[slot] = True
        handle.state = QueryState.RUNNING
        handle.admit_tick = self.clock
        self._queue_waits.append(self.clock - handle.submit_tick)
        if busy:
            self.midflight_admissions += 1

    # ------------------------------------------------------------------
    # the tick
    # ------------------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function,
                       name="serve.advance")
    def _advance(self) -> dict:
        """Advance live groups one engine tick each; returns
        ``{group key: row indices retired by this tick}``."""
        order = [g for g in self._groups.values() if g.live]
        if not order:
            if self._queue:
                # nothing advanced with work pending — contract says
                # this cannot happen (an empty group admits instantly)
                self.idle_barrier_ticks += 1
            return {}
        # rotate so budget/ration cuts land on a different group each
        # tick — round-robin fairness across heterogeneous algorithms
        r = self.clock % len(order)
        order = order[r:] + order[:r]
        budget = self.serve.service_pool_slots
        ration = self.serve.max_groups_per_tick
        used_total = sum(g.used_slots for g in order)
        retired: dict = {}
        advanced = 0
        for g in order:
            over_budget = budget and used_total >= budget
            over_ration = ration and advanced >= ration
            if advanced and (over_budget or over_ration):
                self.throttled_group_ticks += 1
                continue
            before = g.used_slots
            with TraceAnnotation("serve.dispatch"):
                carry, *flags = g.fns["step"](g.carry)
            g.carry = carry
            with TraceAnnotation("serve.flags"):
                # one device-to-host round trip for the tick's three flags
                pending, used, stalled = jax.device_get(flags)
                pend = np.array(pending)  # writable host copy
                raise_if_stalled(g.algo, self.session.cfg, stalled)
            g.used_slots = int(used)
            used_total += g.used_slots - before
            advanced += 1
            done = [q for q, h in enumerate(g.rows)
                    if h is not None and g.pending[q] and not pend[q]]
            g.pending = pend
            if done:
                retired[g.key] = done
        self.peak_service_slots = max(self.peak_service_slots,
                                      used_total)
        return retired

    # ------------------------------------------------------------------
    # retirement
    # ------------------------------------------------------------------
    def _retire_rows(self, g: _Group, slots) -> list[QueryHandle]:
        out = []
        for q in slots:
            handle = g.rows[q]
            with TraceAnnotation("serve.extract"):
                result = self._extract_row(g, q, handle)
            with TraceAnnotation("serve.kill"):
                self._kill_row(g, q)
            handle.retire_tick = self.clock
            handle._resolve(result)
            self._latencies.append(handle.latency_ticks)
            self.completed += 1
            out.append(handle)
        return out

    def _extract_row(self, g: _Group, q: int,
                     handle: QueryHandle) -> RunResult:
        # row q is sliced on the device and copied in one transfer
        row = jax.device_get(g.fns["extract"](g.carry, q))
        self.retire_host_bytes += sum(
            a.nbytes for a in jax.tree_util.tree_leaves(row))
        state = row["state"]
        names = sorted(g.carry["counters"])   # extract's counter order
        metrics = Metrics(**{k: (int(hi) << 32) | int(lo)
                             for k, (hi, lo) in zip(names, row["counters"])})
        trace = None
        if "trace" in row:
            # aggregated-plane traces describe the ONE shared schedule,
            # not this row — only the per-query plane has a row trace
            trace = {k: v[:min(metrics.ticks, TRACE_LEN)]
                     for k, v in row["trace"].items()}
        algo = g.algos[q] or g.algo
        extracted = algo.extract(state, self.session.ctx)
        return self.session._wrap(handle.query, extracted, state,
                                  metrics, trace)

    def _kill_row(self, g: _Group, q: int) -> None:
        # one dispatch, no host array: the dead row is made on the device
        g.carry = g.fns["retire"](g.carry, q)
        g.rows[q] = None
        g.algos[q] = None
        g.pending[q] = False

    # ------------------------------------------------------------------
    # capacity ladder
    # ------------------------------------------------------------------
    def _maybe_shrink(self, g: _Group) -> None:
        if g.carry is None:
            return
        target = max(_ladder(g.live),
                     _ladder(self.serve.initial_capacity))
        if target < g.capacity:
            perm = [q for q, h in enumerate(g.rows) if h is not None]
            self._resize(g, perm, target, g.algo)

    @functools.partial(jax.profiler.annotate_function, name="serve.resize")
    def _resize(self, g: _Group, perm: list[int], newcap: int,
                algo: Algorithm) -> None:
        """Move ``g`` to capacity ``newcap``, gathering the live rows in
        ``perm`` into the low slots of a fresh carry. Grow passes the
        identity perm; shrink passes the surviving rows' indices."""
        fns = self.session.engine.service_fns(algo, newcap, g.mode)
        fresh = fns["carry0"]()
        if g.carry is not None and perm:
            idx = jnp.asarray(np.asarray(perm, np.int32))
            k = len(perm)
            move = lambda fl, ol: fl.at[:k].set(ol[idx])
            if g.mode == "aggregated":
                pq = set(Engine.AGG_PER_QUERY_KEYS)
                carry = {}
                for name, leaf in fresh.items():
                    if name in pq:
                        carry[name] = jax.tree_util.tree_map(
                            move, leaf, g.carry[name])
                    else:
                        # the ONE shared control plane (block states,
                        # pool occupancy, clock, trace) survives the
                        # resize untouched — resident blocks stay hot
                        carry[name] = g.carry[name]
            else:
                carry = jax.tree_util.tree_map(move, fresh, g.carry)
        else:
            carry = fresh
        g.carry = carry
        g.fns = fns
        g.capacity = newcap
        old_rows, old_algos, old_pending = g.rows, g.algos, g.pending
        pad = [None] * (newcap - len(perm))
        g.rows = [old_rows[q] for q in perm] + pad
        g.algos = [old_algos[q] for q in perm] + pad
        pend = np.zeros(newcap, bool)
        pend[:len(perm)] = [bool(old_pending[q]) for q in perm]
        g.pending = pend
        self.resizes += 1
        self.peak_capacity = max(self.peak_capacity, newcap)

    # ------------------------------------------------------------------
    # SLO surface
    # ------------------------------------------------------------------
    def latency_percentiles(self, pcts=(50, 99)) -> dict:
        """Modeled latency percentiles over retired queries, in service
        ticks (submit → retire: queue wait + execution)."""
        if not self._latencies:
            return {f"p{p}": None for p in pcts}
        arr = np.asarray(self._latencies, dtype=np.int64)
        return {f"p{p}": float(np.percentile(arr, p)) for p in pcts}

    def stats(self) -> dict:
        """Serving counters + SLO summary (JSON-friendly scalars)."""
        d = dict(clock=self.clock,
                 submitted=self.submitted,
                 completed=self.completed,
                 queued=len(self._queue),
                 running=sum(g.live for g in self._groups.values()),
                 groups=len(self._groups),
                 midflight_admissions=self.midflight_admissions,
                 idle_barrier_ticks=self.idle_barrier_ticks,
                 throttled_group_ticks=self.throttled_group_ticks,
                 resizes=self.resizes,
                 peak_capacity=self.peak_capacity,
                 peak_service_slots=self.peak_service_slots,
                 retire_host_bytes=self.retire_host_bytes,
                 admit_host_bytes=self.admit_host_bytes)
        pct = self.latency_percentiles()
        d["latency_ticks_p50"] = pct["p50"]
        d["latency_ticks_p99"] = pct["p99"]
        d["queue_wait_ticks_mean"] = (
            float(np.mean(self._queue_waits))
            if self._queue_waits else None)
        ssd = self.session.ssd
        if ssd is not None:
            ts = ssd.tick_seconds
            d["tick_seconds"] = ts
            for k in ("latency_ticks_p50", "latency_ticks_p99"):
                sk = k.replace("ticks", "seconds")
                d[sk] = None if d[k] is None else d[k] * ts
            d["qps"] = (self.completed / (self.clock * ts)
                        if self.clock else None)
        return d
