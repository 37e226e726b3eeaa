"""Block-centric asynchronous execution engine (paper Sec. 4).

The engine advances a deterministic *scheduler tick* inside a
``jax.lax.while_loop``; each tick models exactly the paper's pipeline:

  completions -> preload (async I/O submit, priority queue over uncached
  blocks, buffer-pool capacity) -> pull (cached-queue dominance, FIFO) ->
  batched executor processing (apply/propagation as scatter-combine) ->
  submit (frontier + block-state updates, resident-block *reuse*) ->
  finish (reactivated blocks re-enter the cached queue with NO extra I/O).

The tick is layered across three tiers, mirroring the paper's
architecture (Sec. 4.1):

  * :class:`~repro.core.scheduler.Scheduler` — block-state transitions,
    the preload priority queue, and pluggable cached-queue pull policies
    (``fifo`` / ``priority`` / ``lru`` / ``hybrid``);
  * :class:`~repro.core.pool.BufferPool` — slot accounting (admission,
    release, early-stop reuse eviction);
  * :class:`~repro.core.executor.ExecutorBackend` — batched
    apply/propagation; ``gather`` (searchsorted/gather expansion) and
    ``pallas`` (the TPU-native ``frontier_relax`` kernel) produce
    identical results.

Two exactness-preserving performance layers make per-tick cost
proportional to the blocks actually pulled rather than the worst block
in the graph (skew-proofing):

  * **bucketed tiling** (``EngineConfig.bucketing``): scheduling blocks
    partition into power-of-two size classes by vertex count and edge
    mass; each pulled lane routes through ``lax.switch`` to its class's
    ``(Vm, We, EK)`` tile instead of the global maxima — bit-identical
    state and counters, compat default off;
  * **incremental worklist refresh** (``EngineConfig.refresh``): the
    per-block active counts and priorities are maintained from the
    tick's lane windows (exact pulled-block rebuild + monotone
    destination scatter-max + a ``lax.cond`` full-rebuild guard)
    instead of re-reducing all V vertices twice per tick; sorted-order
    prefix-sum/segmented-scan reductions replace XLA's serial-scatter
    ``segment_*`` ops everywhere. ``check_refresh=True`` traces a
    per-tick incremental-vs-full mismatch count (always zero).

I/O time is *device-model-driven* (Sec. 4.5): at submit the
:class:`~repro.io_sim.device.DeviceModel` assigns each block a completion
deadline proportional to its span with bounded channel parallelism, so
bandwidth / queue-depth sweeps move the actual schedule. The default
:class:`~repro.io_sim.device.UniformDevice` (``io_latency`` ticks per
request) reproduces the constant-latency schedule bit-for-bit.

This module is the orchestrator: it threads the carry through the tiers
and owns only the frontier/submit step and the counters. All of the
paper's claims that we benchmark (read/work inflation, reuse, stalls)
come out of this loop's counters. Sequential consistency (Sec. 4.4)
holds because every algorithm's update is a commutative combiner; any
tick schedule is a valid sequential order. ``sync=True`` gives the
special-case synchronous mode of Sec. 4.3 (fresh worklist per
iteration).

The **concurrent query plane** (:meth:`Engine.run_batch`, PR 5) executes
Q independent queries of one algorithm inside a single loop: every
per-query carry gains a leading Q axis and the solo tick is mapped over
it, while the scheduler's cross-query worklist deduplicates the
queries' preload submissions — one physical read serves every query
with active vertices in the block (``Metrics.io_blocks_shared``), which
is the paper's "reuse active blocks in memory" claim lifted across
queries. Per-query results and counters stay bit-identical to solo runs
by construction.

The **aggregated batch plane** (PR 6, ``EngineConfig.batch_mode=
"aggregated"``) replaces the Q per-query schedules with ONE merged
schedule, legal for schedule-independent algorithms (min-combiners and
explicit opt-ins — see :func:`repro.core.api.aggregation_eligible`):
each tick the per-query worklist metadata is merged
(:meth:`~repro.core.scheduler.Scheduler.aggregate_worklist` — sum of
active counts, max of per-query-rebased priorities), each pulled block
is expanded ONCE against the Q-stacked state
(:meth:`~repro.core.executor.ExecutorBackend.execute_many`), and ONE
real buffer pool admits blocks for the whole batch —
``pool_mode="shared"`` caps batch peak residency at ``pool_slots``
(vs Q x ``pool_slots`` on the per-query plane), ``"per_query"`` keeps
the Q x capacity for memory-parity schedule comparisons. Batch compute
drops from O(Q·blocks) toward O(blocks) (``Metrics.block_passes``);
per-query results are *equivalent* to solo — same fixed point, same
extract output — but NOT bit-parity: the pull order is shared by
design, which is why add-combiner algorithms (PPR/PageRank) are
refused and routed to the per-query plane instead.

Mini vertices (deg <= delta_deg, Sec. 5.2) are grouped into pseudo-blocks
with zero I/O cost — they are always memory-resident, which is exactly the
hybrid storage architecture's point.

Counters are carried as (hi, lo) uint32 limb pairs — a true 64-bit
accumulator without flipping ``jax_enable_x64`` — so ``edges_scanned`` /
``io_blocks`` do not wrap on billion-edge runs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import AlgoContext, Algorithm, aggregation_eligible
from repro.core.executor import EXECUTORS, ExecTables, Tile, make_executor
from repro.core.pool import BufferPool
from repro.core.scheduler import (S_CACHED, S_LOADING, PullView,
                                  Scheduler, make_pull_policy)
from repro.io_sim.compute import ComputeModel
from repro.io_sim.device import DeviceModel, UniformDevice
from repro.storage.hybrid import HybridGraph, mini_offset

TRACE_LEN = 16384

_COUNTERS = ("io_ops", "io_blocks", "edges_scanned", "vertices_processed",
             "reuse_activations", "blocks_reused", "exec_idle_ticks",
             "io_active_ticks", "inflight_ticks", "barriers", "ticks",
             "block_passes", "peak_used_slots", "exec_busy_ticks")

#: batch-only counters: preload submissions served by another query's
#: resident / in-flight copy instead of new device traffic
_SHARED_COUNTERS = ("io_ops_shared", "io_blocks_shared")

#: counters that stay per-query under the AGGREGATED batch plane (their
#: increments come from each query's own frontier masks); every other
#: counter there describes the ONE shared schedule and is replicated
#: into each query's Metrics verbatim — see :func:`batch_totals`
_PER_QUERY_COUNTERS = ("edges_scanned", "vertices_processed",
                       "reuse_activations")


# ---- 64-bit counters as uint32 limb pairs ----------------------------

def _c64_zero():
    z = jnp.zeros((), jnp.uint32)
    return (z, z)


def _c64_add(c, inc):
    """Add a non-negative int32 increment with carry into the high limb."""
    hi, lo = c
    lo2 = lo + inc.astype(jnp.uint32)
    return (hi + (lo2 < lo).astype(jnp.uint32), lo2)


def _c64_int(c) -> int:
    return (int(c[0]) << 32) | int(c[1])


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    lanes: int = 4              # executor batch width (worker threads)
    prefetch: int = 8           # max async I/O submissions per tick
    queue_depth: int = 16       # io_uring-style in-flight cap
    pool_slots: int = 64        # buffer pool capacity in 4 KB units
    chunk_size: int = 256       # mini-vertex pseudo-block width
    cached_policy: str = "fifo"  # 'fifo' (paper) | 'priority' | 'lru'
    #                             | 'hybrid' (cost-aware priority x span)
    executor: str = "gather"    # 'gather' | 'pallas' (frontier_relax kernel)
    sync: bool = False          # Sec. 4.3 synchronous special case
    early_stop: int = 0         # consecutive-reuse eviction threshold (0=off)
    io_latency: int = 1         # uniform-device ticks (used iff device=None)
    device: DeviceModel | None = None  # span-proportional device time;
    #                             None = UniformDevice(io_latency), which
    #                             reproduces the pre-device schedule
    bucketing: int = 6          # executor tile buckets: N > 0 = at most
    #                             N power-of-two block size classes with
    #                             bucket-local tiles — bit-identical
    #                             results, per-tick cost proportional to
    #                             the blocks pulled (default since PR 5,
    #                             after a bench cycle confirmed the
    #                             tick-cost win); 0 = one global
    #                             (Vm, We, EK) tile, the escape hatch
    #                             reproducing the pre-bucketing lowering
    refresh: str = "incremental"  # worklist metadata maintenance:
    #                             'incremental' (delta reductions +
    #                             pulled-block rebuild, exact) | 'full'
    #                             (re-reduce all V vertices per tick)
    check_refresh: bool = False  # debug: per-tick incremental-vs-full
    #                             comparison, traced as refresh_mismatch
    batch_mode: str = "per_query"  # concurrent-query execution plane:
    #                             'per_query' (PR 5 compat: Q solo
    #                             schedules, bit-parity, shared I/O) |
    #                             'aggregated' (PR 6: ONE merged pull
    #                             order, one executor pass per block
    #                             serving all Q queries — equivalence,
    #                             not parity; schedule-independent
    #                             algorithms only)
    pool_mode: str = "per_query"  # aggregated-plane pool capacity:
    #                             'per_query' = Q x pool_slots (memory
    #                             parity with the per-query plane) |
    #                             'shared' = ONE pool_slots budget with
    #                             cross-query admission (batch peak
    #                             residency == a solo run's); requires
    #                             batch_mode='aggregated'
    compute: ComputeModel | None = None  # edge-mass-proportional
    #                             executor occupancy: pulls charge
    #                             ceil(edge_mass / edges_per_tick)
    #                             busy ticks that gate further pulls
    #                             (I/O keeps flowing underneath). None
    #                             = the legacy 1-tick-per-pull
    #                             schedule, bit-for-bit
    agg_fairness: str = "none"  # aggregated-plane merge fairness:
    #                             'none' (PR 6 compat: magnitude-
    #                             rebased max) | 'progress' (near-done
    #                             queries outrank big-frontier ones —
    #                             the mid-flight admission guard; see
    #                             Scheduler.aggregate_worklist)
    max_ticks: int = 200_000
    trace: bool = False         # record per-tick pipeline occupancy


@dataclasses.dataclass
class Metrics:
    """Engine counters; plain python ints, 64-bit safe (the device-side
    accumulators are uint32 limb pairs, decoded in :meth:`Engine.run`)."""
    io_ops: int                 # async read submissions
    io_blocks: int              # 4 KB blocks transferred
    edges_scanned: int
    vertices_processed: int
    reuse_activations: int      # activations landing on resident blocks
    blocks_reused: int          # reactivated blocks re-run without I/O
    exec_idle_ticks: int        # ticks with work pending but no cached block
    io_active_ticks: int        # ticks with reads in flight
    inflight_ticks: int         # sum over ticks of in-flight reads (the
    #                             occupancy integral: /io_active_ticks =
    #                             mean queue depth while I/O is active)
    barriers: int               # sync-mode iterations
    ticks: int
    # ---- concurrent-query (batch) accounting -------------------------
    # In a QueryBatch, each query's preload submissions are split:
    # io_ops/io_blocks count only PHYSICAL reads credited to this query
    # (first requester of a block nobody holds), while *_shared count
    # submissions served by another query's resident or in-flight copy.
    # Per query, physical + shared == the solo run's logical I/O; solo
    # runs (and Q=1 batches) have shared == 0 and are bit-identical to
    # the pre-batch counters.
    io_ops_shared: int = 0
    io_blocks_shared: int = 0
    # ---- compute cost model (EngineConfig.compute) --------------------
    # Ticks the executor spent occupied (pulling or chewing carried
    # multi-tick work). 0 unless a ComputeModel is configured; the
    # SSDModel converts it into measured compute seconds.
    exec_busy_ticks: int = 0
    # ---- schedule-cost / residency accounting (PR 6) ------------------
    # block_passes counts executor lane slots actually executed (one per
    # pulled block per tick). On the per-query plane each query pays its
    # own passes; on the aggregated plane ONE pass serves all Q queries,
    # so block_passes (replicated per query) / Q is the batch-compute
    # win the aggregated mode exists for. peak_used_slots is the max
    # buffer-pool occupancy ever observed (a max, not a sum — summing
    # per-query peaks, as Metrics.__add__ does, gives the per-query
    # plane's Q x pool_slots residency figure by construction).
    block_passes: int = 0
    peak_used_slots: int = 0

    @property
    def io_bytes(self) -> int:
        return self.io_blocks * 4096

    def bytes_per_edge(self) -> float:
        """Read-inflation metric (paper Fig. 10): loaded bytes / edge."""
        return self.io_bytes / max(self.edges_scanned, 1)

    def __add__(self, other: "Metrics") -> "Metrics":
        return Metrics(**{f.name: getattr(self, f.name)
                          + getattr(other, f.name)
                          for f in dataclasses.fields(self)})


def batch_totals(metrics: list[Metrics], batch_mode: str) -> Metrics:
    """Whole-batch totals for a :meth:`Engine.run_batch` metrics list.

    On the per-query plane every counter is per-query, so the total is
    the plain sum. On the aggregated plane the schedule counters (I/O,
    ticks, block_passes, peak_used_slots, ...) describe the ONE shared
    schedule and are replicated verbatim into every query's
    ``Metrics`` — summing them would overcount Q-fold — so totals take
    them from ``metrics[0]`` and sum only the ``_PER_QUERY_COUNTERS``
    (each query's own frontier work).
    """
    total = metrics[0]
    for m in metrics[1:]:
        total = total + m
    if batch_mode != "aggregated" or len(metrics) < 2:
        return total
    agg = dataclasses.replace(metrics[0])
    for k in _PER_QUERY_COUNTERS:
        setattr(agg, k, getattr(total, k))
    return agg


def raise_if_stalled(algo: Algorithm, cfg: EngineConfig, stalled) -> None:
    """A loop that stopped at ``max_ticks`` with work pending holds a
    partial state, not a result."""
    if np.any(np.asarray(stalled)):
        raise RuntimeError(
            f"{algo.name!r} stopped at max_ticks={cfg.max_ticks} with "
            "work still pending; raise EngineConfig.max_ticks")


class Engine:
    """Executable model of ACGraph over a :class:`HybridGraph`."""

    def __init__(self, hg: HybridGraph, cfg: EngineConfig | None = None):
        # None-sentinel: a shared default EngineConfig() instance in the
        # signature would be one mutable-adjacent object aliased across
        # every default-constructed Engine
        cfg = EngineConfig() if cfg is None else cfg
        if cfg.refresh not in ("incremental", "full"):
            raise ValueError(
                f"unknown refresh {cfg.refresh!r}; "
                "available: ['full', 'incremental']")
        if cfg.check_refresh and not (cfg.trace
                                      and cfg.refresh == "incremental"):
            raise ValueError(
                "check_refresh=True records the per-tick incremental-vs-"
                "full mismatch count into the trace; it requires "
                "trace=True and refresh='incremental' (got "
                f"trace={cfg.trace}, refresh={cfg.refresh!r})")
        if cfg.batch_mode not in ("per_query", "aggregated"):
            raise ValueError(
                f"unknown batch_mode {cfg.batch_mode!r}; "
                "available: ['aggregated', 'per_query']")
        if cfg.pool_mode not in ("per_query", "shared"):
            raise ValueError(
                f"unknown pool_mode {cfg.pool_mode!r}; "
                "available: ['per_query', 'shared']")
        if cfg.pool_mode == "shared" and cfg.batch_mode != "aggregated":
            raise ValueError(
                "pool_mode='shared' is the aggregated plane's "
                "cross-query admission budget; the per-query plane "
                "gives every query its own pool_slots by construction "
                "— set batch_mode='aggregated' (or leave pool_mode="
                "'per_query')")
        if cfg.batch_mode == "aggregated" and cfg.sync:
            raise ValueError(
                "batch_mode='aggregated' merges Q asynchronous "
                "worklists into one pull order; the synchronous "
                "special case (sync=True) pins each query to "
                "per-iteration barriers and is only supported on the "
                "per-query plane")
        if cfg.agg_fairness not in ("none", "progress"):
            raise ValueError(
                f"unknown agg_fairness {cfg.agg_fairness!r}; "
                "available: ['none', 'progress']")
        if cfg.executor not in EXECUTORS:
            raise ValueError(
                f"unknown executor {cfg.executor!r}; "
                f"available: {sorted(EXECUTORS)}")
        self.hg = hg
        self.cfg = cfg
        self.policy = make_pull_policy(cfg.cached_policy)
        self.device = cfg.device if cfg.device is not None \
            else UniformDevice(latency=cfg.io_latency)
        self._build_tables()
        self._compiled: dict[Any, Any] = {}

    def _tiers(self, tables: ExecTables, pool_slots: int | None = None):
        """``(scheduler, pool, executor)`` over ``tables`` — in a compiled
        program, its traced tables argument, so no graph table is ever
        captured into a program as a constant. ``pool_slots`` overrides
        the pool's capacity (the aggregated plane's, see
        :meth:`_agg_slots`)."""
        cfg = self.cfg
        sched = Scheduler(tables, prefetch=self.P, lanes=self.E,
                          queue_depth=cfg.queue_depth, device=self.device,
                          policy=self.policy)
        pool = BufferPool(
            self.pool_slots if pool_slots is None else pool_slots,
            tables.sched_io, early_stop=cfg.early_stop)
        return sched, pool, make_executor(cfg.executor, tables)

    # ------------------------------------------------------------------
    def _build_tables(self) -> None:
        hg, cfg = self.hg, self.cfg
        nE, nM = hg.num_entities, hg.num_mini
        NB = hg.num_blocks
        BE = hg.block_edges
        chunk = max(cfg.chunk_size, 1)
        NC = -(-nM // chunk) if nM else 0
        V = nE + nM
        B = NB + max(NC, 1 if nM else 0)
        B = max(B, 1)

        off = hg.offsets_untagged()
        virt = np.zeros(V, dtype=bool)
        virt[:nE] = (hg.offsets_tagged[:nE] >> np.uint64(63)).astype(bool)

        # per-vertex degree / edge start / owning scheduling block
        deg = np.zeros(V, dtype=np.int64)
        deg[:nE] = off[1:nE + 1] - off[:nE]
        deg[:nE][virt[:nE]] = 0
        ids_mini = np.arange(nE, V, dtype=np.int64)
        if nM:
            deg[nE:] = hg.degree_of(ids_mini)
        v_start = np.zeros(V, dtype=np.int64)
        v_start[:nE] = off[:nE]
        if nM:
            v_start[nE:] = NB * BE + mini_offset(ids_mini, hg.theta_id)
        v_sched = np.zeros(V, dtype=np.int64)
        v_sched[:nE] = off[:nE] // BE
        if nM:
            v_sched[nE:] = NB + (ids_mini - nE) // chunk

        # scheduling-block tables: real blocks then mini pseudo-blocks
        sched_first = np.concatenate([
            hg.block_first_ent[:NB],
            nE + np.arange(max(NC, B - NB), dtype=np.int64) * chunk,
            np.array([V], dtype=np.int64)])
        sched_first = np.minimum(sched_first, V)[:B + 1]
        sched_first[-1] = V
        sched_io = np.zeros(B, dtype=np.int64)
        sched_io[:NB] = np.where(hg.is_tail, 0, hg.block_span)

        # executor tile sizes from the data
        counts = np.diff(sched_first)
        Vm = int(max(counts.max(initial=1), 1))
        tot_e = np.bincount(v_sched, weights=deg.astype(np.float64),
                            minlength=B)
        We = int(max(tot_e.max(initial=1.0), 1.0))
        max_span = int(hg.block_span.max(initial=1))
        # widest per-block edge window (pallas executor): max over blocks
        # of (last edge slot of any member vertex) - (first vertex's start)
        base_b = v_start[np.minimum(sched_first[:-1], max(V - 1, 0))]
        top_b = np.zeros(B, dtype=np.int64)
        np.maximum.at(top_b, v_sched, v_start + deg)
        win_b = np.maximum(top_b - base_b, 0)
        EK = int(max(win_b.max(initial=1), 1))

        # bucketed tiling: power-of-two size classes over (vertex count,
        # edge mass, edge window) so one hub block stops inflating every
        # lane's tile. Beyond the cap, classes merge pairwise, cheapest
        # first: a merged class pads each member to the max of every
        # dimension, so merging by cost keeps the common 1024-edge
        # blocks out of a hub's or a mini pseudo-block's tile.
        cnt_b = np.maximum(counts, 1)
        we_b = np.maximum(tot_e.astype(np.int64), 1)
        ek_b = np.maximum(win_b, 1)
        nb = int(cfg.bucketing)
        if nb > 0 and B > 1:
            dims = np.stack([cnt_b, we_b, ek_b], axis=1)
            keys, b_class = np.unique(
                np.ceil(np.log2(dims)).astype(np.int64), axis=0,
                return_inverse=True)
            b_class = b_class.reshape(-1)
            size = np.zeros((len(keys), 3), np.int64)
            np.maximum.at(size, b_class, dims)
            # a group: (member classes, member blocks, padded tile)
            groups = [([k], int(n), size[k]) for k, n in
                      enumerate(np.bincount(b_class))]
            cost = lambda n, tile: n * int(tile.sum())
            while len(groups) > nb:
                _, i, j = min(
                    (cost(gi[1] + gj[1], np.maximum(gi[2], gj[2]))
                     - cost(gi[1], gi[2]) - cost(gj[1], gj[2]), i, j)
                    for i, gi in enumerate(groups)
                    for j, gj in enumerate(groups) if i < j)
                gi, gj = groups[i], groups[j]
                groups[i] = (gi[0] + gj[0], gi[1] + gj[1],
                             np.maximum(gi[2], gj[2]))
                del groups[j]
            groups.sort(key=lambda g: (int(g[2].sum()), min(g[0])))
            group_of = np.zeros(len(keys), np.int32)
            for gidx, g in enumerate(groups):
                group_of[g[0]] = gidx
            b_bucket = group_of[b_class]
            self.tiles = tuple(Tile(Vm=int(t[0]), We=int(t[1]), EK=int(t[2]))
                               for _, _, t in groups)
        else:
            b_bucket = np.zeros(B, dtype=np.int32)
            self.tiles = (Tile(Vm=Vm, We=We, EK=EK),)
        b_fill = np.minimum(counts + tot_e.astype(np.int64), 2 ** 31 - 1)

        self.V, self.B, self.NB = V, B, NB
        self.Vm, self.We, self.EK = Vm, We, EK
        self.E = int(min(cfg.lanes, B))
        self.P = int(min(cfg.prefetch, B))
        self.pool_slots = int(max(cfg.pool_slots, max_span))
        assert V < 2 ** 31 and NB * BE + len(hg.mini_data) < 2 ** 31

        # copied to the device once; every compiled program takes them
        # as its first argument (see _tiers), and readers outside the
        # programs (session context, benchmarks) read them here
        i32 = lambda x: np.asarray(x, dtype=np.int32)
        vs_first, vs_nonempty, run_left, max_run = \
            Scheduler.block_segments(v_sched, B)
        edges = np.concatenate([hg.edge_data, hg.mini_data])
        # edge windows (executor.edge_window) are contiguous reads of
        # up to the widest tile's EK slots: the padding keeps the last
        # one inside the array, reading copies of the last slot
        tail_pad = max(tl.EK for tl in self.tiles)
        edges = np.concatenate([edges, np.full(
            tail_pad, edges[-1] if edges.size else -1, edges.dtype)])
        self.tables = jax.device_put(ExecTables(
            all_edges=edges.astype(np.int32),
            v_start=i32(v_start), v_deg=i32(deg), v_sched=i32(v_sched),
            is_real=~virt, sched_first=i32(sched_first),
            sched_io=i32(sched_io), b_bucket=i32(b_bucket),
            b_fill=i32(b_fill),
            # per-block edge mass for the compute cost model
            # (ComputeModel charges executor ticks proportional to it)
            b_edges=i32(np.minimum(tot_e, 2 ** 31 - 1)),
            vs_first=vs_first, vs_nonempty=vs_nonempty,
            run_left=run_left, V=V, max_run=max_run, tail_pad=tail_pad,
            tiles=self.tiles))

    # ------------------------------------------------------------------
    def algo_ctx(self, tables: ExecTables) -> AlgoContext:
        """The :class:`AlgoContext` an ``init`` hook reads: the degree
        and realness tables of ``tables`` — :attr:`tables` itself, or a
        compiled program's table argument, so that no program holds
        them as constants. ``v2id`` is the host's (``GraphSession.ctx``
        adds it) and ``None`` here."""
        return AlgoContext(V=tables.V, degrees=tables.v_deg,
                           is_real=tables.is_real, v2id=None,
                           orig_num_vertices=self.hg.orig_num_vertices)

    # ------------------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function, name="engine.run")
    def run(self, algo: Algorithm, init_frontier: np.ndarray,
            init_state: dict) -> tuple[dict, Metrics, dict | None]:
        """Execute ``algo`` to convergence; returns (state, metrics, trace).
        The host span ``engine.run`` covers the dispatch and the host
        copies of state and counters."""
        cfg = self.cfg
        front0 = jnp.asarray(init_frontier, dtype=bool)
        state0 = {k: jnp.asarray(v) for k, v in init_state.items()}
        # two ppr_algorithm() closures with different alpha/r_max share a
        # name but must not share a compiled tick — Algorithm.params folds
        # the closed-over values into the key while still letting repeated
        # runs of an equal-parameter algorithm reuse the compilation
        key = (algo.name, algo.params, cfg)
        if key not in self._compiled:
            self._compiled[key] = jax.jit(
                functools.partial(self._run_impl, algo))
        out_state, counters, trace, stalled = self._compiled[key](
            self.tables, front0, state0)
        metrics = Metrics(**{k: _c64_int(v) for k, v in counters.items()})
        raise_if_stalled(algo, cfg, stalled)
        out_state = {k: np.asarray(v) for k, v in out_state.items()}
        if cfg.trace:
            trace = {k: np.asarray(v)[:min(metrics.ticks, TRACE_LEN)]
                     for k, v in trace.items()}
            return out_state, metrics, trace
        return out_state, metrics, None

    # ------------------------------------------------------------------
    def _initial_carry(self, algo: Algorithm, tables: ExecTables, front0,
                       state0):
        """Per-query loop carry at tick 0 (shared by solo and batch);
        ``front0`` is masked to real vertices here."""
        cfg = self.cfg
        B = self.B
        i32 = jnp.int32
        check = cfg.check_refresh and cfg.refresh == "incremental"
        sched = self._tiers(tables)[0]
        front0 = front0 & tables.is_real
        nact0, prio0 = sched.refresh(algo, state0, front0)
        b_state0 = sched.initial_block_state(nact0)
        counters0 = {k: _c64_zero() for k in _COUNTERS}
        trace_keys = ("io_blocks", "lanes", "edges", "frontier",
                      "inflight", "io_active", "used_slots") \
            + (("refresh_mismatch",) if check else ())
        trace0 = {k: jnp.zeros(TRACE_LEN, i32) for k in trace_keys} \
            if cfg.trace else {}

        carry0 = dict(
            state=state0, front=front0,
            front_next=jnp.zeros_like(front0),
            b_state=b_state0,
            b_deadline=jnp.zeros(B, i32), b_stamp=jnp.zeros(B, i32),
            b_reuse=jnp.zeros(B, i32), b_used=jnp.zeros(B, i32),
            b_nactive=nact0, b_prio=prio0,
            used_slots=jnp.zeros((), i32), t=jnp.zeros((), i32),
            counters=counters0, trace=trace0)
        if cfg.refresh == "incremental":
            carry0["v_prio"] = algo.priority(
                state0, tables.v_deg).astype(i32)
        if cfg.compute is not None:
            carry0["exec_busy"] = jnp.zeros((), i32)
        return carry0

    @staticmethod
    def _work_pending(c):
        """Per-query liveness; reduces the trailing axis, so it applies
        unchanged to a solo carry and to each row of a Q-stacked one."""
        pending = (jnp.any(c["front"], axis=-1)
                   | jnp.any(c["front_next"], axis=-1)
                   | jnp.any(c["b_state"] == S_LOADING, axis=-1))
        if "exec_busy" in c:
            # compute model: the run ends when the executor drains too
            pending |= c["exec_busy"] > 0
        return pending

    def _run_impl(self, algo: Algorithm, tables: ExecTables, front0,
                  state0):
        cfg = self.cfg
        tick = self._tick_fn(algo, tables)
        carry0 = self._initial_carry(algo, tables, front0, state0)

        @jax.named_scope("schedule")
        def cond(c):
            return (c["t"] < cfg.max_ticks) & self._work_pending(c)

        def step(c):
            # solo: every submission is physical I/O — credit it as-is
            c2, aux = tick(c)
            with jax.named_scope("schedule"):
                cnt = dict(c2["counters"])
                cnt["io_ops"] = _c64_add(cnt["io_ops"], aux["io_ops"])
                cnt["io_blocks"] = _c64_add(cnt["io_blocks"],
                                            aux["io_blocks"])
            return dict(c2, counters=cnt)

        out = jax.lax.while_loop(cond, step, carry0)
        return (out["state"], out["counters"], out["trace"],
                self._work_pending(out))

    # ------------------------------------------------------------------
    def _tick_fn(self, algo: Algorithm, tables: ExecTables):
        """Build the engine tick: ``carry -> (carry', io_aux)``.

        One body shared verbatim between the solo loop and the
        concurrent batch plane (which maps it over the Q axis). The
        preload's I/O crediting is *returned* (``io_aux``: this tick's
        submission counts plus the per-block submitted spans) instead
        of added to the counters in place, so the batch step can first
        split each tick's submissions into physical vs shared reads
        across queries; the solo step credits them unchanged — same
        additions, same totals.

        The stages run under ``jax.named_scope``s that a device profile
        reads as each op's stage: ``schedule`` (1-3 and 7-10, O(B) block
        work, and the loops' liveness tests), ``execute`` (4) and
        ``refresh`` (5-6, the O(V) frontier and worklist update); the
        per-query plane's map over rows adds ``rows`` around them.
        """
        cfg = self.cfg
        sched, pool, executor = self._tiers(tables)
        i32 = jnp.int32

        incremental = cfg.refresh == "incremental"
        check = cfg.check_refresh and incremental
        compute = cfg.compute

        def tick(c):
            state, front = c["state"], c["front"]
            b_prio, b_nactive = c["b_prio"], c["b_nactive"]
            t = c["t"]
            cnt = dict(c["counters"])
            busy0 = c["exec_busy"] if compute is not None else None

            with jax.named_scope("schedule"):
                # ---- 1. async I/O completions (against device deadlines) ---
                comp = sched.complete_io(c["b_state"], c["b_deadline"],
                                         c["b_stamp"], t)
                b_state, b_stamp = comp.b_state, comp.b_stamp

                # ---- 2. preload: priority queue over uncached blocks ---
                pre = sched.preload(b_state, c["b_deadline"], b_prio,
                                    b_nactive, c["used_slots"], pool, t)
                b_state, b_deadline = pre.b_state, pre.b_deadline
                used_slots = pre.used_slots
                # io_ops/io_blocks are credited by the caller from io_aux
                # (the batch plane first dedups them across queries)

                # ---- 3. pull: cached-queue policy ----------------------
                # compute model: while the executor is busy chewing a prior
                # pull's edge mass, no new pull happens (worklist zeroed ->
                # nothing ready) but stages 1/2 above keep I/O flowing —
                # the paper's compute/I/O overlap, now with real compute
                # occupancy
                pull_nact = b_nactive if compute is None else \
                    jnp.where(busy0 == 0, b_nactive, 0)
                eidx, lane_valid, b_used = sched.pull(
                    b_state, pull_nact,
                    PullView(b_stamp=b_stamp, b_prio=b_prio,
                             b_used=c["b_used"], t=t))

            with jax.named_scope("execute"):
                # ---- 4. process: batched apply / propagation -----------
                res = executor.execute(algo, state, front, eidx, lane_valid)
                state = res.state
                if compute is not None:
                    # lanes run in parallel; the heaviest pulled block gates
                    # the batch. cost-1: this tick itself is the first busy
                    # tick of the new pull
                    lane_cost = compute.cost_ticks(tables.b_edges[eidx])
                    cost = jnp.max(jnp.where(lane_valid, lane_cost, 0))
                    busy1 = jnp.where(jnp.any(lane_valid),
                                      jnp.maximum(cost - 1, 0),
                                      jnp.maximum(busy0 - 1, 0))

            with jax.named_scope("refresh"):
                # ---- 5. submit: frontier update + reuse accounting -----
                front1 = front & ~res.processed
                if cfg.sync:
                    front2 = front1
                    front_next = c["front_next"] | res.activated
                else:
                    front2 = front1 | res.activated
                    front_next = c["front_next"]
                cnt["reuse_activations"] = _c64_add(
                    cnt["reuse_activations"],
                    sched.resident_count(b_state, res.activated))

                # ---- 6. worklist metadata refresh -----------------------
                if incremental:
                    b_nactive2, b_prio2, v_prio2 = sched.refresh_delta(
                        algo, state, front2, c["v_prio"], b_prio, eidx,
                        lane_valid)
                else:
                    b_nactive2, b_prio2 = sched.refresh(algo, state, front2)
                if check:
                    # today the counts half is vacuous (refresh_delta rebuilds
                    # counts with refresh's own prefix-sum primitive); it is
                    # kept so the witness automatically covers counts the day
                    # they become genuinely incremental. The priorities half
                    # is the live comparison.
                    nact_f, prio_f = sched.refresh(algo, state, front2)
                    mismatch = (jnp.sum(nact_f != b_nactive2)
                                + jnp.sum(prio_f != b_prio2)).astype(i32)
                    if algo.priority_at is not None:
                        # windowed-priority witness: the threaded
                        # v_prio must be exact at every frontier vertex —
                        # the only positions future reductions read
                        vp_f = algo.priority(state, tables.v_deg).astype(i32)
                        mismatch = mismatch + jnp.sum(
                            front2 & (vp_f != v_prio2)).astype(i32)

            with jax.named_scope("schedule"):
                # ---- 7. finish: reactivated blocks re-enter cached queue ---
                fin = sched.finish(b_state, b_stamp, c["b_reuse"], b_nactive2,
                                   eidx, lane_valid, used_slots, pool, t)
                b_state, b_stamp = fin.b_state, fin.b_stamp
                b_reuse, used_slots = fin.b_reuse, fin.used_slots
                cnt["blocks_reused"] = _c64_add(cnt["blocks_reused"],
                                                fin.blocks_reused)

                # ---- 8. activation transitions for inactive blocks ------
                b_state, b_stamp = sched.activate(b_state, b_stamp, b_nactive2,
                                                  t)

                # ---- 9. sync barrier (Sec. 4.3) --------------------------
                if cfg.sync:
                    (front2, front_next, b_state, b_nactive2, b_prio2,
                     used_slots, barrier) = sched.barrier(
                        algo, state, front2, front_next, b_state, b_nactive2,
                        b_prio2, used_slots, pool, lazy=incremental)
                    cnt["barriers"] = _c64_add(cnt["barriers"],
                                               barrier.astype(i32))

                # ---- 10. counters & trace -------------------------------
                lanes_used = jnp.sum(lane_valid).astype(i32)
                cnt["edges_scanned"] = _c64_add(cnt["edges_scanned"],
                                                res.edges_scanned)
                cnt["vertices_processed"] = _c64_add(cnt["vertices_processed"],
                                                     res.vertices_processed)
                idle = (lanes_used == 0) & jnp.any(front2)
                if compute is not None:
                    # a busy executor is the opposite of an idle one: only
                    # ticks where it *could* have pulled and found nothing
                    # cached count as stalls
                    idle &= busy0 == 0
                    cnt["exec_busy_ticks"] = _c64_add(
                        cnt["exec_busy_ticks"],
                        ((busy0 > 0) | jnp.any(lane_valid)).astype(i32))
                cnt["exec_idle_ticks"] = _c64_add(cnt["exec_idle_ticks"],
                                                  idle.astype(i32))
                # io_active samples in-flight BEFORE completions so a tick
                # whose last read retires still counts; the occupancy
                # *integral* uses the post-completion count + submissions,
                # which never double-counts a completion/submit handoff and
                # is bounded by queue_depth
                io_active = (comp.inflight + pre.io_ops > 0).astype(i32)
                occ = pre.inflight + pre.io_ops
                cnt["io_active_ticks"] = _c64_add(cnt["io_active_ticks"],
                                                  io_active)
                cnt["inflight_ticks"] = _c64_add(cnt["inflight_ticks"], occ)
                cnt["ticks"] = _c64_add(cnt["ticks"], jnp.ones((), i32))
                cnt["block_passes"] = _c64_add(cnt["block_passes"],
                                               lanes_used)
                # peak residency is a MAX, not a sum: tracked in the low
                # limb (used_slots is i32, never wraps)
                cnt["peak_used_slots"] = (
                    cnt["peak_used_slots"][0],
                    jnp.maximum(cnt["peak_used_slots"][1],
                                pre.used_slots.astype(jnp.uint32)))
                trace = c["trace"]
                if cfg.trace:
                    ti = jnp.minimum(t, TRACE_LEN - 1)
                    trace = {
                        "io_blocks": trace["io_blocks"].at[ti].set(
                            pre.io_blocks),
                        "lanes": trace["lanes"].at[ti].set(lanes_used),
                        "edges": trace["edges"].at[ti].set(res.edges_scanned),
                        "frontier": trace["frontier"].at[ti].set(
                            jnp.sum(front2).astype(i32)),
                        "inflight": trace["inflight"].at[ti].set(occ),
                        "io_active": trace["io_active"].at[ti].set(io_active),
                        "used_slots": trace["used_slots"].at[ti].set(
                            used_slots),
                    }
                    if check:
                        trace["refresh_mismatch"] = \
                            c["trace"]["refresh_mismatch"].at[ti].set(mismatch)

                out_c = dict(state=state, front=front2, front_next=front_next,
                             b_state=b_state, b_deadline=b_deadline,
                             b_stamp=b_stamp,
                             b_reuse=b_reuse, b_used=b_used,
                             b_nactive=b_nactive2, b_prio=b_prio2,
                             used_slots=used_slots, t=t + 1,
                             counters=cnt, trace=trace)
                if incremental:
                    out_c["v_prio"] = v_prio2
                if compute is not None:
                    out_c["exec_busy"] = busy1
                io_aux = dict(io_ops=pre.io_ops, io_blocks=pre.io_blocks,
                              sub_mask=pre.sub_mask, sub_spans=pre.sub_spans)
            return out_c, io_aux

        return tick

    # ------------------------------------------------------------------
    # concurrent query plane (PR 5): Q-stacked execution, shared I/O
    # ------------------------------------------------------------------
    def run_batch(self, algo: Algorithm, init_fronts: np.ndarray,
                  init_states: dict, batch_mode: str | None = None
                  ) -> tuple[dict, list[Metrics], list[dict] | None]:
        """Execute Q stacked instances of ``algo`` in ONE engine loop.

        ``init_fronts`` is bool[Q, V]; every array in ``init_states`` is
        [Q, V]-stacked. Each query carries its OWN control plane (block
        states, worklist metadata, pool accounting), advanced in
        lockstep by mapping the solo tick over the Q axis — so every
        query's schedule, state trajectory, and non-I/O counters are
        bit-identical to a solo :meth:`run` of the same query. The
        cross-query worklist lives at the I/O layer: each tick, all
        queries' preload submissions are deduplicated
        (:meth:`~repro.core.scheduler.Scheduler.split_shared_io`)
        so one physical read serves every query that wants the block
        while it is resident; per-query ``Metrics.io_blocks`` counts
        only the physical reads credited to that query and
        ``io_blocks_shared`` the rest (physical + shared == the solo
        run's logical I/O, exactly).

        Why per-query schedules are the *default*: add-combiner
        algorithms (PPR's forward push) have schedule-dependent results
        — even in exact arithmetic the final (p, r) split depends on
        how residuals interleave — so any shared pull order would break
        the solo-equivalence contract the query API promises. On this
        plane the Q axis is mapped (``lax.map``/scan), not vmapped: the
        scanned body is the solo tick's exact computation (bit-parity
        by construction) and needs no batching rules for the per-lane
        ``lax.switch`` routing or the pallas kernel.

        ``batch_mode`` (``None`` = ``cfg.batch_mode``) selects the
        plane per call: ``"aggregated"`` runs the PR 6 merged-schedule
        plane instead (one pull order, one executor pass per block for
        all Q queries, one real pool — see the module docstring) and
        raises ``ValueError`` for algorithms that are not
        schedule-independent (``api.aggregation_eligible``); the
        service/session layer catches that routing decision *before*
        calling, falling back to per-query transparently.

        A converged query's rows pass through untouched (``lax.cond``)
        while the loop drains the others, so its counters freeze at the
        solo run's final values; its resident blocks stay in its pool
        partition (each query budgets ``pool_slots`` of its own) and
        keep serving other queries' requests as shared hits.

        Returns ``(state, metrics, traces)``: ``state`` dict of [Q, V]
        arrays, per-query ``Metrics`` list, and per-query trace dicts
        iff ``cfg.trace``. Compiled once per ``(Q, name, params, cfg)``
        — batches differing only in init data share the compilation.
        """
        cfg = self.cfg
        mode = cfg.batch_mode if batch_mode is None else batch_mode
        if mode not in ("per_query", "aggregated"):
            raise ValueError(
                f"unknown batch_mode {mode!r}; "
                "available: ['aggregated', 'per_query']")
        if mode == "aggregated":
            if not aggregation_eligible(algo):
                raise ValueError(
                    f"algorithm {algo.name!r} is not schedule-"
                    f"independent (combine={algo.combine!r}, "
                    f"on_process={'set' if algo.on_process else 'None'},"
                    f" schedule_independent="
                    f"{algo.schedule_independent}): a shared pull "
                    "order would change its per-query results — run "
                    "it on the per-query plane (batch_mode="
                    "'per_query'), as GraphService does automatically")
            if cfg.sync:
                raise ValueError(
                    "batch_mode='aggregated' is asynchronous-only; "
                    "sync=True requires the per-query plane")
        front0 = jnp.asarray(init_fronts, dtype=bool)
        if front0.ndim != 2:
            raise ValueError(
                f"init_fronts must be [Q, V], got shape {front0.shape}")
        Q = int(front0.shape[0])
        state0 = {k: jnp.asarray(v) for k, v in init_states.items()}
        key = ("batch", mode, Q, algo.name, algo.params, cfg)
        if key not in self._compiled:
            impl = self._run_batch_agg_impl if mode == "aggregated" \
                else self._run_batch_impl
            self._compiled[key] = jax.jit(functools.partial(impl, algo))
        out_state, counters, trace, stalled = self._compiled[key](
            self.tables, front0, state0)
        raise_if_stalled(algo, cfg, stalled)
        counters = {k: (np.asarray(hi), np.asarray(lo))
                    for k, (hi, lo) in counters.items()}
        metrics = [Metrics(**{k: (int(hi[q]) << 32) | int(lo[q])
                              for k, (hi, lo) in counters.items()})
                   for q in range(Q)]
        out_state = {k: np.asarray(v) for k, v in out_state.items()}
        if cfg.trace:
            trace = {k: np.asarray(v) for k, v in trace.items()}
            traces = [{k: v[q][:min(metrics[q].ticks, TRACE_LEN)]
                       for k, v in trace.items()} for q in range(Q)]
            return out_state, metrics, traces
        return out_state, metrics, None

    def _batch_carry0(self, algo: Algorithm, tables: ExecTables, fronts0,
                      states0):
        """Q-stacked per-query carries at tick 0 (shared by the batch
        loop and the serving plane). The map body is the solo
        :meth:`_initial_carry` verbatim; the shared-I/O counters are
        added on top."""
        Q = fronts0.shape[0]
        carry0 = jax.lax.map(
            lambda fs: self._initial_carry(algo, tables, fs[0], fs[1]),
            (fronts0, states0))
        zq = jnp.zeros(Q, jnp.uint32)
        cnt0 = dict(carry0["counters"])
        for k in _SHARED_COUNTERS:
            cnt0[k] = (zq, zq)
        return dict(carry0, counters=cnt0)

    def _batch_alive(self, c):
        """Per-row liveness of a Q-stacked carry — identical to the
        solo loop's continue condition, so a row's last tick is the
        same tick solo would have stopped after."""
        return (c["t"] < self.cfg.max_ticks) & self._work_pending(c)

    def _batch_step_fn(self, algo: Algorithm, tables: ExecTables):
        """One per-query-plane batch tick: alive-masked solo ticks over
        the Q axis + the cross-query physical/shared I/O split. Shared
        by :meth:`_run_batch_impl`'s while_loop and the serving plane's
        single-tick step."""
        B = self.B
        i32 = jnp.int32
        tick = self._tick_fn(algo, tables)

        @jax.named_scope("rows")
        def step(c):
            alive = self._batch_alive(c)
            # residency at the START of the tick (post-finish of the
            # previous tick): LOADING and CACHED copies can both serve
            # another query's request without new device traffic
            resident = (c["b_state"] == S_LOADING) | \
                       (c["b_state"] == S_CACHED)

            def qstep(args):
                av, cq = args

                def dead(cq):
                    zero = jnp.zeros((), i32)
                    return cq, dict(io_ops=zero, io_blocks=zero,
                                    sub_mask=jnp.zeros(B, bool),
                                    sub_spans=jnp.zeros(B, i32))

                return jax.lax.cond(av, tick, dead, cq)

            c2, aux = jax.lax.map(qstep, (alive, c))
            ops_p, blk_p, ops_s, blk_s = Scheduler.split_shared_io(
                resident, aux["sub_mask"], aux["sub_spans"])
            cnt = dict(c2["counters"])
            cnt["io_ops"] = _c64_add(cnt["io_ops"], ops_p)
            cnt["io_blocks"] = _c64_add(cnt["io_blocks"], blk_p)
            cnt["io_ops_shared"] = _c64_add(cnt["io_ops_shared"], ops_s)
            cnt["io_blocks_shared"] = _c64_add(cnt["io_blocks_shared"],
                                               blk_s)
            return dict(c2, counters=cnt)

        return step

    def _run_batch_impl(self, algo: Algorithm, tables: ExecTables,
                        fronts0, states0):
        carry0 = self._batch_carry0(algo, tables, fronts0, states0)
        step = self._batch_step_fn(algo, tables)

        @jax.named_scope("schedule")
        def cond(c):
            return jnp.any(self._batch_alive(c))

        out = jax.lax.while_loop(cond, step, carry0)
        return (out["state"], out["counters"], out["trace"],
                self._work_pending(out))

    # ------------------------------------------------------------------
    # aggregated batch plane (PR 6): one merged schedule for Q queries
    # ------------------------------------------------------------------
    def _agg_slots(self, Q: int) -> int:
        """Capacity of the aggregated plane's ONE real pool for a
        Q-batch."""
        return self.pool_slots if self.cfg.pool_mode == "shared" \
            else Q * self.pool_slots

    def _agg_carry0(self, algo: Algorithm, tables: ExecTables, fronts0,
                    states0):
        """Aggregated-plane carry at tick 0: ONE shared control plane
        (block states/deadlines/pool/pull history, scalar clock), Q-
        stacked worklist metadata / frontier / state / counters."""
        cfg = self.cfg
        B = self.B
        i32 = jnp.int32
        Q = fronts0.shape[0]
        sched = self._tiers(tables)[0]
        fronts0 = fronts0 & tables.is_real[None, :]
        incremental = cfg.refresh == "incremental"
        check = cfg.check_refresh and incremental

        nact0, prio0 = jax.lax.map(
            lambda a: sched.refresh(algo, a[0], a[1]),
            (states0, fronts0))
        b_state0 = sched.initial_block_state(jnp.sum(nact0, axis=0))
        zq = jnp.zeros(Q, jnp.uint32)
        counters0 = {k: (zq, zq) for k in _COUNTERS + _SHARED_COUNTERS}
        trace_keys = ("io_blocks", "lanes", "edges", "frontier",
                      "inflight", "io_active", "used_slots") \
            + (("refresh_mismatch",) if check else ())
        trace0 = {k: jnp.zeros(TRACE_LEN, i32) for k in trace_keys} \
            if cfg.trace else {}
        carry0 = dict(
            state=states0, front=fronts0, b_state=b_state0,
            b_deadline=jnp.zeros(B, i32), b_stamp=jnp.zeros(B, i32),
            b_reuse=jnp.zeros(B, i32), b_used=jnp.zeros(B, i32),
            b_nactive=nact0, b_prio=prio0,
            used_slots=jnp.zeros((), i32), t=jnp.zeros((), i32),
            counters=counters0, trace=trace0)
        if incremental:
            carry0["v_prio"] = jax.lax.map(
                lambda st: algo.priority(st, tables.v_deg).astype(i32),
                states0)
        if cfg.compute is not None:
            # ONE executor serves the merged schedule -> shared busy
            carry0["exec_busy"] = jnp.zeros((), i32)
        return carry0

    def _agg_pending(self, c):
        """Aggregated-plane liveness (ignoring max_ticks): any frontier
        work, in-flight I/O, or carried executor occupancy."""
        work = jnp.any(c["front"]) | jnp.any(c["b_state"] == S_LOADING)
        if "exec_busy" in c:
            work |= c["exec_busy"] > 0
        return work

    def _run_batch_agg_impl(self, algo: Algorithm, tables: ExecTables,
                            fronts0, states0):
        """One merged pull order serving Q stacked queries (PR 6).

        ONE shared control plane (block states, deadlines, pool
        accounting, pull history) drives the tick; only the worklist
        metadata, frontier, and algorithm state stay per-query. Each
        tick merges the Q metadata vectors
        (:meth:`Scheduler.aggregate_worklist`), preloads/pulls against
        the merged worklist once, expands each pulled block ONCE over
        the Q-stacked state (:meth:`ExecutorBackend.execute_many`),
        then refreshes each query's metadata from the same lane
        windows (``lax.map``, so the incremental full-rebuild
        ``lax.cond`` stays a real branch per query). Finish/activate
        run on the cross-query active refcount ``sum_q nact`` — a
        block leaves the pool only when NO query has work in it.
        """
        cfg = self.cfg
        Q = fronts0.shape[0]
        carry0 = self._agg_carry0(algo, tables, fronts0, states0)
        tick = self._agg_tick_fn(algo, tables, self._agg_slots(Q))

        @jax.named_scope("schedule")
        def cond(c):
            return (c["t"] < cfg.max_ticks) & self._agg_pending(c)

        out = jax.lax.while_loop(cond, tick, carry0)
        trace = out["trace"]
        if cfg.trace:
            # one shared schedule -> one trace, replicated per query so
            # run_batch's per-query decode applies unchanged
            trace = {k: jnp.broadcast_to(v[None, :], (Q, TRACE_LEN))
                     for k, v in trace.items()}
        return out["state"], out["counters"], trace, self._agg_pending(out)

    def _agg_tick_fn(self, algo: Algorithm, tables: ExecTables,
                     pool_slots: int):
        """Build the aggregated-plane tick (shared by the batch
        while_loop and the serving plane's single-tick step)."""
        cfg = self.cfg
        i32 = jnp.int32
        sched, pool, executor = self._tiers(tables, pool_slots)
        incremental = cfg.refresh == "incremental"
        check = cfg.check_refresh and incremental
        compute = cfg.compute

        def tick(c):
            state, front = c["state"], c["front"]
            t = c["t"]
            cnt = dict(c["counters"])
            busy0 = c["exec_busy"] if compute is not None else None

            with jax.named_scope("schedule"):
                nact_agg, prio_agg = Scheduler.aggregate_worklist(
                    c["b_nactive"], c["b_prio"], cfg.agg_fairness)

                # ---- 1. async I/O completions --------------------------
                comp = sched.complete_io(c["b_state"], c["b_deadline"],
                                         c["b_stamp"], t)
                b_state, b_stamp = comp.b_state, comp.b_stamp

                # ---- 2. preload against the MERGED worklist ------------
                pre = sched.preload(b_state, c["b_deadline"], prio_agg,
                                    nact_agg, c["used_slots"], pool, t)
                b_state, b_deadline = pre.b_state, pre.b_deadline
                used_slots = pre.used_slots

                # ---- 3. ONE pull for the whole batch (compute-gated) ---
                pull_nact = nact_agg if compute is None else \
                    jnp.where(busy0 == 0, nact_agg, 0)
                eidx, lane_valid, b_used = sched.pull(
                    b_state, pull_nact,
                    PullView(b_stamp=b_stamp, b_prio=prio_agg,
                             b_used=c["b_used"], t=t))

            with jax.named_scope("execute"):
                # ---- 4. ONE executor pass per block, Q-stacked state ---
                res = executor.execute_many(algo, state, front, eidx,
                                            lane_valid)
                state = res.state
                if compute is not None:
                    lane_cost = compute.cost_ticks(tables.b_edges[eidx])
                    cost = jnp.max(jnp.where(lane_valid, lane_cost, 0))
                    busy1 = jnp.where(jnp.any(lane_valid),
                                      jnp.maximum(cost - 1, 0),
                                      jnp.maximum(busy0 - 1, 0))

            with jax.named_scope("refresh"):
                # ---- 5. per-query frontier update + reuse accounting ---
                front2 = (front & ~res.processed) | res.activated
                reuse_q = jax.vmap(lambda a: sched.resident_count(
                    b_state, a))(res.activated)

                # ---- 6. per-query worklist refresh (lax.map keeps the
                # incremental full-rebuild lax.cond a real branch) -----------
                if incremental:
                    nact2, prio2, v_prio2 = jax.lax.map(
                        lambda a: sched.refresh_delta(
                            algo, a[0], a[1], a[2], a[3], eidx, lane_valid),
                        (state, front2, c["v_prio"], c["b_prio"]))
                else:
                    nact2, prio2 = jax.lax.map(
                        lambda a: sched.refresh(algo, a[0], a[1]),
                        (state, front2))
                if check:
                    nact_f, prio_f = jax.lax.map(
                        lambda a: sched.refresh(algo, a[0], a[1]),
                        (state, front2))
                    mismatch = (jnp.sum(nact_f != nact2)
                                + jnp.sum(prio_f != prio2)).astype(i32)
                    if algo.priority_at is not None:
                        vp_f = jax.lax.map(
                            lambda st: algo.priority(
                                st, tables.v_deg).astype(i32), state)
                        mismatch = mismatch + jnp.sum(
                            front2 & (vp_f != v_prio2)).astype(i32)
                nact2_agg = jnp.sum(nact2, axis=0)

            with jax.named_scope("schedule"):
                # ---- 7./8. finish + activation on the cross-query refcount
                fin = sched.finish(b_state, b_stamp, c["b_reuse"],
                                   nact2_agg, eidx, lane_valid, used_slots,
                                   pool, t)
                b_state, b_stamp = fin.b_state, fin.b_stamp
                b_reuse, used_slots = fin.b_reuse, fin.used_slots
                b_state, b_stamp = sched.activate(b_state, b_stamp,
                                                  nact2_agg, t)

                # ---- 10. counters & trace: schedule-wide values broadcast
                # into every query's accumulators, _PER_QUERY_COUNTERS from
                # each query's own masks (see batch_totals) ------------------
                lanes_used = jnp.sum(lane_valid).astype(i32)
                cnt["io_ops"] = _c64_add(cnt["io_ops"], pre.io_ops)
                cnt["io_blocks"] = _c64_add(cnt["io_blocks"], pre.io_blocks)
                cnt["edges_scanned"] = _c64_add(cnt["edges_scanned"],
                                                res.edges_scanned)
                cnt["vertices_processed"] = _c64_add(
                    cnt["vertices_processed"], res.vertices_processed)
                cnt["reuse_activations"] = _c64_add(cnt["reuse_activations"],
                                                    reuse_q)
                cnt["blocks_reused"] = _c64_add(cnt["blocks_reused"],
                                                fin.blocks_reused)
                idle = (lanes_used == 0) & jnp.any(front2)
                if compute is not None:
                    idle &= busy0 == 0
                    cnt["exec_busy_ticks"] = _c64_add(
                        cnt["exec_busy_ticks"],
                        ((busy0 > 0) | jnp.any(lane_valid)).astype(i32))
                cnt["exec_idle_ticks"] = _c64_add(cnt["exec_idle_ticks"],
                                                  idle.astype(i32))
                io_active = (comp.inflight + pre.io_ops > 0).astype(i32)
                occ = pre.inflight + pre.io_ops
                cnt["io_active_ticks"] = _c64_add(cnt["io_active_ticks"],
                                                  io_active)
                cnt["inflight_ticks"] = _c64_add(cnt["inflight_ticks"], occ)
                cnt["ticks"] = _c64_add(cnt["ticks"], jnp.ones((), i32))
                cnt["block_passes"] = _c64_add(cnt["block_passes"],
                                               lanes_used)
                cnt["peak_used_slots"] = (
                    cnt["peak_used_slots"][0],
                    jnp.maximum(cnt["peak_used_slots"][1],
                                pre.used_slots.astype(jnp.uint32)))
                trace = c["trace"]
                if cfg.trace:
                    ti = jnp.minimum(t, TRACE_LEN - 1)
                    trace = {
                        "io_blocks": trace["io_blocks"].at[ti].set(
                            pre.io_blocks),
                        "lanes": trace["lanes"].at[ti].set(lanes_used),
                        "edges": trace["edges"].at[ti].set(
                            jnp.sum(res.edges_scanned).astype(i32)),
                        "frontier": trace["frontier"].at[ti].set(
                            jnp.sum(front2).astype(i32)),
                        "inflight": trace["inflight"].at[ti].set(occ),
                        "io_active": trace["io_active"].at[ti].set(
                            io_active),
                        "used_slots": trace["used_slots"].at[ti].set(
                            used_slots),
                    }
                    if check:
                        trace["refresh_mismatch"] = \
                            c["trace"]["refresh_mismatch"].at[ti].set(
                                mismatch)

                out_c = dict(state=state, front=front2, b_state=b_state,
                             b_deadline=b_deadline, b_stamp=b_stamp,
                             b_reuse=b_reuse, b_used=b_used,
                             b_nactive=nact2, b_prio=prio2,
                             used_slots=used_slots, t=t + 1,
                             counters=cnt, trace=trace)
                if incremental:
                    out_c["v_prio"] = v_prio2
                if compute is not None:
                    out_c["exec_busy"] = busy1
            return out_c

        return tick

    # ------------------------------------------------------------------
    # continuous-serving hooks: open-ended carry, admit / retire
    # ------------------------------------------------------------------

    #: aggregated-plane carry leaves with a leading Q axis (everything
    #: else is the ONE shared control plane); the serving layer's
    #: capacity resize gathers/pads exactly these and carries the
    #: shared leaves through unchanged. ``v_prio`` only exists under
    #: refresh='incremental'.
    AGG_PER_QUERY_KEYS = ("state", "front", "b_nactive", "b_prio",
                          "v_prio", "counters")

    def service_fns(self, algo: Algorithm, Q: int, mode: str) -> dict:
        """Compiled single-tick serving functions for a Q-capacity batch.

        The continuous service (:class:`repro.core.serving.
        ContinuousService`) never drains, so it cannot live inside one
        ``while_loop``; instead the host loop calls these per tick:

          * ``carry0() -> carry`` — a fresh Q-capacity carry of dead
            rows, made on the device;
          * ``step(carry) -> (carry', pending[Q], used_slots,
            stalled[Q])`` — ONE engine tick, the exact batch-plane step
            body (per-query plane: alive-masked solo ticks + shared-I/O
            split; aggregated plane: the merged-schedule tick), plus
            each row's liveness, the post-tick pool occupancy for the
            host's retirement / budget decisions, and which rows stopped
            at ``max_ticks`` with work pending (the host raises: such a
            row holds a partial state, not a result);
          * ``admit(carry, q, src) -> carry`` — stack a fresh query
            into row ``q`` at a tick boundary. ``algo.init`` runs inside
            the program on the tables and ``src``, the query's init
            data (:meth:`Algorithm.init_data`: the source's engine id,
            or ``None``), so a query's start costs the host one int32.
            Per-query plane: the row becomes the solo tick-0 carry
            verbatim, so everything after is bit-identical to a solo
            run no matter when it was admitted. Aggregated plane: only
            the per-query leaves are replaced and the shared block
            states are re-activated against the new cross-query
            refcount (:meth:`Scheduler.reactivate_on_admit`) — the
            newcomer's blocks wake without disturbing the running
            schedule;
          * ``retire(carry, q) -> carry`` — take row ``q`` out, with no
            host array. Per-query plane: the row resets to a dead
            tick-0 carry (zero frontier, zero state of ``init``'s
            shapes and dtypes). Aggregated plane: clear the row's
            frontier/worklist and release residency no live query
            needs (:meth:`Scheduler.reclaim_idle`), so a service that
            never drains gives slots back at retirement instead of
            ratcheting the shared pool full;
          * ``extract(carry, q) -> row`` — row ``q`` sliced on the
            device, so retirement copies one row to the host, not Q:
            ``state`` (each leaf's row), ``counters`` (every counter's
            ``(hi, lo)`` limbs as one uint32 ``[K, 2]`` array, counters
            in sorted-name order) and, on the per-query plane with
            ``cfg.trace`` set, ``trace`` (each key's full row; the host
            cuts it to the row's ticks). The aggregated plane's state
            and counters are per-query leaves too
            (:attr:`AGG_PER_QUERY_KEYS`); its trace is the shared
            schedule's and is left out.

        Each is a compiled program taking the engine's tables as its
        first argument, bound here. Compiled once per ``(Q, mode, name,
        params, cfg)`` and cached — admissions and retirements at a
        given capacity never recompile (``q`` and ``src`` are traced, so
        one program serves every row and source); capacity changes (the
        serving layer's power-of-two ladder) do.
        """
        key = ("svc", mode, Q, algo.name, algo.params, self.cfg)
        if key in self._compiled:
            return self._compiled[key]
        if mode not in ("per_query", "aggregated"):
            raise ValueError(
                f"unknown batch_mode {mode!r}; "
                "available: ['aggregated', 'per_query']")
        if mode == "aggregated" and not aggregation_eligible(algo):
            raise ValueError(
                f"algorithm {algo.name!r} is not schedule-independent; "
                "serve it on the per-query plane (see Engine.run_batch)")
        i32 = jnp.int32
        incremental = self.cfg.refresh == "incremental"
        start = lambda tables, src: algo.init(self.algo_ctx(tables), src)

        def dead(tables, lead=()):
            """A dead row's frontier and state: zeros of ``init``'s
            shapes and dtypes, with ``lead`` axes in front."""
            src = None if algo.source is None \
                else jax.ShapeDtypeStruct((), i32)
            front, state = jax.eval_shape(
                lambda s: start(tables, s), src)
            zeros = lambda a: jnp.zeros(lead + a.shape, a.dtype)
            return zeros(front), jax.tree_util.tree_map(zeros, state)

        if mode == "aggregated":
            slots = self._agg_slots(Q)

            def step(tables, c):
                c2 = self._agg_tick_fn(algo, tables, slots)(c)
                with jax.named_scope("schedule"):
                    return (c2, jnp.any(c2["front"], axis=-1),
                            c2["used_slots"], jnp.zeros(Q, bool))

            def carry0(tables):
                return self._agg_carry0(algo, tables, *dead(tables, (Q,)))

            def admit(tables, c, q, src):
                sched = self._tiers(tables)[0]
                front0, state0 = start(tables, src)
                front0 = front0 & tables.is_real
                nact0, prio0 = sched.refresh(algo, state0, front0)
                z = jnp.zeros((), jnp.uint32)
                # the row's counters restart at admission: on this
                # plane schedule counters are the shared schedule's,
                # so a row measures the schedule DURING its residency
                row = dict(
                    state=state0, front=front0,
                    b_nactive=nact0, b_prio=prio0,
                    counters={k: (z, z)
                              for k in _COUNTERS + _SHARED_COUNTERS})
                if incremental:
                    row["v_prio"] = algo.priority(
                        state0, tables.v_deg).astype(i32)
                sub = jax.tree_util.tree_map(
                    lambda full, r: full.at[q].set(r),
                    {k: c[k] for k in row}, row)
                c = dict(c, **sub)
                nact_agg = jnp.sum(c["b_nactive"], axis=0)
                b_state, b_stamp = sched.reactivate_on_admit(
                    c["b_state"], c["b_stamp"], nact_agg, c["t"])
                return dict(c, b_state=b_state, b_stamp=b_stamp)

            def retire(tables, c, q):
                sched, pool, _ = self._tiers(tables, slots)
                front = c["front"].at[q].set(False)
                b_nactive = c["b_nactive"].at[q].set(0)
                nact_agg = jnp.sum(b_nactive, axis=0)
                b_state, used_slots = sched.reclaim_idle(
                    c["b_state"], c["used_slots"], nact_agg, pool)
                return dict(c, front=front, b_nactive=b_nactive,
                            b_state=b_state, used_slots=used_slots)
        else:
            def step(tables, c):
                c2 = self._batch_step_fn(algo, tables)(c)
                with jax.named_scope("schedule"):
                    alive = self._batch_alive(c2)
                    return (c2, alive, jnp.sum(c2["used_slots"]),
                            self._work_pending(c2) & ~alive)

            def carry0(tables):
                return self._batch_carry0(algo, tables,
                                          *dead(tables, (Q,)))

            def put_row(tables, c, q, front0, state0):
                row = self._initial_carry(algo, tables, front0, state0)
                z = jnp.zeros((), jnp.uint32)
                cnt = dict(row["counters"])
                for k in _SHARED_COUNTERS:
                    cnt[k] = (z, z)
                row = dict(row, counters=cnt)
                return jax.tree_util.tree_map(
                    lambda full, r: full.at[q].set(r), c, row)

            def admit(tables, c, q, src):
                return put_row(tables, c, q, *start(tables, src))

            # per-query retirement IS an admit of the empty query: the
            # row resets to a dead tick-0 carry (all-INACTIVE block
            # states), which also zeroes its private pool accounting
            def retire(tables, c, q):
                return put_row(tables, c, q, *dead(tables))

        row_trace = self.cfg.trace and mode == "per_query"

        def extract(tables, c, q):
            cnt = c["counters"]
            row = dict(
                state={k: v[q] for k, v in c["state"].items()},
                counters=jnp.stack([jnp.stack([cnt[k][0][q], cnt[k][1][q]])
                                    for k in sorted(cnt)]))
            if row_trace:
                row["trace"] = {k: v[q] for k, v in c["trace"].items()}
            return row

        bound = lambda f: functools.partial(jax.jit(f), self.tables)
        fns = dict(carry0=bound(carry0), step=bound(step),
                   admit=bound(admit), retire=bound(retire),
                   extract=bound(extract))
        self._compiled[key] = fns
        return fns


# ----------------------------------------------------------------------
# Paper-API veneer (Sec. 4.6)
# ----------------------------------------------------------------------

def foreach_vertex_frontier(priority: np.ndarray) -> np.ndarray:
    """``foreachVertex`` semantics: vertices with priority > 0 activate."""
    return np.asarray(priority) > 0
