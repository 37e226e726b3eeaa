"""Scheduler tier: block-state transitions and queue policies (paper Sec. 4).

This module owns the per-block control plane of the engine tick:

  * async I/O completion (LOADING -> CACHED) against per-block
    **deadlines** assigned at submit time by the
    :class:`~repro.io_sim.device.DeviceModel` — service time is
    span-proportional with bounded channel parallelism, so slow devices
    and shallow queues visibly stretch the schedule (paper Figs. 3, 8,
    12),
  * the preload priority queue over UNCACHED blocks (top-k by worklist
    priority, bounded by the io_uring-style queue depth; capacity
    admission is delegated to the :class:`~repro.core.pool.BufferPool`),
  * the cached-queue *pull* step behind a small policy protocol
    (:class:`PullPolicy`) — ``fifo`` (paper default), ``priority``,
    ``lru``, and the cost-aware ``hybrid`` (priority × static block
    fill) / ``hybrid_active`` (priority × live active count) are
    provided and new policies register via :data:`CACHED_POLICIES`,
  * the **cross-query worklist** for the concurrent query plane — in
    per-query batch mode, :meth:`Scheduler.split_shared_io`
    deduplicates the Q schedules' preload submissions (one physical
    read serves every query that wants the block while it is resident;
    the rest is accounted as *shared* I/O); in aggregated batch mode,
    :meth:`Scheduler.aggregate_worklist` merges the Q per-query
    metadata vectors into ONE worklist (sum of active counts, max of
    per-query-rebased priorities) so preload and pull make a single
    decision per tick that serves every query,
  * worklist metadata (per-block active counts and priorities), either
    rebuilt from scratch every tick (:meth:`Scheduler.refresh`) or
    maintained *incrementally* from the executor's lane windows
    (:meth:`Scheduler.refresh_delta`) — exact, not approximate,
  * finish/reactivation/eviction transitions after execution, activation
    of newly woken blocks, and the Sec. 4.3 synchronous barrier.

Everything is a pure jnp function of the carried per-block arrays so the
whole scheduler composes inside ``jax.lax.while_loop``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import edge_window
from repro.core.pool import BufferPool
from repro.core.prefix import cumsum
from repro.io_sim.device import DeviceModel

# persistent per-tick block states (PROCESSING/REACTIVATED are intra-tick)
S_INACTIVE, S_UNCACHED, S_LOADING, S_CACHED = 0, 1, 2, 3

NEG_INF = np.iinfo(np.int32).min // 2

#: row widths of the two-stage prefix sum and top-k below (each the
#: width the TPU compiler handles fastest)
_SUM_ROW, _ROW = 1024, 128


def _prefix_sum(x):
    """Inclusive prefix sum of an int vector: per-row sums over rows of
    ``_SUM_ROW``, plus the prefix sum of the row totals. The same
    integers as ``jnp.cumsum``, whose TPU compile takes half a minute at
    a million elements (this form: about two seconds)."""
    n = x.shape[0]
    if n <= _SUM_ROW:
        return cumsum(x)
    rows = -(-n // _SUM_ROW)
    part = cumsum(jnp.pad(x, (0, rows * _SUM_ROW - n)).reshape(
        rows, _SUM_ROW), axis=1)
    tot = part[:, -1]
    return (part + (_prefix_sum(tot) - tot)[:, None]).reshape(-1)[:n]


def _top_k(x, k: int):
    """``jax.lax.top_k`` of a vector in two stages: the top ``k`` of
    each row of ``_ROW``, then the top ``k`` of those. Same values and
    indices — ties go to the lower index at both stages, and rows are in
    index order — at a tenth of the TPU compile time for tens of
    thousands of blocks."""
    n = x.shape[0]
    if n <= _ROW or k > _ROW:
        return jax.lax.top_k(x, k)
    rows = -(-n // _ROW)
    low = (jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer)
           else -jnp.inf)
    val, idx = jax.lax.top_k(jnp.pad(x, (0, rows * _ROW - n),
                                     constant_values=low).reshape(
        rows, _ROW), k)
    idx = idx + (jnp.arange(rows, dtype=idx.dtype) * _ROW)[:, None]
    top, pick = jax.lax.top_k(val.reshape(-1), k)
    return top, idx.reshape(-1)[pick]


# ----------------------------------------------------------------------
# cached-queue pull policies
# ----------------------------------------------------------------------

@dataclasses.dataclass
class PullView:
    """Per-block metadata a pull policy may rank on."""
    b_stamp: jnp.ndarray   # tick the block (re)entered the cached queue
    b_prio: jnp.ndarray    # worklist priority (max active-vertex priority)
    b_used: jnp.ndarray    # tick the block was last pulled (0 = never)
    t: jnp.ndarray         # current tick
    #: per-block I/O span in 4 KB slots (0 = memory-resident mini block);
    #: filled in by :meth:`Scheduler.pull` from its block table when the
    #: caller leaves it None
    b_span: jnp.ndarray | None = None
    #: per-block *fill* — the static block size (vertices + edges it
    #: holds, fixed at build time; NOT a live pool-residency measure):
    #: the work one pull can amortize. Filled in by
    #: :meth:`Scheduler.pull` when None. Unlike span (1 for every
    #: non-giant block), fill varies on low-skew graphs too, so
    #: fill-aware policies keep a signal there
    b_fill: jnp.ndarray | None = None
    #: per-block ACTIVE vertex count this tick — the *dynamic* work a
    #: pull retires right now, as opposed to the static ``b_fill``
    #: capacity. Filled in by :meth:`Scheduler.pull` from the worklist
    #: metadata it already receives
    b_nactive: jnp.ndarray | None = None


class PullPolicy:
    """Ranks CACHED blocks for execution; higher key is pulled sooner."""

    name = "base"

    def key(self, ready: jnp.ndarray, view: PullView) -> jnp.ndarray:
        raise NotImplementedError


class FifoPolicy(PullPolicy):
    """Paper default: oldest cached-queue entry first."""

    name = "fifo"

    def key(self, ready, view):
        return jnp.where(ready, -view.b_stamp, NEG_INF)


class PriorityPolicy(PullPolicy):
    """Beyond-paper: highest worklist priority first."""

    name = "priority"

    def key(self, ready, view):
        return jnp.where(ready, view.b_prio, NEG_INF)


class LruPolicy(PullPolicy):
    """Least-recently-executed first: anti-starvation round-robin that
    spreads executor time across the cached queue instead of letting a
    hot reactivated block monopolize the lanes."""

    name = "lru"

    def key(self, ready, view):
        return jnp.where(ready, -view.b_used, NEG_INF)


class HybridPolicy(PullPolicy):
    """Cost-aware: worklist priority × block fill.

    Pure ``priority`` loses to ``fifo`` on PPR at fast devices: it keeps
    draining small high-residual hub blocks, so each pull retires few
    slots and the preload queue starves behind the pool. Weighting the
    priority by the block's *fill* (its static size in vertices + edges)
    favors blocks whose execution amortizes the most buffered work per
    pull —
    at fast devices this behaves closer to throughput-ordered fifo,
    while on slow devices the priority factor still dominates (the
    regime where priority wins, see ``bench_device_sweep.py``).

    Fill, not span: the I/O span only exceeds 1 at giant vertices
    (deg > block_edges), so a span-weighted score degenerates to pure
    ``priority`` on low-skew graphs. Fill varies across blocks on any
    graph, keeping the cost signal alive (ROADMAP follow-on). When the
    caller provides no fill table the span is used as the fallback
    weight.

    Priorities are algorithm-defined and may be negative (BFS uses
    ``-dis``, WCC ``-label``), where a raw product would *invert* the
    fill preference; scores therefore rebase priority to >= 1 against
    the minimum over ready blocks before scaling, keeping the key
    monotone in both factors. Scores are float32 (int32 priority × fill
    overflows) and always >= 1 for ready blocks, so the engine's
    ``key > NEG_INF`` validity test is safe by construction.
    """

    name = "hybrid"

    def key(self, ready, view):
        fill = view.b_fill if view.b_fill is not None else view.b_span
        return _rebased_score(ready, view.b_prio, fill)


class HybridActivePolicy(PullPolicy):
    """Cost-aware like ``hybrid``, weighted by the *active* fill.

    ``hybrid`` weighs priority by the block's static size (everything
    resident), which overstates a pull's value once most of the block
    has gone quiet: a hub block with 2 active vertices left still
    outranks a small block that is fully active. Weighting by
    ``b_nactive`` — the live per-block active count the worklist
    already maintains — tracks the useful work *this* pull retires
    (ROADMAP follow-on to the fill-aware policy). Falls back to fill /
    span when the caller supplies no active counts.
    """

    name = "hybrid_active"

    def key(self, ready, view):
        w = view.b_nactive
        if w is None:
            w = view.b_fill if view.b_fill is not None else view.b_span
        return _rebased_score(ready, view.b_prio, w)


def _rebased_score(ready, prio, weight):
    """priority × weight with priority rebased >= 1 over ready blocks.

    Shared by the ``hybrid*`` policies: algorithm priorities may be
    negative (BFS ``-dis``, WCC ``-label``), where a raw product would
    invert the weight preference; rebasing against the ready-minimum
    keeps the key monotone in both factors. float32 (int32 products
    overflow), always >= 1 for ready blocks so the engine's
    ``key > NEG_INF`` validity test is safe by construction.
    """
    weight = jnp.maximum(weight, 1).astype(jnp.float32)
    prio = prio.astype(jnp.float32)
    pmin = jnp.min(jnp.where(ready, prio, jnp.inf))
    pmin = jnp.where(jnp.isfinite(pmin), pmin, 0.0)
    score = (prio - pmin + 1.0) * weight
    return jnp.where(ready, score, jnp.float32(NEG_INF))


CACHED_POLICIES: dict[str, type[PullPolicy]] = {
    p.name: p for p in (FifoPolicy, PriorityPolicy, LruPolicy,
                        HybridPolicy, HybridActivePolicy)
}


def make_pull_policy(name: str) -> PullPolicy:
    try:
        return CACHED_POLICIES[name]()
    except KeyError:
        raise ValueError(
            f"unknown cached_policy {name!r}; "
            f"available: {sorted(CACHED_POLICIES)}") from None


# ----------------------------------------------------------------------
# scheduler
# ----------------------------------------------------------------------

@dataclasses.dataclass
class CompletionResult:
    b_state: jnp.ndarray
    b_stamp: jnp.ndarray
    inflight: jnp.ndarray    # reads in flight BEFORE completions (i32):
    #                          a tick whose last read completes here was
    #                          still I/O-active, so occupancy accounting
    #                          must sample this, not the post-completion
    #                          count


@dataclasses.dataclass
class PreloadResult:
    b_state: jnp.ndarray
    b_deadline: jnp.ndarray  # per-block completion deadline (device time)
    used_slots: jnp.ndarray
    io_ops: jnp.ndarray      # submissions this tick (i32)
    io_blocks: jnp.ndarray   # 4 KB blocks submitted this tick (i32)
    inflight: jnp.ndarray    # reads in flight before this tick's submits
    #                          (post-completion: the queue-depth budget)
    sub_mask: jnp.ndarray    # bool[B]: block submitted this tick — the
    #                          per-block view of io_ops. An explicit mask,
    #                          NOT sub_spans > 0: zero-span submissions
    #                          exist (early-stop can evict a block_io==0
    #                          pseudo-block to UNCACHED) and still count
    #                          as ops in the solo accounting
    sub_spans: jnp.ndarray   # i32[B]: span submitted per block this tick
    #                          (0 elsewhere) — the per-block view of
    #                          io_blocks; the cross-query plane dedups
    #                          both with :meth:`Scheduler.split_shared_io`


@dataclasses.dataclass
class FinishResult:
    b_state: jnp.ndarray
    b_stamp: jnp.ndarray
    b_reuse: jnp.ndarray
    used_slots: jnp.ndarray
    blocks_reused: jnp.ndarray  # reactivated without eviction (i32)


class Scheduler:
    """Block-state control plane shared by every executor backend.

    Reads its arrays from ``tables`` (an
    :class:`~repro.core.executor.ExecTables`): ``block_io`` (its
    ``sched_io``) is per-block I/O cost in 4 KB slots, ``v_sched`` maps
    vertices to scheduling blocks, ``v_deg`` is the per-vertex degree
    table used for worklist priorities. ``device`` assigns every
    submitted block a completion deadline from its span and the queue
    depth (:class:`~repro.io_sim.device.DeviceModel`).
    """

    def __init__(self, tables, *, prefetch: int, lanes: int,
                 queue_depth: int, device: DeviceModel, policy: PullPolicy):
        #: :class:`~repro.core.executor.ExecTables` — inside a compiled
        #: program, its traced tables argument
        self.tables = tables
        self.block_io = tables.sched_io
        self.block_fill = tables.b_fill
        self.v_sched = tables.v_sched
        self.v_deg = tables.v_deg
        self.B = int(tables.sched_io.shape[0])
        self.P = int(prefetch)
        self.E = int(lanes)
        self.queue_depth = int(queue_depth)
        self.device = device
        self.policy = policy

    @staticmethod
    def block_segments(v_sched, num_blocks: int):
        """``(vs_first, vs_nonempty, run_left, max_run)`` of a
        block-sorted ``v_sched`` (host numpy): each block's first index,
        whether it owns a vertex, how many indices from each one to the
        end of its block's run (itself included), and the longest run."""
        # v_sched is block-sorted by construction (entities in offset
        # order, minis appended in chunk order); the worklist reductions
        # below rely on it to avoid XLA's serial-scatter segment ops.
        # Hard error (not assert): a violation silently mis-buckets
        # every count/priority under python -O
        vs = np.asarray(v_sched)
        if not (np.diff(vs) >= 0).all():
            raise ValueError(
                "v_sched must be block-sorted (non-decreasing); the "
                "prefix-sum/segmented-max worklist reductions are only "
                "exact over a block-contiguous vertex order")
        vs_first = np.searchsorted(vs, np.arange(num_blocks + 1))
        run_end = vs_first[np.minimum(vs + 1, num_blocks)]
        return (vs_first.astype(np.int32), vs_first[1:] > vs_first[:-1],
                (run_end - np.arange(vs.shape[0])).astype(np.int32),
                int(np.diff(vs_first).max(initial=1)))

    # ---- worklist metadata -------------------------------------------
    def _block_counts(self, front):
        """segment_sum(front) over the block-sorted vertex order, as a
        prefix sum differenced at block boundaries (vectorized — 5-10x
        faster than XLA's scatter-based segment_sum on CPU, identical
        values)."""
        s = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             _prefix_sum(front.astype(jnp.int32))])
        at = s[self.tables.vs_first]   # one gather for both boundaries
        return at[1:] - at[:-1]

    def _block_prio(self, front, v_prio):
        """segment_max(where(front, v_prio, NEG_INF)) over the
        block-sorted order, by doubling: after step j each index holds
        the max of the next 2**j indices of its own block, so
        ceil(log2(longest block)) shifted maxima leave each block's max
        at its first index. Bit-identical values, including the
        empty-block identity (int32 min); no scan, which the TPU
        compiler takes minutes over at a million vertices."""
        t = self.tables
        x = jnp.where(front, v_prio, NEG_INF)
        step = 1
        while step < t.max_run:
            ahead = jnp.concatenate([x[step:], x[:step]])
            x = jnp.where(step < t.run_left, jnp.maximum(x, ahead), x)
            step *= 2
        first = jnp.minimum(t.vs_first[:-1], x.shape[0] - 1)
        return jnp.where(t.vs_nonempty, x[first], jnp.iinfo(jnp.int32).min)

    def resident_count(self, b_state, mask):
        """Number of ``mask`` vertices whose block is resident (LOADING or
        CACHED), reduced per block over the block-sorted order. Gathering
        each vertex's block state instead costs a V-long gather: about
        7.6 ms of a 13 ms tick on one v5e chip at V = 1.07 M."""
        resident = (b_state == S_CACHED) | (b_state == S_LOADING)
        return jnp.sum(jnp.where(resident, self._block_counts(mask), 0)
                       ).astype(jnp.int32)

    def refresh(self, algo, state, front):
        """Per-block active counts and priorities (worklist metadata)."""
        v_prio = algo.priority(state, self.v_deg).astype(jnp.int32)
        return self._block_counts(front), self._block_prio(front, v_prio)

    def refresh_delta(self, algo, state, front_new, v_prio_old, b_prio,
                      eidx, lane_valid):
        """Incremental worklist refresh — exact, not approximate.

        The full :meth:`refresh` re-reduces all V vertices into B blocks
        every tick even when a handful of vertices changed. This
        maintains the same metadata from the tick's per-lane windows
        instead; every lane routes through its block's *bucket* tile
        (``lax.switch``), so the work executed is proportional to the
        blocks actually pulled, not the worst block in the graph:

          * **counts** — the sorted-prefix-sum of :meth:`_block_counts`
            (vectorized, no scatter);
          * **priorities of pulled blocks** — a vertex can only *leave*
            the frontier by being processed, and processed vertices live
            in the pulled lanes' windows, which span each pulled block's
            entire vertex range; the new block max is recomputed exactly
            inside each lane's window (this also covers ``on_process``
            state mutation, e.g. PPR residual consumption);
          * **priorities of touched destinations** — all destinations a
            lane's scatter touched lie in its block's contiguous edge
            window; priorities move *up* elsewhere (activations,
            residual adds), so an idempotent ``scatter-max`` of the
            window's active destinations is exact. Extra window slots
            (neighboring blocks' edges inside the tile) only ever
            contribute a true priority of a true frontier vertex to its
            own block — never above that block's max;
          * **rebuild guard** — an active destination in a *non-pulled*
            block whose priority moved *down* off its block's max
            (possible only when ``priority`` depends on mutated non-key
            state in a non-monotone way) cannot be fixed by a monotone
            scatter-max; such ticks fall back to the full reduction
            under ``lax.cond`` (never taken by the six stock
            algorithms).

        Contract: ``on_process`` may only modify rows of processed
        vertices, and activation implies a key change (both hold for
        every paper algorithm — they are the semantics of Alg. 1).

        **Windowed priority (PR 6):** when the algorithm defines
        ``priority_at``, the all-V ``algo.priority(state, deg)``
        re-evaluation is skipped too. ``v_prio`` starts from the carried
        ``v_prio_old`` and is re-evaluated only inside each pulled
        lane's vertex window and at its edge window's destinations —
        the only positions whose state rows the tick may have mutated
        (same contract as the count/priority windows above). Every
        position a lane *reads* (its own window max, its own scatter
        destinations) it has already re-evaluated, and cross-lane
        duplicate writes carry identical post-tick values, so the
        threaded ``v_prio`` is exact wherever it is consumed. The
        full-rebuild ``lax.cond`` recomputes ``v_prio`` over all V in
        this mode, because wide-tile lanes' windows were never walked.

        Returns ``(b_nactive', b_prio', v_prio')`` where ``v_prio'`` is
        the per-vertex priority under the post-tick state (carried so
        the next tick can detect downward moves without re-evaluating
        the old state).
        """
        i32 = jnp.int32
        imin = jnp.iinfo(jnp.int32).min
        t = self.tables
        V = int(self.v_sched.shape[0])
        windowed_prio = algo.priority_at is not None
        if windowed_prio:
            v_prio = v_prio_old
        else:
            v_prio = algo.priority(state, self.v_deg).astype(i32)
        nact2 = self._block_counts(front_new)
        pulled = jnp.zeros(self.B, bool).at[eidx].max(lane_valid)

        def lane_branch(tile):
            def br(op):
                prio2, v_prio, e, valid = op
                first = t.sched_first[e]
                end = t.sched_first[e + 1]
                vids = first + jnp.arange(tile.Vm, dtype=i32)
                vc = jnp.minimum(vids, t.V - 1)
                if windowed_prio:
                    # processed sources live in this window: re-evaluate
                    # their priority here, before the reads below.
                    # Masked slots route to index V, dropped by scatter
                    upd = (vids < end) & valid
                    pv = algo.priority_at(state, vc,
                                          self.v_deg[vc]).astype(i32)
                    v_prio = v_prio.at[jnp.where(upd, vc, t.V)].set(
                        pv, mode="drop")
                act = (vids < end) & valid & front_new[vc]
                lm = jnp.max(jnp.where(act, v_prio[vc], NEG_INF))
                prio2 = prio2.at[e].set(jnp.where(valid, lm, prio2[e]))
                dst = edge_window(t, first, tile.EK)
                dvalid = valid & (dst >= 0)
                dc = jnp.maximum(dst, 0)
                if windowed_prio:
                    # scatter destinations: duplicate dc entries write
                    # identical post-tick values, so order is immaterial
                    pd = algo.priority_at(state, dc,
                                          self.v_deg[dc]).astype(i32)
                    v_prio = v_prio.at[jnp.where(dvalid, dc, t.V)].set(
                        pd, mode="drop")
                db = self.v_sched[dc]
                dmask = dvalid & front_new[dc]
                # imin fill: a no-op even against an empty block's
                # identity (which sits below NEG_INF)
                prio2 = prio2.at[jnp.where(dvalid, db, 0)].max(
                    jnp.where(dmask, v_prio[dc], imin))
                drop = dmask & ~pulled[db] & (v_prio[dc] < v_prio_old[dc]) \
                    & (v_prio_old[dc] == b_prio[db])
                return prio2, v_prio, jnp.any(drop)
            return br

        # a tile whose window rivals V costs more than the vectorized
        # full reduction (scatter updates are ~an order of magnitude
        # slower per element than a scan pass): lanes routed to such
        # tiles trigger ONE exact full rebuild below instead — only on
        # ticks that actually pull such a block
        windowed = [(tile.Vm + 2 * tile.EK) * 6 <= V for tile in t.tiles]
        lane_bucket = t.b_bucket[eidx]
        prio2 = b_prio
        any_drop = jnp.zeros((), bool)
        need_full = jnp.zeros((), bool)
        if not all(windowed):
            is_wide = jnp.asarray([not w for w in windowed])
            need_full = jnp.any(lane_valid & is_wide[lane_bucket])
        if any(windowed):
            cheapest = min(
                (k for k in range(len(t.tiles)) if windowed[k]),
                key=lambda k: t.tiles[k].Vm + t.tiles[k].EK)
            branches = [lane_branch(tile) if w else lane_branch(
                t.tiles[cheapest]) for tile, w in zip(t.tiles, windowed)]
            use_window = jnp.asarray(np.array(windowed))

            def lane(i, op):
                prio2, v_prio, any_drop = op
                valid = lane_valid[i] & use_window[lane_bucket[i]]
                op = (prio2, v_prio, eidx[i], valid)
                if len(branches) == 1:
                    prio2, v_prio, drop = branches[0](op)
                else:
                    k = jnp.where(valid, lane_bucket[i], cheapest)
                    prio2, v_prio, drop = jax.lax.switch(k, branches, op)
                return prio2, v_prio, any_drop | drop

            prio2, v_prio, any_drop = jax.lax.fori_loop(
                0, eidx.shape[0], lane, (prio2, v_prio, any_drop))

        def _full_rebuild(args):
            prio2, v_prio = args
            if windowed_prio:
                # wide-tile lanes never walked their windows, so the
                # threaded v_prio may be stale — recompute it whole
                v_prio = algo.priority(state, self.v_deg).astype(i32)
            return self._block_prio(front_new, v_prio), v_prio

        prio2, v_prio = jax.lax.cond(
            any_drop | need_full, _full_rebuild, lambda a: a,
            (prio2, v_prio))
        return nact2, prio2, v_prio

    def initial_block_state(self, nact: jnp.ndarray) -> jnp.ndarray:
        return jnp.where(nact > 0,
                         jnp.where(self.block_io > 0, S_UNCACHED, S_CACHED),
                         S_INACTIVE).astype(jnp.int32)

    # ---- stage 1: async I/O completions ------------------------------
    def complete_io(self, b_state, b_deadline, b_stamp,
                    t) -> CompletionResult:
        """Retire LOADING blocks whose device deadline has passed."""
        inflight = jnp.sum(b_state == S_LOADING).astype(jnp.int32)
        done = (b_state == S_LOADING) & (t >= b_deadline)
        b_state = jnp.where(done, S_CACHED, b_state)
        b_stamp = jnp.where(done, t, b_stamp)
        return CompletionResult(b_state=b_state, b_stamp=b_stamp,
                                inflight=inflight)

    # ---- stage 2: preload priority queue -----------------------------
    def preload(self, b_state, b_deadline, b_prio, b_nactive, used_slots,
                pool: BufferPool, t) -> PreloadResult:
        i32 = jnp.int32
        inflight = jnp.sum(b_state == S_LOADING)
        want = (b_state == S_UNCACHED) & (b_nactive > 0)
        pkey = jnp.where(want, b_prio, NEG_INF)
        _, pidx = _top_k(pkey, self.P)
        pvalid = pkey[pidx] > NEG_INF
        budget = jnp.clip(self.queue_depth - inflight, 0, self.P)
        within = jnp.arange(self.P, dtype=i32) < budget
        spans = self.block_io[pidx]
        take, used_slots = pool.admit(used_slots, spans, pvalid & within)
        b_state = b_state.at[pidx].set(
            jnp.where(take, S_LOADING, b_state[pidx]))
        lat = self.device.latency_ticks(spans, self.queue_depth)
        b_deadline = b_deadline.at[pidx].set(
            jnp.where(take, t + lat, b_deadline[pidx]))
        sub_mask = jnp.zeros(self.B, bool).at[pidx].max(take)
        sub_spans = jnp.zeros(self.B, i32).at[pidx].add(
            jnp.where(take, spans, 0))
        return PreloadResult(
            b_state=b_state, b_deadline=b_deadline, used_slots=used_slots,
            io_ops=jnp.sum(take).astype(i32),
            io_blocks=jnp.sum(spans * take).astype(i32),
            inflight=inflight, sub_mask=sub_mask, sub_spans=sub_spans)

    # ---- stage 3: pull from the cached queue -------------------------
    def pull(self, b_state, b_nactive, view: PullView):
        """Select up to ``lanes`` cached blocks for execution.

        Returns ``(eidx, lane_valid, b_used')`` where ``b_used`` records
        the pull tick for the LRU policy.
        """
        if view.b_span is None:
            view = dataclasses.replace(view, b_span=self.block_io)
        if view.b_fill is None:
            view = dataclasses.replace(view, b_fill=self.block_fill)
        if view.b_nactive is None:
            view = dataclasses.replace(view, b_nactive=b_nactive)
        ready = (b_state == S_CACHED) & (b_nactive > 0)
        ekey = self.policy.key(ready, view)
        _, eidx = _top_k(ekey, self.E)
        lane_valid = ekey[eidx] > NEG_INF
        b_used = view.b_used.at[eidx].set(
            jnp.where(lane_valid, view.t + 1, view.b_used[eidx]))
        return eidx, lane_valid, b_used

    # ---- cross-query worklist: physical/shared I/O split -------------
    @staticmethod
    def split_shared_io(resident, sub_mask, sub_spans):
        """Aggregate per-query preload submissions across a query batch.

        ``resident[q, b]`` — block ``b`` held resident (LOADING or
        CACHED) by query ``q`` at the START of this tick; ``sub_mask[q,
        b]`` / ``sub_spans[q, b]`` — whether / how many 4 KB slots
        query ``q`` submitted for ``b`` THIS tick (the mask is
        explicit because zero-span submissions exist and count as
        ops). A submission is *physical* (it actually touches the
        device) only if no query already holds the block and no
        earlier-indexed query submitted it this same tick; every other
        submission is *shared* — served by the in-flight read or the
        resident copy another query's worklist already paid for. This
        is the cross-query worklist's I/O dedup: per-query counts split
        exactly, ``physical + shared == solo logical I/O``.

        Queries only ever submit blocks they do not hold (preload takes
        UNCACHED blocks), so ``resident`` rows never mask a query's own
        submissions. Returns per-query i32 vectors
        ``(io_ops_phys, io_blocks_phys, io_ops_shared,
        io_blocks_shared)``.
        """
        i32 = jnp.int32
        subm = sub_mask
        resident_any = jnp.any(resident, axis=0)
        qidx = jnp.arange(subm.shape[0])[:, None]
        first = jnp.argmax(subm, axis=0)        # first submitter per block
        phys = subm & ~resident_any[None, :] & (qidx == first[None, :])
        shared = subm & ~phys
        spans = lambda m: jnp.sum(jnp.where(m, sub_spans, 0),
                                  axis=1).astype(i32)
        count = lambda m: jnp.sum(m, axis=1).astype(i32)
        return count(phys), spans(phys), count(shared), spans(shared)

    # ---- cross-query worklist: aggregated pull order -----------------

    #: progress-fairness priority band width: each query's rebased
    #: priorities are clipped into [1, FAIRNESS_BAND] and queries are
    #: stacked in disjoint bands by remaining work, so Q * band must
    #: stay well inside int32 (2**20 leaves room for Q up to ~2000)
    FAIRNESS_BAND = 1 << 20

    @staticmethod
    def aggregate_worklist(b_nactive, b_prio, fairness: str = "none"):
        """Merge Q per-query worklists into ONE (aggregated batch mode).

        ``b_nactive[q, b]`` / ``b_prio[q, b]`` — query ``q``'s per-block
        active count / frontier priority max. Returns ``(nact_agg,
        prio_agg)``, the single worklist the merged tick schedules by:

          * ``nact_agg[b] = sum_q b_nactive[q, b]`` — the cross-query
            refcount; a block *finishes* only when no query has work in
            it, which is exactly what finish/activate/pool accounting
            need on the merged plane;
          * ``prio_agg[b] = max_q rebased(b_prio[q, b])`` where each
            query's ACTIVE block priorities are first rebased to >= 1
            against that query's own active minimum. Per-query rebasing
            before the cross-query max keeps one query's priority scale
            (e.g. BFS ``-dis`` in ``[-V, 0]``) from drowning out
            another's — every query's most-urgent block competes at the
            same magnitude. Blocks with no active query get ``NEG_INF``
            so preload/pull skip them.

        ``fairness="progress"`` additionally weights the merge by
        per-query *progress* so a huge-frontier query cannot starve a
        near-done one (the mid-flight-admission hazard: a freshly
        admitted query's giant frontier would otherwise monopolize the
        shared pull order for the whole tail of an almost-finished
        query). Queries are ranked by ascending remaining active-vertex
        count; each query's rebased priorities are clipped into
        ``[1, FAIRNESS_BAND]`` and offset by ``(Q-1-rank) * band``,
        placing every query in its own disjoint priority band.
        **Fairness bound** (asserted in ``test_aggregated.py``): every
        block the least-remaining query has work in strictly outranks
        every block it does not — the near-done query's tail is always
        at the front of the merged preload/pull order, so it finishes
        within its own solo tail length regardless of co-runners.

        Legal only for schedule-independent algorithms (see
        ``api.aggregation_eligible``): the merged order is *some* valid
        async order for each query, so every per-query fixed point is
        unchanged even though the schedule differs from solo.
        """
        i32 = jnp.int32
        imax = jnp.iinfo(jnp.int32).max
        active = b_nactive > 0                            # [Q, B]
        nact_agg = jnp.sum(b_nactive, axis=0).astype(i32)
        has = jnp.any(active, axis=1, keepdims=True)      # [Q, 1]
        pmin = jnp.min(jnp.where(active, b_prio, imax), axis=1,
                       keepdims=True)
        reb = jnp.where(active,
                        b_prio - jnp.where(has, pmin, 0) + 1, NEG_INF)
        if fairness == "progress":
            band = Scheduler.FAIRNESS_BAND
            Q = b_nactive.shape[0]
            remaining = jnp.sum(b_nactive, axis=1)        # [Q]
            # queries with NO work sort last (their rows are NEG_INF
            # anyway); ties break by query index via stable argsort
            order = jnp.argsort(jnp.where(remaining > 0, remaining,
                                          imax), stable=True)
            rank = jnp.argsort(order, stable=True)        # [Q]
            boost = ((Q - 1 - rank) * band).astype(i32)
            reb = jnp.where(active,
                            jnp.clip(reb, 1, band) + boost[:, None],
                            NEG_INF)
        elif fairness != "none":
            raise ValueError(
                f"unknown fairness {fairness!r}; "
                "available: ['none', 'progress']")
        prio_agg = jnp.max(reb, axis=0).astype(i32)
        return nact_agg, prio_agg

    # ---- continuous-serving hooks: admission / retirement ------------
    def reactivate_on_admit(self, b_state, b_stamp, nact_agg, t):
        """Wake the blocks a mid-flight admission's frontier activates.

        A query admitted into a RUNNING batch lands between ticks, so
        the shared block states were computed against the *old* merged
        worklist: blocks the newcomer needs may sit INACTIVE. This is
        the admission-time counterpart of the tick's stage-8
        :meth:`activate` — INACTIVE blocks with work under the new
        cross-query refcount re-enter the preload queue (UNCACHED) or
        the cached queue directly (zero-I/O pseudo-blocks). Blocks
        already UNCACHED/LOADING/CACHED are untouched: an in-flight or
        resident copy serves the newcomer as shared I/O, exactly like
        any other cross-query hit.
        """
        return self.activate(b_state, b_stamp, nact_agg, t)

    def reclaim_idle(self, b_state, used_slots, nact_agg,
                     pool: BufferPool):
        """Release residency no live query needs (retirement hook).

        In a drain-to-idle batch, a retired query's CACHED blocks stay
        resident harmlessly — the loop ends soon. A continuous service
        never drains, so retirement must give slots back or the shared
        pool ratchets full and admission of the *next* query starves.
        Releases CACHED blocks whose cross-query active refcount is
        zero (→ INACTIVE; stage-8 activation re-admits them if a later
        query wakes them). Runs only at retirement events, not per
        tick, so mid-run reuse residency (``blocks_reused``) is
        unaffected. Returns ``(b_state, used_slots)``.
        """
        released = (b_state == S_CACHED) & (nact_agg == 0)
        b_state = jnp.where(released, S_INACTIVE, b_state)
        used_slots = pool.release(used_slots, released)
        return b_state, used_slots

    # ---- stage 7: finish / reactivation / eviction -------------------
    def finish(self, b_state, b_stamp, b_reuse, b_nactive2, eidx,
               lane_valid, used_slots, pool: BufferPool, t) -> FinishResult:
        pulled = jnp.zeros(self.B, bool).at[eidx].max(lane_valid)
        reactivated = pulled & (b_nactive2 > 0)
        evict, b_reuse = pool.reuse_evictions(b_reuse, pulled, reactivated)
        finished = pulled & (b_nactive2 == 0)
        released = (finished | evict) & (b_state == S_CACHED)
        b_state = jnp.where(finished, S_INACTIVE, b_state)
        b_state = jnp.where(evict, S_UNCACHED, b_state)
        b_stamp = jnp.where(reactivated & ~evict, t, b_stamp)
        b_reuse = jnp.where(evict, 0, b_reuse)
        used_slots = pool.release(used_slots, released)
        return FinishResult(
            b_state=b_state, b_stamp=b_stamp, b_reuse=b_reuse,
            used_slots=used_slots,
            blocks_reused=jnp.sum(reactivated & ~evict).astype(jnp.int32))

    # ---- stage 8: activation transitions for inactive blocks ---------
    def activate(self, b_state, b_stamp, b_nactive2, t):
        newly = (b_state == S_INACTIVE) & (b_nactive2 > 0)
        b_state = jnp.where(newly & (self.block_io > 0), S_UNCACHED,
                            b_state)
        goes_cached = newly & (self.block_io == 0)
        b_state = jnp.where(goes_cached, S_CACHED, b_state)
        b_stamp = jnp.where(goes_cached, t, b_stamp)
        return b_state, b_stamp

    # ---- stage 9: synchronous barrier (Sec. 4.3) ---------------------
    def barrier(self, algo, state, front2, front_next, b_state,
                b_nactive2, b_prio2, used_slots, pool: BufferPool,
                lazy: bool = False):
        """Swap in the next-iteration worklist once the current one and
        all in-flight I/O drain. Resident blocks with work stay; the rest
        are released. ``lazy`` computes the swapped worklist's metadata
        under ``lax.cond`` — only on the (rare) barrier tick — instead
        of reducing all V vertices every tick and discarding the result;
        the selected values are identical either way."""
        inflight_now = jnp.any(b_state == S_LOADING)
        barrier = (~jnp.any(front2)) & (~inflight_now) \
            & jnp.any(front_next)
        front2 = jnp.where(barrier, front_next, front2)
        front_next = jnp.where(barrier, False, front_next)
        if lazy:
            b_nactive2, b_prio2 = jax.lax.cond(
                barrier,
                lambda: self.refresh(algo, state, front2),
                lambda: (b_nactive2, b_prio2))
        else:
            nact_b, prio_b = self.refresh(algo, state, front2)
            b_nactive2 = jnp.where(barrier, nact_b, b_nactive2)
            b_prio2 = jnp.where(barrier, prio_b, b_prio2)
        drop = barrier & (b_state == S_CACHED) & (b_nactive2 == 0)
        used_slots = pool.release(used_slots, drop)
        b_state = jnp.where(drop, S_INACTIVE, b_state)
        wake = barrier & (b_state == S_INACTIVE) & (b_nactive2 > 0)
        b_state = jnp.where(wake & (self.block_io > 0), S_UNCACHED,
                            b_state)
        b_state = jnp.where(wake & (self.block_io == 0), S_CACHED,
                            b_state)
        return (front2, front_next, b_state, b_nactive2, b_prio2,
                used_slots, barrier)
