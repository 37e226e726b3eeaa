"""Paper Fig. 2: disk read volume under OPT / SUB / LRU buffer-pool
policies (synchronous execution) vs. ACGraph's asynchronous engine.

The synchronous block-request stream is derived exactly: per BFS level /
WCC iteration, the set of blocks owning frontier vertices (in block-id
order, as a synchronous system would scan them). OPT is Belady's optimal
eviction, SUB evicts blocks unused in the next iteration, LRU is standard.
ACGraph's line is the async engine's measured I/O with a ~1% buffer.

Also sweeps the engine's own pluggable cached-queue pull policies
(``fifo`` / ``priority`` / ``lru``, see ``core/scheduler.py``) on the
same workloads — the async analogue of the eviction-policy question:
which cached block should the executor drain first?
"""
from __future__ import annotations

import collections

import numpy as np

from benchmarks.common import bench_graph, emit, make_session
from repro.algorithms import BFS, WCC


def sync_block_trace(hg, levels, v_sched, n_blocks):
    """Per-iteration block request lists from per-vertex 'levels'."""
    trace = []
    iters = int(levels[levels >= 0].max()) + 1 if (levels >= 0).any() else 0
    for it in range(iters):
        vs = np.where(levels == it)[0]
        blocks = np.unique(v_sched[vs])
        blocks = blocks[blocks >= 0]
        trace.append(blocks.tolist())
    return trace


def simulate(trace, capacity, policy):
    """Returns number of block loads under the given eviction policy."""
    flat = [b for it in trace for b in it]
    nxt_use = collections.defaultdict(list)   # block -> positions
    for i, b in enumerate(flat):
        nxt_use[b].append(i)
    iter_of = []
    for it, blocks in enumerate(trace):
        iter_of += [it] * len(blocks)

    cache: dict[int, int] = {}   # block -> last use position
    loads = 0
    for i, b in enumerate(flat):
        nxt_use[b].pop(0)
        if b in cache:
            cache[b] = i
            continue
        loads += 1
        if len(cache) >= capacity:
            if policy == "lru":
                victim = min(cache, key=cache.get)
            elif policy == "opt":
                victim = max(cache, key=lambda x: nxt_use[x][0]
                             if nxt_use[x] else 1 << 60)
            elif policy == "sub":
                cur_it = iter_of[i]
                unused_next = [x for x in cache
                               if not any(iter_of[p] == cur_it + 1
                                          for p in nxt_use[x][:1])]
                victim = unused_next[0] if unused_next else \
                    next(iter(cache))
            else:
                raise ValueError(policy)
            del cache[victim]
        cache[b] = i
    return loads


def pull_policy_sweep() -> None:
    """Engine cached-queue policy sweep: measured I/O + ticks per policy."""
    from repro.core.scheduler import CACHED_POLICIES

    for algo_name, query in (("bfs", BFS(0)), ("wcc", WCC())):
        g = bench_graph(scale=11, symmetric=(algo_name == "wcc"))
        for policy in sorted(CACHED_POLICIES):
            sess = make_session(g, pool_slots=32, cached_policy=policy)
            m = sess.run(query).metrics
            emit(f"pull_policy_{algo_name}_{policy}", 0.0,
                 f"io_{m.io_blocks}_ticks_{m.ticks}_edges_"
                 f"{m.edges_scanned}")


def main() -> None:
    pull_policy_sweep()
    for algo_name in ("bfs", "wcc"):
        g = bench_graph(scale=11, symmetric=(algo_name == "wcc"))
        sess = make_session(g, pool_slots=32)
        if algo_name == "bfs":
            res = sess.run(BFS(0))
            m_async = res.metrics
            levels = np.where(res.result >= 2 ** 29, -1, res.result)
        else:
            # WCC has no per-vertex level structure; the sync trace below
            # is approximated as rounds over all active blocks instead
            m_async = sess.run(WCC()).metrics
            levels = None
        # the sync-trace simulator needs the block layout: an engine
        # internal, accessed through the session it belongs to
        eng, hg = sess.engine, sess.hg
        v_sched = np.asarray(eng.tables.v_sched).copy()
        v_sched[~np.asarray(sess.ctx.is_real)] = -1
        orig_sched = v_sched[sess.ctx.v2id]

        if algo_name == "bfs":
            trace = sync_block_trace(hg, levels, orig_sched, eng.B)
        else:
            # all vertices active for the first iterations (work inflation):
            # approximate the sync trace as 3 rounds over all active blocks
            blocks = np.unique(orig_sched[orig_sched >= 0])
            trace = [blocks.tolist()] * 3
        total_blocks = len({b for it in trace for b in it})
        for frac in (0.02, 0.05, 0.10, 0.20):
            cap = max(4, int(total_blocks * frac))
            for pol in ("opt", "sub", "lru"):
                loads = simulate(trace, cap, pol)
                emit(f"fig2_{algo_name}_{pol}_buf{int(frac*100)}pct",
                     0.0, f"{loads}_block_loads")
        emit(f"fig2_{algo_name}_acgraph_async", 0.0,
             f"{m_async.io_blocks}_block_loads")


if __name__ == "__main__":
    main()
