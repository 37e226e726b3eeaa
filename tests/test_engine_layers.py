"""Unit tests for the layered engine tick: scheduler (block-state
transitions, preload queue, pull policies), buffer pool (slot
accounting, early-stop eviction), and executor backends — each tier
exercised in isolation, outside the engine's while_loop — and the
compiled programs' contract with them: the graph tables are an argument
of every program, and a loop cut at ``max_ticks`` raises."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import BFS, bfs_batch
from repro.algorithms.bfs import bfs_algorithm
from repro.core import ContinuousService, GraphSession
from repro.core.engine import Engine, EngineConfig, _c64_add, _c64_int, \
    _c64_zero
from repro.core.executor import ExecTables, make_executor
from repro.core.pool import BufferPool
from repro.core.scheduler import (CACHED_POLICIES, NEG_INF, S_CACHED,
                                  S_INACTIVE, S_LOADING, S_UNCACHED,
                                  PullView, Scheduler, _prefix_sum, _top_k,
                                  make_pull_policy)
from repro.io_sim.device import DeviceModel, UniformDevice
from repro.storage.csr import from_edges
from repro.storage.hybrid import build_hybrid
from repro.storage.rmat import rmat_graph

I32 = jnp.int32


def arr(vals, dtype=I32):
    return jnp.asarray(vals, dtype=dtype)


# ----------------------------------------------------------------------
# 64-bit counters (uint32 limb pairs; jax_enable_x64 stays off)
# ----------------------------------------------------------------------

def test_counter_limbs_carry_past_int32():
    c = _c64_zero()
    big = jnp.asarray(2 ** 31 - 1, I32)  # max int32 increment
    for _ in range(5):
        c = _c64_add(c, big)
    assert _c64_int(c) == 5 * (2 ** 31 - 1)  # > int32 and > uint32 range


def test_counter_limbs_small_increments():
    c = _c64_add(_c64_zero(), jnp.asarray(7, I32))
    assert _c64_int(c) == 7


# ----------------------------------------------------------------------
# buffer pool
# ----------------------------------------------------------------------

def test_pool_admit_respects_capacity_prefix():
    pool = BufferPool(slots=4, block_io=arr([2, 2, 2]))
    spans = arr([2, 2, 2])
    want = jnp.asarray([True, True, True])
    take, used = pool.admit(jnp.zeros((), I32), spans, want)
    # only the first two candidates fit in 4 slots
    assert np.asarray(take).tolist() == [True, True, False]
    assert int(used) == 4


def test_pool_admit_skips_unwanted_candidates():
    pool = BufferPool(slots=4, block_io=arr([1, 1, 1]))
    take, used = pool.admit(jnp.zeros((), I32), arr([3, 3, 1]),
                            jnp.asarray([True, False, True]))
    assert np.asarray(take).tolist() == [True, False, True]
    assert int(used) == 4


def test_pool_release_returns_slots():
    pool = BufferPool(slots=8, block_io=arr([3, 2, 1]))
    used = pool.release(jnp.asarray(6, I32),
                        jnp.asarray([True, False, True]))
    assert int(used) == 2


def test_pool_reuse_eviction_threshold():
    pool = BufferPool(slots=8, block_io=arr([1, 1, 1]), early_stop=2)
    b_reuse = arr([2, 2, 0])
    pulled = jnp.asarray([True, True, True])
    reactivated = jnp.asarray([True, False, True])
    evict, b_reuse = pool.reuse_evictions(b_reuse, pulled, reactivated)
    # block 0: counter 3 > 2 -> evicted; block 1 exhausted -> reset;
    # block 2: first reactivation, counter 1
    assert np.asarray(evict).tolist() == [True, False, False]
    assert np.asarray(b_reuse).tolist() == [3, 0, 1]


def test_pool_early_stop_disabled_never_evicts():
    pool = BufferPool(slots=8, block_io=arr([1]), early_stop=0)
    evict, _ = pool.reuse_evictions(arr([99]), jnp.asarray([True]),
                                    jnp.asarray([True]))
    assert not bool(evict[0])


# ----------------------------------------------------------------------
# scheduler
# ----------------------------------------------------------------------

def make_sched(B=4, policy="fifo", block_io=None, v_sched=None,
               v_deg=None, **kw):
    """A scheduler over tables holding only what it reads; block fill
    equals the I/O span."""
    block_io = arr([1] * B) if block_io is None else block_io
    v_sched = arr([0]) if v_sched is None else v_sched
    v_deg = arr([0]) if v_deg is None else v_deg
    V = v_sched.shape[0]
    vs_first, vs_nonempty, run_left, max_run = Scheduler.block_segments(
        v_sched, B)
    zv, zb = jnp.zeros(V, I32), jnp.zeros(B, I32)
    tables = ExecTables(
        all_edges=zv, v_start=zv, v_deg=v_deg, v_sched=v_sched,
        is_real=jnp.ones(V, bool), sched_first=jnp.zeros(B + 1, I32),
        sched_io=block_io, b_bucket=zb, b_fill=block_io, b_edges=zb,
        vs_first=jnp.asarray(vs_first), vs_nonempty=jnp.asarray(vs_nonempty),
        run_left=jnp.asarray(run_left), V=V, max_run=max_run, tail_pad=0,
        tiles=())
    defaults = dict(prefetch=B, lanes=2, queue_depth=8,
                    device=UniformDevice(latency=1))
    defaults.update(kw)
    return Scheduler(tables, policy=make_pull_policy(policy), **defaults)


def test_complete_io_after_deadline():
    sched = make_sched(device=UniformDevice(latency=2))
    b_state = arr([S_LOADING, S_LOADING, S_UNCACHED, S_INACTIVE])
    b_deadline = arr([2, 5, 0, 0])  # issued at 0 and 3, latency 2
    comp = sched.complete_io(b_state, b_deadline, jnp.zeros(4, I32),
                             jnp.asarray(4, I32))
    # deadline 2 <= 4 completes; deadline 5 still in flight
    assert np.asarray(comp.b_state).tolist() == [S_CACHED, S_LOADING,
                                                 S_UNCACHED, S_INACTIVE]
    assert int(comp.b_stamp[0]) == 4
    # occupancy is sampled BEFORE completions: both reads were in flight
    assert int(comp.inflight) == 2


def test_preload_picks_highest_priority_within_budget():
    sched = make_sched(B=4, prefetch=2)
    pool = BufferPool(slots=64, block_io=sched.block_io)
    b_state = arr([S_UNCACHED] * 4)
    b_prio = arr([1, 9, 5, 3])
    pre = sched.preload(b_state, jnp.zeros(4, I32), b_prio,
                        arr([1, 1, 1, 1]), jnp.zeros((), I32), pool,
                        jnp.asarray(0, I32))
    st = np.asarray(pre.b_state).tolist()
    # top-2 by priority (blocks 1 and 2) go to LOADING
    assert st == [S_UNCACHED, S_LOADING, S_LOADING, S_UNCACHED]
    assert int(pre.io_ops) == 2 and int(pre.io_blocks) == 2
    assert int(pre.used_slots) == 2


def test_preload_honors_queue_depth():
    sched = make_sched(B=4, prefetch=4, queue_depth=3)
    pool = BufferPool(slots=64, block_io=sched.block_io)
    b_state = arr([S_LOADING, S_LOADING, S_UNCACHED, S_UNCACHED])
    pre = sched.preload(b_state, jnp.zeros(4, I32), arr([0, 0, 5, 9]),
                        arr([0, 0, 1, 1]), jnp.asarray(2, I32), pool,
                        jnp.asarray(0, I32))
    # 2 in flight, depth 3 -> only one new submission (highest prio = 3)
    assert int(pre.io_ops) == 1
    assert np.asarray(pre.b_state).tolist()[3] == S_LOADING
    assert int(pre.inflight) == 2


def test_activate_routes_by_io_cost():
    sched = make_sched(B=3, block_io=arr([1, 0, 1]))
    b_state, b_stamp = sched.activate(
        arr([S_INACTIVE, S_INACTIVE, S_INACTIVE]), jnp.zeros(3, I32),
        arr([2, 2, 0]), jnp.asarray(5, I32))
    # io>0 -> UNCACHED; io==0 (mini pseudo-block) -> CACHED, no I/O ever
    assert np.asarray(b_state).tolist() == [S_UNCACHED, S_CACHED,
                                            S_INACTIVE]
    assert int(b_stamp[1]) == 5


def test_finish_releases_exhausted_and_keeps_reactivated():
    sched = make_sched(B=3)
    pool = BufferPool(slots=8, block_io=sched.block_io)
    b_state = arr([S_CACHED, S_CACHED, S_CACHED])
    eidx = arr([0, 1])
    lane_valid = jnp.asarray([True, True])
    fin = sched.finish(b_state, jnp.zeros(3, I32), jnp.zeros(3, I32),
                       arr([0, 3, 1]), eidx, lane_valid,
                       jnp.asarray(3, I32), pool, jnp.asarray(7, I32))
    st = np.asarray(fin.b_state).tolist()
    # block 0 exhausted -> INACTIVE + slot released; block 1 reactivated
    # -> stays CACHED with refreshed stamp; block 2 untouched
    assert st == [S_INACTIVE, S_CACHED, S_CACHED]
    assert int(fin.used_slots) == 2
    assert int(fin.b_stamp[1]) == 7
    assert int(fin.blocks_reused) == 1


# ----------------------------------------------------------------------
# pull policies
# ----------------------------------------------------------------------

def _view(stamp, prio, used, t=10):
    return PullView(b_stamp=arr(stamp), b_prio=arr(prio),
                    b_used=arr(used), t=jnp.asarray(t, I32))


def test_policy_registry_complete():
    assert set(CACHED_POLICIES) == {"fifo", "priority", "lru", "hybrid",
                                    "hybrid_active"}
    with pytest.raises(ValueError, match="unknown cached_policy"):
        make_pull_policy("belady")


def test_fifo_pulls_oldest_stamp():
    sched = make_sched(B=3, policy="fifo", lanes=1)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED, S_CACHED]), arr([1, 1, 1]),
        _view([5, 2, 9], [0, 0, 0], [0, 0, 0]))
    assert bool(lane_valid[0]) and int(eidx[0]) == 1


def test_priority_pulls_highest_priority():
    sched = make_sched(B=3, policy="priority", lanes=1)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED, S_CACHED]), arr([1, 1, 1]),
        _view([5, 2, 9], [3, 8, 1], [0, 0, 0]))
    assert bool(lane_valid[0]) and int(eidx[0]) == 1


def test_lru_pulls_least_recently_executed_and_records_use():
    sched = make_sched(B=3, policy="lru", lanes=1)
    view = _view([0, 0, 0], [0, 0, 0], [4, 1, 7], t=9)
    eidx, lane_valid, b_used = sched.pull(
        arr([S_CACHED, S_CACHED, S_CACHED]), arr([1, 1, 1]), view)
    assert bool(lane_valid[0]) and int(eidx[0]) == 1
    assert int(b_used[1]) == 10  # t + 1, so "never pulled" (0) sorts first


def test_hybrid_pulls_priority_times_span():
    # priorities [5, 3, 4] rebased to >=1 against the ready-min (3) give
    # [3, 1, 2]; x spans [1, 8, 2] -> scores [3, 8, 4]: the cost-aware
    # policy picks the block amortizing the most span per pull
    sched = make_sched(B=3, policy="hybrid", block_io=arr([1, 8, 2]),
                       lanes=1)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED, S_CACHED]), arr([1, 1, 1]),
        _view([0, 0, 0], [5, 3, 4], [0, 0, 0]))
    assert bool(lane_valid[0]) and int(eidx[0]) == 1


def test_hybrid_negative_priority_keeps_span_preference():
    # BFS/WCC priorities are negative (-dis / -label): the rebase must
    # keep 'bigger span wins at equal priority' instead of inverting it,
    # and better priority must still beat equal-span worse priority
    sched = make_sched(B=3, policy="hybrid", block_io=arr([1, 8, 8]),
                       lanes=3)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED, S_CACHED]), arr([1, 1, 1]),
        _view([0, 0, 0], [-5, -5, -7], [0, 0, 0]))
    # rebase min is -7: scores (2+1)*1=3, (2+1)*8=24, (0+1)*8=8 —
    # span breaks the [-5, -5] tie, and the large-span -7 block outranks
    # the span-1 -5 block (span amortization outweighs a small priority
    # gap — the multiplicative trade-off this policy is for)
    assert np.asarray(lane_valid).all()
    assert np.asarray(eidx).tolist() == [1, 2, 0]


def test_hybrid_extreme_priority_stays_valid():
    # extreme negative priority must not fall below the NEG_INF validity
    # sentinel (ready scores are rebased >= 1 by construction)
    sched = make_sched(B=2, policy="hybrid", block_io=arr([64, 1]),
                       lanes=2)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED]), arr([1, 1]),
        _view([0, 0], [NEG_INF + 1, 1], [0, 0]))
    assert int(np.asarray(lane_valid).sum()) == 2  # both lanes valid
    assert int(eidx[0]) == 1  # rebased high priority ranks first


def test_hybrid_active_weighs_live_active_counts():
    # equal priorities, equal spans/fills: the active-fill variant must
    # prefer the block with the most LIVE active vertices (b_nactive is
    # filled into the view by Scheduler.pull), where static-fill hybrid
    # is blind — the ROADMAP "useful work per pull" follow-on
    sched = make_sched(B=3, policy="hybrid_active",
                       block_io=arr([4, 4, 4]), lanes=1)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED, S_CACHED]), arr([2, 9, 1]),
        _view([0, 0, 0], [5, 5, 5], [0, 0, 0]))
    assert bool(lane_valid[0]) and int(eidx[0]) == 1


def test_hybrid_active_trades_priority_against_activity():
    # the multiplicative rebase is shared with 'hybrid': rebased
    # priorities [3, 1] x active counts [2, 8] -> scores [6, 8]; a
    # large enough active count outranks a modest priority edge
    sched = make_sched(B=2, policy="hybrid_active",
                       block_io=arr([1, 1]), lanes=2)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_CACHED]), arr([2, 8]),
        _view([0, 0], [5, 3], [0, 0]))
    assert np.asarray(lane_valid).all()
    assert np.asarray(eidx).tolist() == [1, 0]


def test_split_shared_io_zero_span_and_residency():
    # Q=2, B=3. Block 0: ZERO-SPAN submission (an early-stop-evicted
    # block_io==0 pseudo-block re-preloading) by q0 — must count as a
    # physical op with 0 blocks, not vanish (the explicit sub_mask is
    # the regression: span>0 inference dropped these). Block 1: both
    # queries submit span 3 the same tick -> first submitter physical,
    # second shared. Block 2: q1 submits while q0 holds it resident ->
    # shared.
    resident = jnp.asarray([[False, False, True],
                            [False, False, False]])
    sub_mask = jnp.asarray([[True, True, False],
                            [False, True, True]])
    sub_spans = arr([[0, 3, 0], [0, 3, 2]])
    ops_p, blk_p, ops_s, blk_s = Scheduler.split_shared_io(
        resident, sub_mask, sub_spans)
    assert np.asarray(ops_p).tolist() == [2, 0]
    assert np.asarray(blk_p).tolist() == [3, 0]
    assert np.asarray(ops_s).tolist() == [0, 2]
    assert np.asarray(blk_s).tolist() == [0, 5]
    # conservation: physical + shared == every submission, per query
    assert np.asarray(ops_p + ops_s).tolist() == [2, 2]
    assert np.asarray(blk_p + blk_s).tolist() == [3, 5]


@pytest.mark.parametrize("n", [1, 1023, 1024, 1025, 5000])
def test_prefix_sum_equals_cumsum(n):
    x = jnp.asarray(np.random.default_rng(n).integers(0, 3, n), I32)
    assert np.array_equal(np.asarray(_prefix_sum(x)),
                          np.asarray(jnp.cumsum(x)))


@pytest.mark.parametrize("n,k,dtype", [(5, 3, "int32"), (128, 8, "int32"),
                                       (129, 8, "int32"),
                                       (1000, 8, "int32"),
                                       (1000, 4, "float32")])
def test_two_stage_top_k_equals_top_k(n, k, dtype):
    # few distinct values: ties everywhere, and the lower index must win
    x = np.random.default_rng(n).integers(-3, 3, n).astype(dtype)
    x[::7] = NEG_INF
    got_v, got_i = _top_k(jnp.asarray(x), k)
    want_v, want_i = jax.lax.top_k(jnp.asarray(x), k)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert np.array_equal(np.asarray(got_v), np.asarray(want_v))


def test_block_prio_equals_segment_max():
    # 300 vertices over 40 blocks, some empty, one run of 37
    rng = np.random.default_rng(0)
    v_sched = np.sort(rng.choice(40, 300, p=np.r_[0.2, [0.8 / 39] * 39]))
    sched = make_sched(B=40, v_sched=arr(v_sched), v_deg=arr([1] * 300))
    front = rng.random(300) < 0.5
    v_prio = rng.integers(-50, 50, 300).astype(np.int32)
    want = np.full(40, np.iinfo(np.int32).min, np.int32)
    for b in range(40):
        m = v_sched == b
        if m.any():
            want[b] = np.where(front[m], v_prio[m], NEG_INF).max()
    got = sched._block_prio(jnp.asarray(front), jnp.asarray(v_prio))
    assert np.array_equal(np.asarray(got), want)


def test_resident_count_matches_per_vertex_gather():
    # 7 vertices over 4 blocks (block 2 owns none), block-sorted
    v_sched = arr([0, 0, 1, 1, 1, 3, 3])
    sched = make_sched(B=4, v_sched=v_sched, v_deg=arr([1] * 7))
    b_state = arr([S_CACHED, S_UNCACHED, S_LOADING, S_LOADING])
    mask = jnp.asarray([True, False, True, True, False, True, True])
    want = jnp.sum(mask & ((b_state[v_sched] == S_CACHED)
                           | (b_state[v_sched] == S_LOADING)))
    assert int(sched.resident_count(b_state, mask)) == int(want) == 3


def test_pull_skips_blocks_without_work():
    sched = make_sched(B=3, policy="fifo", lanes=2)
    eidx, lane_valid, _ = sched.pull(
        arr([S_CACHED, S_UNCACHED, S_CACHED]), arr([1, 1, 0]),
        _view([0, 0, 0], [0, 0, 0], [0, 0, 0]))
    # only block 0 is cached AND has active vertices
    assert np.asarray(lane_valid).sum() == 1
    assert int(eidx[np.argmax(np.asarray(lane_valid))]) == 0


# ----------------------------------------------------------------------
# device models (span-proportional service time)
# ----------------------------------------------------------------------

def test_uniform_device_constant_latency():
    lat = UniformDevice(latency=3).latency_ticks(arr([1, 4, 16]),
                                                 queue_depth=8)
    assert np.asarray(lat).tolist() == [3, 3, 3]


def test_device_model_span_proportional():
    lat = DeviceModel(ticks_per_slot=2, channels=1).latency_ticks(
        arr([1, 4, 16]), queue_depth=8)
    assert np.asarray(lat).tolist() == [2, 8, 32]


def test_device_model_channels_bounded_by_queue_depth():
    # 16 device channels but queue_depth 4 -> effective parallelism 4
    lat = DeviceModel(ticks_per_slot=1, channels=16).latency_ticks(
        arr([16]), queue_depth=4)
    assert int(lat[0]) == 4
    # channels=0 derives parallelism from queue_depth
    lat0 = DeviceModel(ticks_per_slot=1).latency_ticks(arr([16]),
                                                       queue_depth=8)
    assert int(lat0[0]) == 2
    # latency never drops below one tick
    assert int(DeviceModel().latency_ticks(arr([1]), queue_depth=64)[0]) == 1


def test_device_model_from_bandwidth():
    assert DeviceModel.from_bandwidth(6.0).ticks_per_slot == 1
    assert DeviceModel.from_bandwidth(1.5).ticks_per_slot == 4
    assert DeviceModel.from_bandwidth(100.0).ticks_per_slot == 1


def test_preload_sets_span_deadlines():
    sched = make_sched(B=3, block_io=arr([2, 8, 1]),
                       device=DeviceModel(ticks_per_slot=2, channels=1))
    pool = BufferPool(slots=64, block_io=sched.block_io)
    pre = sched.preload(arr([S_UNCACHED] * 3), jnp.zeros(3, I32),
                        arr([3, 2, 1]), arr([1, 1, 1]),
                        jnp.zeros((), I32), pool, jnp.asarray(10, I32))
    # deadline = t + span * ticks_per_slot on a single channel
    assert np.asarray(pre.b_deadline).tolist() == [14, 26, 12]


def test_pool_in_bounds_invariant():
    pool = BufferPool(slots=8, block_io=arr([1]))
    assert pool.in_bounds(np.asarray([0, 4, 8]))
    assert not pool.in_bounds(np.asarray([9]))
    assert not pool.in_bounds(np.asarray([-1]))


# ----------------------------------------------------------------------
# executor backends (direct, outside the while_loop)
# ----------------------------------------------------------------------

def _line_engine(executor):
    # path graph 0-1-2-3-4: deterministic one-hop relaxations
    n = 5
    src = np.array([0, 1, 2, 3])
    dst = np.array([1, 2, 3, 4])
    g = from_edges(n, np.r_[src, dst], np.r_[dst, src])
    hg = build_hybrid(g, delta_deg=0, block_edges=8)
    return Engine(hg, EngineConfig(lanes=2, chunk_size=4,
                                   executor=executor)), hg


@pytest.mark.parametrize("executor", ["gather", "pallas"])
def test_executor_single_step_relax(executor):
    eng, hg = _line_engine(executor)
    algo = bfs_algorithm()
    src_new = int(hg.v2id[0])
    dis = np.full(eng.V, 2 ** 30, np.int32)
    dis[src_new] = 0
    front = np.zeros(eng.V, bool)
    front[src_new] = True
    eidx = jnp.asarray([int(eng.tables.v_sched[src_new])] * eng.E, I32)
    lane_valid = jnp.asarray([True] + [False] * (eng.E - 1))
    res = make_executor(executor, eng.tables).execute(
        algo, {"dis": jnp.asarray(dis)}, jnp.asarray(front), eidx,
        lane_valid)
    new_dis = np.asarray(res.state["dis"])[hg.v2id]
    assert new_dis[0] == 0 and new_dis[1] == 1  # one-hop relax
    assert bool(res.processed[src_new])
    assert int(res.vertices_processed) >= 1
    assert int(res.edges_scanned) >= 1


@pytest.mark.parametrize("executor", ["gather", "pallas"])
def test_executor_invalid_lanes_are_noop(executor):
    eng, hg = _line_engine(executor)
    algo = bfs_algorithm()
    dis = jnp.asarray(np.full(eng.V, 2 ** 30, np.int32))
    front = jnp.zeros(eng.V, bool)
    res = make_executor(executor, eng.tables).execute(
        algo, {"dis": dis}, front, jnp.zeros(eng.E, I32),
        jnp.zeros(eng.E, bool))
    assert int(res.edges_scanned) == 0
    assert int(res.vertices_processed) == 0
    assert not bool(res.processed.any())
    assert np.array_equal(np.asarray(res.state["dis"]), np.asarray(dis))


# ----------------------------------------------------------------------
# compiled programs: graph tables are arguments; max_ticks is an error
# ----------------------------------------------------------------------

def _lowered_solo_bfs(scale):
    sess = GraphSession(rmat_graph(scale, avg_degree=8, seed=1),
                        EngineConfig(bucketing=0))
    eng = sess.engine
    algo = BFS(0).build()
    front0, state0 = algo.init(sess.ctx, algo.init_data(sess.ctx))
    lowered = jax.jit(functools.partial(eng._run_impl, algo)).lower(
        eng.tables, front0, state0)
    return eng.tables.all_edges.shape[0], lowered.as_text()


def test_lowered_program_does_not_grow_with_the_graph():
    _, text10 = _lowered_solo_bfs(10)
    n_edges, text12 = _lowered_solo_bfs(12)
    # the edge array enters as an argument, never as a literal
    assert not re.search(rf"constant[^\n]*tensor<{n_edges}xi32>", text12)
    assert abs(len(text12) - len(text10)) < 0.1 * len(text10)


@pytest.mark.parametrize("plane", ["solo", "per_query", "aggregated",
                                   "service"])
def test_loop_cut_at_max_ticks_raises(plane):
    g = rmat_graph(8, avg_degree=8, seed=3)
    cfg = EngineConfig(max_ticks=3, batch_mode="aggregated"
                       if plane == "aggregated" else "per_query")
    sess = GraphSession(g, cfg, block_edges=64)
    with pytest.raises(RuntimeError, match="max_ticks"):
        if plane == "solo":
            sess.run(BFS(0))
        elif plane == "service":
            svc = ContinuousService(sess)
            svc.submit(BFS(0))
            svc.run_until_idle()
        else:
            sess.run(bfs_batch([0, 1]))
