"""Continuous-service acceptance: mid-flight admission, retirement
compaction, heterogeneous co-execution, and the latency-SLO surface.

The contracts under test:

  * **Admission identity** — a query admitted into a RUNNING batch at
    tick t produces results bit-identical to a solo ``session.run``
    (per-query plane; its row IS the solo carry) or fixed-point-equal
    (aggregated plane), for any t;
  * **Conservation at every Q transition** — each retired query's
    physical + shared I/O equals its solo run's logical I/O, no matter
    how many admissions / retirements / capacity resizes happened while
    it was resident;
  * **Q=1 degenerate case** — a service with capacity 1 reproduces
    ``GraphSession.run`` exactly, metrics included;
  * **Never drains** — with work pending the loop advances every tick
    (``idle_barrier_ticks == 0``);
  * **Compile once per capacity** — steady-state admissions and
    retirements at a fixed capacity add no compile-cache entries.
"""
import dataclasses
import functools
import re

import numpy as np
import pytest

from repro.algorithms import BFS, MIS, PPR, PageRank, WCC
from repro.core import (ContinuousService, EngineConfig, GraphService,
                        GraphSession, QueryBatch, QueryState, ServeConfig)
from repro.core.engine import TRACE_LEN, Metrics
from repro.storage.csr import symmetrize
from repro.storage.rmat import rmat_graph

CFG = dict(lanes=4, prefetch=4, queue_depth=8, pool_slots=24,
           chunk_size=64, bucketing=0)
AGG = dict(batch_mode="aggregated", pool_mode="shared")
SOURCES = (0, 3, 7, 21, 50, 101)


@functools.lru_cache(maxsize=None)
def _graph(symmetric: bool = False):
    g = rmat_graph(scale=8, avg_degree=8, a=0.65, b=0.15, c=0.15, seed=0)
    return symmetrize(g) if symmetric else g


def make_session(g, **kw) -> GraphSession:
    return GraphSession(g, EngineConfig(**{**CFG, **kw}), block_edges=64)


@functools.lru_cache(maxsize=None)
def _solo(source: int):
    return make_session(_graph()).run(BFS(source))


def _service(serve=None, **kw) -> ContinuousService:
    return ContinuousService(make_session(_graph(), **kw), serve=serve)


# ----------------------------------------------------------------------
# Q=1: the degenerate service is session.run
# ----------------------------------------------------------------------

def test_q1_service_identical_to_session_run():
    svc = _service(ServeConfig(initial_capacity=1, max_capacity=1))
    h = svc.submit(BFS(0))
    assert h.state == QueryState.PENDING and not h.done
    svc.run_until_idle()
    solo = _solo(0)
    assert h.state == QueryState.DONE
    assert np.array_equal(h.result().result, solo.result)
    for k in solo.state:
        assert np.array_equal(h.result().state[k], solo.state[k]), k
    # counters too: one row, nothing shared, same tick schedule
    assert h.result().metrics == solo.metrics
    # execution latency == the solo tick count (admitted at tick 0)
    assert h.retire_tick - h.admit_tick == solo.metrics.ticks


# ----------------------------------------------------------------------
# mid-flight admission: bit-identity regardless of admission tick
# ----------------------------------------------------------------------

def test_midflight_admission_bit_identical_per_query():
    svc = _service(ServeConfig(initial_capacity=2, max_capacity=8))
    staggered = {0: SOURCES[:2], 5: SOURCES[2:4], 9: SOURCES[4:]}
    handles = {}
    for tick in range(12):
        for s in staggered.get(tick, ()):
            handles[s] = svc.submit(BFS(s))
        svc.step()
    svc.run_until_idle()
    for s, h in handles.items():
        solo = _solo(s)
        assert np.array_equal(h.result().result, solo.result), s
        m = h.result().metrics
        # the row ran the solo tick body on the solo carry: same
        # schedule length and work, I/O split into physical + shared
        assert m.ticks == solo.metrics.ticks, s
        assert m.edges_scanned == solo.metrics.edges_scanned, s
        assert m.io_ops + m.io_ops_shared == solo.metrics.io_ops, s
        assert m.io_blocks + m.io_blocks_shared \
            == solo.metrics.io_blocks, s
    st = svc.stats()
    assert st["midflight_admissions"] == 4     # the tick-5 and tick-9 cohorts
    assert st["idle_barrier_ticks"] == 0
    assert handles[SOURCES[2]].admit_tick > handles[SOURCES[0]].admit_tick


def test_midflight_admission_aggregated_fixed_point():
    svc = ContinuousService(
        make_session(_graph(), **AGG),
        serve=ServeConfig(initial_capacity=2, max_capacity=8))
    h0 = svc.submit(BFS(SOURCES[0]))
    h1 = svc.submit(BFS(SOURCES[1]))
    for _ in range(4):
        svc.step()
    h2 = svc.submit(BFS(SOURCES[2]))   # joins the merged schedule live
    svc.run_until_idle()
    for s, h in zip(SOURCES, (h0, h1, h2)):
        assert np.array_equal(h.result().result, _solo(s).result), s
    st = svc.stats()
    assert st["midflight_admissions"] == 1
    assert st["idle_barrier_ticks"] == 0


# ----------------------------------------------------------------------
# retirement compaction: conservation at every Q transition
# ----------------------------------------------------------------------

def test_conservation_at_every_q_transition():
    """Queries of very different lengths share a group, so rows retire
    one by one while others keep running — every retirement (a Q
    transition, possibly with a capacity shrink) must hand back a
    metrics row satisfying physical + shared == solo logical."""
    svc = _service(ServeConfig(initial_capacity=2, max_capacity=8))
    handles = {s: svc.submit(BFS(s)) for s in SOURCES}
    seen = []
    for _ in range(10_000):
        retired = svc.step()
        for h in retired:
            s = h.query.source
            m, ms = h.result().metrics, _solo(s).metrics
            assert m.io_ops + m.io_ops_shared == ms.io_ops, s
            assert m.io_blocks + m.io_blocks_shared == ms.io_blocks, s
            assert m.ticks == ms.ticks, s
            assert np.array_equal(h.result().result, _solo(s).result)
            seen.append(s)
        if not svc.pending:
            break
    assert sorted(seen) == sorted(SOURCES)
    # the ladder actually moved: grow to hold 6 rows, shrink at the tail
    assert svc.stats()["resizes"] >= 2
    assert svc.stats()["peak_capacity"] >= 8 or \
        svc.stats()["peak_capacity"] >= len(SOURCES)


# ----------------------------------------------------------------------
# compile once per capacity
# ----------------------------------------------------------------------

def test_steady_state_admissions_never_recompile(monkeypatch):
    svc = _service(ServeConfig(initial_capacity=2, max_capacity=2))
    svc.submit(BFS(SOURCES[0]))
    svc.submit(BFS(SOURCES[1]))
    svc.run_until_idle()
    compiled = svc.session.num_compiled
    slots = _check_retirements(monkeypatch, svc)
    # a second wave at the same capacity — admission, stepping and
    # retirement reuse every compiled fn
    for s in SOURCES[2:]:
        svc.submit(BFS(s))
    svc.run_until_idle()
    assert svc.session.num_compiled == compiled
    assert svc.stats()["completed"] == len(SOURCES)
    # rows retired at every slot, and the row slice compiled once: the
    # slot is traced, not a constant of the program
    assert set(slots) == {0, 1}
    (g,) = svc._groups.values()
    assert g.fns["extract"].func._cache_size() == 1
    # the source is traced too: six sources, one admit program; and the
    # row's reset is its own program, compiled once
    assert g.fns["admit"].func._cache_size() == 1
    assert g.fns["retire"].func._cache_size() == 1


# ----------------------------------------------------------------------
# admission sends the init data alone; a reset sends nothing
# ----------------------------------------------------------------------

@pytest.mark.parametrize("plane", ["per_query", "aggregated"])
def test_admission_uploads_no_vertex_array(monkeypatch, plane):
    """``admit_host_bytes`` counts what admissions and resets copy to the
    device: one int32 source per single-source admission, nothing for a
    whole-graph one, nothing per reset. No program holds a V-long
    constant in place of the upload."""
    kw = dict(AGG) if plane == "aggregated" else {}
    svc = ContinuousService(
        GraphSession(_graph(True), EngineConfig(**CFG, **kw),
                     block_edges=64),
        serve=ServeConfig(initial_capacity=2, max_capacity=2))
    if plane == "per_query":
        queries = [PPR(source=s, alpha=0.15, r_max=1e-3) for s in SOURCES]
        queries.append(PageRank(alpha=0.15, r_max=1e-3))
    else:
        queries = [BFS(s) for s in SOURCES]
    resets = []
    real = svc._kill_row

    def kill(g, q):
        before = svc.admit_host_bytes
        real(g, q)
        resets.append(svc.admit_host_bytes - before)
    monkeypatch.setattr(svc, "_kill_row", kill)
    for qy in queries:
        svc.submit(qy)
    svc.run_until_idle()
    st = svc.stats()
    assert st["completed"] == len(queries) == len(resets)
    assert resets == [0] * len(queries)
    assert st["admit_host_bytes"] == len(SOURCES) * np.int32(0).nbytes
    eng = svc.session.engine
    for g in svc._groups.values():
        for name, args in (("admit", (g.carry, 0, g.algo.init_data(
                svc.session.ctx))), ("retire", (g.carry, 0)),
                           ("carry0", ())):
            text = g.fns[name].func.lower(eng.tables, *args).as_text()
            assert not re.search(rf"constant[^\n]*tensor<{eng.V}x", text), \
                name


# ----------------------------------------------------------------------
# retirement copies one row: the device slice equals the full-leaf read
# ----------------------------------------------------------------------

def _full_leaf_row(g, q, trace: bool) -> tuple:
    """Row ``q`` read as retirement read it before the device slice:
    each whole ``[Q, ...]`` leaf copied to the host, then indexed."""
    c = g.carry
    state = {k: np.asarray(v)[q] for k, v in c["state"].items()}
    metrics = Metrics(**{k: (int(np.asarray(hi)[q]) << 32)
                         | int(np.asarray(lo)[q])
                         for k, (hi, lo) in c["counters"].items()})
    rows = None
    if trace and g.mode == "per_query":
        n = min(metrics.ticks, TRACE_LEN)
        rows = {k: np.asarray(v)[q][:n] for k, v in c["trace"].items()}
    return state, metrics, rows


def _check_retirements(monkeypatch, svc) -> list:
    """Wrap ``svc._extract_row`` so that each retired row must equal,
    bit for bit, the row the full-leaf reads give; returns the retired
    slots in order."""
    slots = []
    real = svc._extract_row

    def checked(g, q, handle):
        state, metrics, rows = _full_leaf_row(g, q, svc.session.cfg.trace)
        result = real(g, q, handle)
        for k in state:
            assert np.array_equal(result.state[k], state[k]), k
        assert result.metrics == metrics
        if rows is None:
            assert result.trace is None
        else:
            assert result.trace.keys() == rows.keys()
            for k in rows:
                assert np.array_equal(result.trace[k], rows[k]), k
        slots.append(q)
        return result
    monkeypatch.setattr(svc, "_extract_row", checked)
    return slots


def _solo_io(m: Metrics) -> Metrics:
    """``m`` with its shared I/O folded back into the physical counts:
    what a solo run of the row's query counts."""
    return dataclasses.replace(
        m, io_ops=m.io_ops + m.io_ops_shared, io_ops_shared=0,
        io_blocks=m.io_blocks + m.io_blocks_shared, io_blocks_shared=0)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("plane", ["per_query", "aggregated"])
def test_retired_row_equals_full_leaf_read(monkeypatch, plane, trace):
    """A row sliced on the device and copied alone is the row the old
    full-leaf reads gave, bit for bit: state, every counter and the
    row's trace. On the per-query plane it is also the solo run."""
    kw = dict(AGG) if plane == "aggregated" else {}
    g = _graph(True)
    svc = ContinuousService(
        GraphSession(g, EngineConfig(**CFG, **kw, trace=trace),
                     block_edges=64),
        serve=ServeConfig(initial_capacity=4, max_capacity=4))
    queries = [BFS(s) for s in SOURCES[:4]]
    if plane == "per_query":
        queries += [PPR(source=s, alpha=0.15, r_max=1e-3)
                    for s in SOURCES[:3]]
    slots = _check_retirements(monkeypatch, svc)
    handles = [svc.submit(qy) for qy in queries]
    svc.run_until_idle()
    assert all(grp.mode == plane for grp in svc._groups.values())
    assert len(slots) == len(queries) and max(slots) > 0
    if plane == "aggregated":
        return
    sess = GraphSession(g, EngineConfig(**CFG, trace=trace), block_edges=64)
    for qy, h in zip(queries, handles):
        solo, got = sess.run(qy), h.result()
        assert np.array_equal(got.result, solo.result), qy
        for k in solo.state:
            assert np.array_equal(got.state[k], solo.state[k]), (qy, k)
        assert _solo_io(got.metrics) == solo.metrics, qy
        if trace:
            assert got.trace.keys() == solo.trace.keys()
            for k in solo.trace:
                assert np.array_equal(got.trace[k], solo.trace[k]), (qy, k)
        else:
            assert got.trace is None and solo.trace is None


def test_retirement_copies_one_row_to_the_host():
    """``retire_host_bytes`` counts one row's state and counters per
    retired query — not the ``[Q, ...]`` leaves it came from."""
    svc = ContinuousService(
        GraphSession(_graph(True), EngineConfig(**CFG), block_edges=64),
        serve=ServeConfig(initial_capacity=4, max_capacity=4))
    for s in SOURCES:
        svc.submit(PPR(source=s, alpha=0.15, r_max=1e-3))
    svc.run_until_idle()
    (g,) = svc._groups.values()
    state_bytes = sum(v[0].nbytes for v in g.carry["state"].values())
    assert state_bytes == 2 * 4 * svc.session.engine.V   # p, r: f32[V]
    counter_bytes = len(dataclasses.fields(Metrics)) * 2 * 4  # u32 limbs
    st = svc.stats()
    assert st["completed"] == len(SOURCES)
    assert st["retire_host_bytes"] == \
        st["completed"] * (state_bytes + counter_bytes)


# ----------------------------------------------------------------------
# heterogeneous co-execution
# ----------------------------------------------------------------------

def test_heterogeneous_groups_coexecute():
    """Different algorithms share the host loop tick-for-tick: their
    [admit, retire) intervals overlap instead of serializing."""
    g = _graph(True)
    sess = GraphSession(g, EngineConfig(**CFG), block_edges=64)
    solo_bfs = sess.run(BFS(0))
    solo_wcc = sess.run(WCC())
    solo_ppr = sess.run(PPR(source=0, alpha=0.15, r_max=1e-3))
    svc = ContinuousService(
        GraphSession(g, EngineConfig(**CFG), block_edges=64),
        serve=ServeConfig(initial_capacity=2, max_capacity=4))
    hb = svc.submit(BFS(0))
    hw = svc.submit(WCC())
    hp = svc.submit(PPR(source=0, alpha=0.15, r_max=1e-3))
    svc.run_until_idle()
    assert np.array_equal(hb.result().result, solo_bfs.result)
    assert np.array_equal(hw.result().result, solo_wcc.result)
    assert np.array_equal(hp.result().result, solo_ppr.result)
    assert svc.stats()["groups"] == 3
    first_retire = min(h.retire_tick for h in (hb, hw, hp))
    last_admit = max(h.admit_tick for h in (hb, hw, hp))
    assert first_retire > last_admit, "groups serialized"
    assert svc.stats()["idle_barrier_ticks"] == 0


def test_group_ration_still_progresses():
    """max_groups_per_tick=1 serializes engine ticks across groups but
    the rotation keeps every group moving — same results, no barrier."""
    svc = _service(ServeConfig(initial_capacity=1, max_capacity=2,
                               max_groups_per_tick=1))
    hb = svc.submit(BFS(0))
    hp = svc.submit(PPR(source=0, alpha=0.15, r_max=1e-3))
    svc.run_until_idle(max_ticks=100_000)
    assert np.array_equal(hb.result().result, _solo(0).result)
    st = svc.stats()
    assert st["throttled_group_ticks"] > 0      # the ration did bite
    assert st["idle_barrier_ticks"] == 0        # ... without idling
    assert st["completed"] == 2


# ----------------------------------------------------------------------
# capacity SLO: bounded batches queue instead of growing
# ----------------------------------------------------------------------

def test_capacity_bound_queues_admissions():
    svc = _service(ServeConfig(initial_capacity=1, max_capacity=2))
    handles = [svc.submit(BFS(s)) for s in SOURCES[:4]]
    svc.step()
    st = svc.stats()
    assert st["queued"] == 2 and st["running"] == 2
    assert handles[2].state == QueryState.PENDING
    svc.run_until_idle()
    for s, h in zip(SOURCES, handles):
        assert np.array_equal(h.result().result, _solo(s).result), s
    assert svc.stats()["peak_capacity"] <= 2
    # the queued queries paid visible queue wait
    assert handles[3].latency_ticks > handles[0].latency_ticks


# ----------------------------------------------------------------------
# drain migration shim + lifecycle + rejections
# ----------------------------------------------------------------------

def test_drain_shim_matches_graphservice():
    g = _graph()
    drain_svc = GraphService(make_session(g))
    cont_svc = ContinuousService(make_session(g))
    for s in SOURCES[:3]:
        drain_svc.submit(BFS(s))
        cont_svc.submit(BFS(s))
    old = drain_svc.drain()
    new = cont_svc.drain()
    assert len(old) == len(new)
    for a, b in zip(old, new):
        assert np.array_equal(a.result, b.result)
    assert cont_svc.pending == 0


def test_lifecycle_and_rejections():
    svc = _service()
    with pytest.raises(ValueError, match="member queries individually"):
        svc.submit(QueryBatch((BFS(0), BFS(3))))
    with pytest.raises(ValueError, match="cannot join the continuous"):
        svc.submit(MIS())
    h = svc.submit(BFS(0))
    with pytest.raises(RuntimeError, match="not finished"):
        h.result()
    svc.run_until_idle()
    assert h.done and h.state == QueryState.DONE
    assert h.submit_tick == 0 and h.retire_tick == h.latency_ticks


def test_serve_config_validation():
    with pytest.raises(ValueError, match="exceeds"):
        ServeConfig(initial_capacity=8, max_capacity=4)
    with pytest.raises(ValueError, match=">= 1"):
        ServeConfig(initial_capacity=0)
