"""What a device profile can read of the program: host spans around the
service loop's parts and the solo engine call, stage scopes in the
compiled tick's op metadata, and results that do not change while a
profile records."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import BFS, PPR
from repro.core import ContinuousService, EngineConfig, GraphSession
from repro.core import ServeConfig, prefix
from repro.storage.csr import symmetrize
from repro.storage.rmat import rmat_graph

CFG = dict(lanes=4, prefetch=4, queue_depth=8, pool_slots=24,
           chunk_size=64, bucketing=0)
SERVE = ServeConfig(initial_capacity=2, max_capacity=2, shrink=False)
PROGRAM_SPANS = ("serve.", "engine.")


@pytest.fixture(scope="module")
def graph():
    return symmetrize(rmat_graph(scale=8, avg_degree=8, a=0.57, b=0.19,
                                 c=0.19, seed=0))


def session(graph, **kw):
    return GraphSession(graph, EngineConfig(**{**CFG, **kw}),
                        block_edges=64)


def profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; its result and the program's host
    spans ``(start, end, name)`` in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = max(pathlib.Path(tmp_path).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    pd = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(PROGRAM_SPANS))
    return out, spans


def inside(child, spans, parent: str) -> bool:
    s, e, _ = child
    return any(n == parent and a <= s and e <= b for a, b, n in spans)


def serve_ppr(graph, sources, r_max=1e-4):
    svc = ContinuousService(session(graph), serve=SERVE)
    handles = [svc.submit(PPR(s, r_max=r_max)) for s in sources]
    svc.run_until_idle()
    return svc, [h.result() for h in handles]


def test_service_step_spans_nest(graph, tmp_path):
    svc, _ = serve_ppr(graph, [0])          # compiles outside the trace
    handles = [svc.submit(PPR(s, r_max=1e-4)) for s in (3, 7, 21)]
    steps = []

    def drive():
        while svc.pending:
            steps.append(len(svc.step()))

    _, spans = profiled(tmp_path, drive)
    names = [n for _, _, n in spans]
    for name in ("serve.admit", "serve.advance", "serve.retire"):
        assert names.count(name) == len(steps), name
    for child, parent in (("serve.dispatch", "serve.advance"),
                          ("serve.flags", "serve.advance"),
                          ("serve.extract", "serve.retire"),
                          ("serve.kill", "serve.retire")):
        got = [s for s in spans if s[2] == child]
        assert got and all(inside(s, spans, parent) for s in got), child
    # one extract and one kill per retired query, each step's parts in
    # the loop's order: admit, then advance, then retire
    assert names.count("serve.extract") == sum(steps) == len(handles)
    assert names.count("serve.kill") == len(handles)
    assert names.count("serve.dispatch") == len(steps)
    top = [n for s in spans if (n := s[2]) in
           ("serve.admit", "serve.advance", "serve.retire")]
    assert top == ["serve.admit", "serve.advance",
                   "serve.retire"] * len(steps)


def test_resize_span_inside_admission(graph, tmp_path):
    svc = ContinuousService(session(graph), serve=ServeConfig(
        initial_capacity=1, max_capacity=2, shrink=False))
    svc.submit(PPR(0, r_max=1e-4))
    svc.submit(PPR(3, r_max=1e-4))
    _, spans = profiled(tmp_path, svc.step)
    resized = [s for s in spans if s[2] == "serve.resize"]
    assert len(resized) == 2        # the first rung, then the doubling
    assert all(inside(s, spans, "serve.admit") for s in resized)


def test_engine_run_span(graph, tmp_path):
    sess = session(graph)
    sess.run(BFS(0))
    _, spans = profiled(tmp_path, lambda: sess.run(BFS(0)))
    assert [n for _, _, n in spans] == ["engine.run"]


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _stages(op_names) -> set:
    return {part for name in op_names for part in name.split("/")} & {
        "rows", "schedule", "execute", "refresh"}


def test_solo_run_carries_stage_scopes(graph):
    sess = session(graph)
    eng, algo = sess.engine, BFS(0).build()
    front0, state0 = algo.init(sess.ctx, algo.init_data(sess.ctx))
    run = jax.jit(lambda t, f, s: eng._run_impl(algo, t, f, s))
    names = _op_names(run.lower(eng.tables, front0, state0).compile())
    assert _stages(names) == {"schedule", "execute", "refresh"}
    # the loop's liveness test is the schedule's
    assert any("while/cond" in n and "/schedule/" in n for n in names)


@pytest.mark.parametrize("mode,want", [
    ("per_query", {"rows", "schedule", "execute", "refresh"}),
    ("aggregated", {"schedule", "execute", "refresh"}),
])
def test_service_step_carries_stage_scopes(graph, mode, want):
    sess = session(graph, batch_mode="aggregated", pool_mode="shared")
    eng, algo = sess.engine, BFS(0).build()
    fns = eng.service_fns(algo, 2, mode)
    carry = fns["carry0"]()
    names = _op_names(fns["step"].func.lower(eng.tables, carry).compile())
    assert _stages(names) == want
    if mode == "per_query":
        # the tick's own stages sit inside the map over rows
        assert any(re.search(r"/rows/.*/execute/", n) for n in names)


def test_results_identical_with_the_profiler_on(graph, tmp_path):
    sources = (0, 3, 7, 21)
    _, off = serve_ppr(graph, sources)
    (_, on), _ = profiled(tmp_path / "ppr",
                          lambda: serve_ppr(graph, sources))
    for a, b in zip(off, on):
        assert np.array_equal(a.result, b.result)
        assert a.metrics == b.metrics
    sess = session(graph)
    bfs_off = sess.run(BFS(5))
    bfs_on, spans = profiled(tmp_path / "bfs", lambda: sess.run(BFS(5)))
    assert spans
    assert np.array_equal(bfs_off.result, bfs_on.result)
    assert bfs_off.metrics == bfs_on.metrics


@pytest.mark.parametrize("shape,axis,dtype", [
    ((1000,), -1, np.int32), ((7, 1024), 1, np.int32),
    ((5, 33), 0, np.float32), ((0,), 0, np.int32)])
def test_scoped_cumsum_equals_jnp_cumsum(shape, axis, dtype):
    x = np.random.default_rng(0).integers(-50, 50, size=shape).astype(dtype)
    got = prefix.cumsum(jnp.asarray(x), axis)
    want = jnp.cumsum(jnp.asarray(x), axis=axis)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_scoped_cumsum_keeps_the_callers_scope():
    def f(x):
        with jax.named_scope("refresh"):
            return prefix.cumsum(x), jnp.cumsum(x)

    names = _op_names(jax.jit(f).lower(jnp.ones(256, jnp.int32)).compile())
    sums = {n for n in names if n.endswith("reduce_window_sum")}
    # ours keeps the scope; jnp.cumsum's out-of-line lowering drops it
    assert any("/refresh/" in n for n in sums)
    assert "reduce_window_sum" in sums
