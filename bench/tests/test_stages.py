"""The reduction of program spans and op stages: idle put down to the
innermost span, device time to the innermost op's stage, the readers'
sums against ``readers.py``'s, a plain recorded step read as before, and
the names and paths a CPU profile of the program gives."""
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import readers
import stages
import tracing
import xspace

DATA = pathlib.Path(__file__).parent / "data"


def service_step_trace():
    """Two service steps, each split as the program's spans split it,
    and ops of three stages and one unscoped admission."""
    spans = [
        (0, 100, "step"),
        (2, 20, "serve.admit"), (8, 14, "serve.resize"),
        (20, 60, "serve.advance"), (20, 26, "serve.dispatch"),
        (26, 60, "serve.flags"),
        (60, 96, "serve.retire"), (60, 90, "serve.extract"),
        (90, 96, "serve.kill"),
        (100, 104, "retire"),
        (110, 160, "step"),
        (110, 115, "serve.admit"), (115, 150, "serve.advance"),
        (115, 120, "serve.dispatch"), (120, 150, "serve.flags"),
        (150, 160, "serve.retire"),
    ]
    ops = [(10, 13, "jit_admit:copy"),                # inside serve.resize
           (22, 58, "jit_step:while"),                # the row loop
           (23, 30, "jit_step:fusion.1"), (30, 40, "jit_step:fusion.2"),
           (41, 50, "jit_step:fusion.3"), (50, 57, "jit_step:dus"),
           (92, 94, "jit_admit:copy"),                # the row's reset
           (118, 140, "jit_step:fusion.1")]
    paths = ["", "jit(step)/rows/while",
             "jit(step)/rows/while/body/schedule/x",
             "jit(step)/rows/while/body/execute/y",
             "jit(step)/rows/while/body/refresh/z",
             "jit(step)/rows/dynamic_update_slice", "",
             "jit(step)/rows/while/body/schedule/x"]
    return stages.ScopedTrace(ops, spans, paths)


def run_of(trace, ticks=()):
    answered = [types.SimpleNamespace(info={"ticks": t}) for t in ticks]
    return types.SimpleNamespace(
        trace=trace, window=types.SimpleNamespace(answered=answered))


def test_stage_of_takes_the_innermost_scope():
    assert stages.stage_of("jit(step)/rows/while/body/execute/gather") \
        == "execute"
    assert stages.stage_of("jit(step)/rows/dynamic_update_slice") == "rows"
    assert stages.stage_of("reduce_window_sum") == stages.UNSCOPED
    assert stages.stage_of("jit(f)/jit(executed)/add") == stages.UNSCOPED
    assert stages.stage_of("") == stages.UNSCOPED


def test_innermost_pieces_cover_the_union():
    pieces = stages.innermost([(0, 10, "a"), (2, 4, "b"), (3, 5, "c"),
                               (12, 14, "d")])
    assert pieces == [(0, 2, "a"), (2, 3, "b"), (3, 5, "c"), (5, 10, "a"),
                      (12, 14, "d")]
    assert stages.innermost([]) == []


def test_idle_goes_to_the_innermost_span():
    tr = service_step_trace()
    gaps = dict(tr.idle_gaps(k=100))
    ns = 1e-9
    assert gaps["serve.resize"] == pytest.approx(3 * ns)     # 8..14 less 3
    assert gaps["serve.admit"] == pytest.approx((12 + 5) * ns)
    assert gaps["serve.dispatch"] == pytest.approx((2 + 3) * ns)
    assert gaps["serve.flags"] == pytest.approx((2 + 10) * ns)
    assert "serve.advance" not in gaps     # its children fill it
    assert gaps["serve.extract"] == pytest.approx(30 * ns)
    assert gaps["serve.kill"] == pytest.approx(4 * ns)
    assert gaps["serve.retire"] == pytest.approx(10 * ns)
    assert gaps["step"] == pytest.approx((2 + 4) * ns)  # 0..2, 96..100
    assert gaps["retire"] == pytest.approx(4 * ns)
    # every idle instant of the window goes to one name
    assert sum(gaps.values()) == pytest.approx(tr.window_s() - tr.busy_s())
    # the harness-span reading of the same trace: one lump in ``step``
    plain = dict(tracing.Trace(tr.ops, tr.spans).idle_gaps(k=100))
    assert plain["step"] > gaps["step"]


def test_gap_and_stage_readings_add_up():
    run = run_of(service_step_trace())
    parts = [stages.gap_ms_per_step(run, n) for n in stages.SERVE_PARTS]
    host_gap = readers.step_host_gap_ms(run)
    # idle 0..2 and 96..100 lie in the first step outside the
    # program's spans; 100..110 lies outside both steps
    outside = (2 + 4) / 2 * 1e-6
    assert sum(parts) + outside == pytest.approx(host_gap)
    st = {s: stages.stage_ms_per_step(run, s) for s in stages.STAGES}
    uns = stages.stage_ms_per_step(run, stages.UNSCOPED)
    assert sum(st.values()) + uns == pytest.approx(readers.step_busy_ms(run))
    # the loop's own time is ``rows``; the admission copies are unscoped
    assert st == pytest.approx({"rows": (3 + 7) / 2e6,
                                "schedule": (7 + 22) / 2e6,
                                "execute": 10 / 2e6, "refresh": 9 / 2e6})
    assert uns == pytest.approx((3 + 2) / 2e6)
    assert stages.stage_ms_per_step(run, "unscoped:jit_admit") == \
        pytest.approx(uns)
    assert service_step_trace().unscoped_programs() == ["unscoped:jit_admit"]


def test_tick_readings_add_up_to_tick_busy():
    ops = [(1, 5, "p:a"), (5, 9, "p:b"), (12, 20, "p:c"), (20, 21, "p:d")]
    paths = ["x/schedule/a", "x/execute/b", "x/refresh/c", "x/while"]
    spans = [(0, 10, "bfs_call"), (0, 10, "engine.run"),
             (11, 22, "bfs_call"), (11, 22, "engine.run")]
    run = run_of(stages.ScopedTrace(ops, spans, paths), ticks=(3, 5))
    per = {s: stages.stage_ms_per_tick(run, s)
           for s in stages.STAGES + (stages.UNSCOPED,)}
    assert sum(per.values()) == pytest.approx(readers.tick_busy_ms(run))
    assert per["refresh"] == pytest.approx(8 / 8e6)


def test_readers_read_nothing_from_a_plain_trace():
    plain = tracing.Trace([(0, 1, "a")], [(0, 2, "step")])
    run = run_of(plain, ticks=(1,))
    assert stages.stage_ms_per_step(run, "execute") is None
    assert stages.stage_ms_per_tick(run, "execute") is None
    assert stages.gap_ms_per_step(run, "serve.admit") is None
    assert stages.gap_ms_per_step(run_of(None), "serve.admit") is None


def test_recorded_step_reads_as_before():
    """``trace_step.json`` (no program spans, no paths) reads what
    ``test_tracing.py`` pins for a plain ``Trace``."""
    d = json.loads((DATA / "trace_step.json").read_text())
    ops = [tuple(o) for o in d["ops"]]
    spans = [tuple(s) for s in d["spans"]]
    plain, scoped = tracing.Trace(ops, spans), stages.ScopedTrace(ops, spans)
    assert scoped.window_s() == plain.window_s() == pytest.approx(0.01520442)
    assert scoped.busy_s() == plain.busy_s() == pytest.approx(0.00554687)
    for name in ("step", "retire"):
        for a, b in zip(scoped.span_times(name), plain.span_times(name)):
            assert list(a) == list(b)
    assert scoped.top_ops(10 ** 6) == plain.top_ops(10 ** 6)
    got, want = scoped.idle_gaps(), plain.idle_gaps()
    assert [n for n, _ in got] == [n for n, _ in want]
    assert [v for _, v in got] == pytest.approx([v for _, v in want])
    run = run_of(scoped)
    assert readers.step_busy_ms(run) == pytest.approx(5.54687)
    assert readers.step_host_gap_ms(run) == pytest.approx(9.64411)
    # without paths every op is unscoped, and the stages still add up
    assert stages.stage_ms_per_step(run, stages.UNSCOPED) == \
        pytest.approx(readers.step_busy_ms(run))


def test_program_spans_pass_the_filter(tmp_path):
    """Every span a profiled service step records is kept: a program
    span renamed out of ``serve.*`` fails here, not in a chip run."""
    from repro.algorithms import PPR
    from repro.core import (ContinuousService, EngineConfig, GraphSession,
                            ServeConfig)
    from repro.storage.rmat import rmat_graph

    g = rmat_graph(scale=7, avg_degree=8, a=0.57, b=0.19, c=0.19, seed=0)
    sess = GraphSession(g, EngineConfig(lanes=4, pool_slots=24,
                                        bucketing=0), block_edges=64)
    svc = ContinuousService(sess, serve=ServeConfig(initial_capacity=1,
                                                    max_capacity=2))
    svc.submit(PPR(0, r_max=1e-3))
    svc.run_until_idle()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("step"):
        svc.submit(PPR(1, r_max=1e-3))
        svc.submit(PPR(2, r_max=1e-3))
        svc.run_until_idle()
    jax.profiler.stop_trace()
    path = next(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    kept = {n for _, _, n in stages.host_spans(pd)}
    assert kept == {"step", "serve.admit", "serve.resize", "serve.advance",
                    "serve.dispatch", "serve.flags", "serve.retire",
                    "serve.extract", "serve.kill"}


def test_trace_file_gives_each_op_its_path(tmp_path):
    def f(x):
        with jax.named_scope("execute"):
            y = jnp.sin(x) * 2
        with jax.named_scope("refresh"):
            return jnp.cumsum(y) + 1

    g = jax.jit(f)
    x = jnp.arange(512.0)
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    data = next(pathlib.Path(tmp_path).rglob("*.xplane.pb")).read_bytes()
    programs = xspace.op_paths(data)
    (name, paths), = [(n, p) for n, p in programs.items()
                      if n.startswith("jit_f(")]
    assert {stages.stage_of(p[0]) for p in paths.values()} >= {
        "execute", "refresh"}
    # a fusion carries the paths of the instructions it fuses
    fused = [p for p in paths.values() if len(p) > 1]
    assert fused and all(all(p[1:]) for p in fused)
    assert any("/execute/sin" in q for p in fused for q in p[1:])
    # an op is found by the program the device says it ran in
    op = next(k for k, p in paths.items() if "/execute/" in p[0])
    got = stages._paths([(5, 6, op), (1, 2, op)],
                        [(0, "jit_other(9)"), (4, name)], programs)
    assert got == [paths[op][0], ""]
    # where the device names the program differently, a unique prefix
    assert stages._paths([(5, 6, op)], [(4, "jit_f(0)")], programs) == \
        [paths[op][0]]
    assert xspace.op_paths(b"") == {}


def test_pick_path_falls_back_to_what_a_fusion_fuses():
    own = "jit(step)/rows/while/body/execute/gather"
    assert stages.pick_path((own, "x/refresh/a")) == own
    # named after an instruction outside any scope, or after none
    assert stages.pick_path(("gather", "x/refresh/a", "x/execute/b",
                             "x/refresh/c")) == "x/refresh/a"
    assert stages.pick_path(("", "reduce_window_sum", "y/schedule/z")) \
        == "y/schedule/z"
    assert stages.pick_path(("", "reduce_window_sum")) == ""
    assert stages.pick_path(("cond/branch_0_fun/max",)) == \
        "cond/branch_0_fun/max"
    assert stages.pick_path(()) == ""


def test_metrics_name_each_cells_readings():
    bench = json.loads((DATA.parents[2] / "BENCHMARK.json").read_text())
    assert set(stages.METRICS) <= {w["name"] for w in bench["workloads"]}
    names = {c: sorted(m) for c, m in stages.METRICS.items()}
    assert names == {
        "bfs-g500-s18": ["execute_ms.bfs", "refresh_ms.bfs",
                         "schedule_ms.bfs"],
        "ppr-g500-s18-closed16": [
            "admit_gap_ms.closed", "advance_gap_ms.closed",
            "execute_ms.closed", "refresh_ms.closed", "retire_gap_ms.closed",
            "rows_ms.closed", "schedule_ms.closed"],
        "ppr-g500-s18-closed16-short": [
            "admit_gap_ms.short", "advance_gap_ms.short",
            "retire_gap_ms.short"]}
    run = run_of(service_step_trace())
    got = {n: read(run) for n, read in
           stages.METRICS["ppr-g500-s18-closed16"].items()}
    assert got["rows_ms.closed"] == pytest.approx((3 + 7) / 2e6)
    assert got["retire_gap_ms.closed"] == pytest.approx((30 + 4 + 10) / 2e6)


def test_breakdown_of_a_service_run():
    run = run_of(service_step_trace())
    out = stages.breakdown(run, "ppr-g500-s18-closed16-short")
    assert out["per"] == "step"
    assert set(out["metrics"]) == set(
        stages.METRICS["ppr-g500-s18-closed16-short"])
    assert out["device_ms"]["unscoped:jit_admit"] == pytest.approx(5 / 2e6)
    assert sum(out["device_ms"][k] for k in stages.STAGES + (
        stages.UNSCOPED,)) == pytest.approx(readers.step_busy_ms(run))
    # a part's idle holds its children's
    gap = out["gap_ms"]
    assert gap["serve.advance"] == pytest.approx(
        gap["serve.dispatch"] + gap["serve.flags"])
    assert gap["serve.resize"] == pytest.approx(3 / 2e6)
    top = out["top_ops"]
    assert top["unscoped"] == [["jit_admit:copy", pytest.approx(5e-9)]]
    # the row loop holds other ops, so it is no leaf, as in top_ops
    assert top["rows"] == [["jit_step:dus", pytest.approx(7e-9)]]
    assert out["ops_with_path"] == pytest.approx(6 / 8)


def test_breakdown_of_a_solo_run():
    ops = [(1, 5, "p:a"), (5, 9, "p:b")]
    spans = [(0, 10, "bfs_call"), (0, 10, "engine.run")]
    run = run_of(stages.ScopedTrace(ops, spans, ["x/schedule/a", ""]),
                 ticks=(2,))
    out = stages.breakdown(run, "bfs-g500-s18")
    assert out["per"] == "engine tick" and out["gap_ms"] == {}
    assert out["metrics"] == {"schedule_ms.bfs": pytest.approx(2e-6),
                              "execute_ms.bfs": 0.0, "refresh_ms.bfs": 0.0}
    assert out["device_ms"]["unscoped:p"] == pytest.approx(2e-6)


def test_recorded_chip_step_splits():
    """``trace_step_scoped.json``, one step of the short PPR cell
    recorded on the chip with the program's spans and op paths: its idle
    splits by service span and its device time by stage, and both add
    up to what ``readers.py`` reads of the same step."""
    tr = stages.read_step(DATA / "trace_step_scoped.json")
    run = run_of(tr)
    busy, gap = readers.step_busy_ms(run), readers.step_host_gap_ms(run)
    assert (busy, gap) == pytest.approx((22.694021, 77.012268))
    parts = {n: read(run) for n, read in
             stages.METRICS["ppr-g500-s18-closed16-short"].items()}
    assert parts == pytest.approx({"admit_gap_ms.short": 8.383969,
                                   "advance_gap_ms.short": 5.293691,
                                   "retire_gap_ms.short": 63.308388})
    # what is left is the step's own Python between the three parts
    assert gap - sum(parts.values()) == pytest.approx(0.02622, abs=1e-5)
    st = {s: stages.stage_ms_per_step(run, s)
          for s in stages.STAGES + (stages.UNSCOPED,)}
    assert st == pytest.approx({"rows": 4.525588, "schedule": 0.563461,
                                "execute": 4.233271, "refresh": 8.549805,
                                "unscoped": 4.821896})
    assert sum(st.values()) == pytest.approx(busy)
    assert stages.stage_ms_per_step(run, "unscoped:jit_admit") == \
        pytest.approx(1.418984)
    # the stacked carry's writes are the row map's, not admission's
    rows = [op for op, _ in stages.top_ops_by_stage(tr)["rows"][:3]]
    assert sorted(rows) == [f"jit_step:dynamic_update_slice.{i}"
                            for i in (129, 130, 133)]
    # idle goes to the innermost span, once: extraction holds most of it
    idle = dict(tr.idle_gaps(k=100))
    assert max(idle, key=idle.get) == "serve.extract"
    assert sum(idle.values()) == pytest.approx(tr.window_s() - tr.busy_s())
