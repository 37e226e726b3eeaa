#!/usr/bin/env python
"""Program spans and device-op stages in a profiler trace.

``tracing.py`` reduces a trace to device busy time and the harness's own
host spans. The program records more on the same clock:

* host spans (``jax.profiler.TraceAnnotation``) inside the service loop,
  ``serve.admit`` (``serve.resize``), ``serve.advance``
  (``serve.dispatch``, ``serve.flags``) and ``serve.retire``
  (``serve.extract``, ``serve.kill``), and ``engine.run`` around a solo
  engine call;
* a stage for every device op: the innermost of ``STAGES`` in the op's
  name path, which the engine tick's ``jax.named_scope``s write into the
  program's metadata (``unscoped`` where the path holds none; for a
  fusion, ``pick_path``); the paths come from the programs the trace
  file keeps (``xspace.py``).

``load`` keeps both in a ``ScopedTrace``, a ``tracing.Trace`` that reads
everything a ``Trace`` reads the same way, and adds device time per
stage and idle time per innermost span. Each instant of device time goes
to the innermost op running then (the latest started), so a loop's own
time is its stage's and its body's ops are theirs, and the stages' times
add up to the busy time. The readers at the end take a run as
``readers.py``'s do, and read None from a plain ``Trace``; ``METRICS``
binds them to each cell's per-layer readings.

Run as a script, it makes one traced run of a cell as ``bench/run.py
--trace 1`` does and prints the run's result line with a ``stages``
breakdown added (per step, or per engine tick in a solo cell)::

    python bench/stages.py --workload ppr-g500-s18-closed16-short --seed 7
    python bench/stages.py ... --record step.json   # one step, for a test
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import tracing  # noqa: E402
import xspace  # noqa: E402

#: the engine tick's stage scopes, outermost first
STAGES = ("rows", "schedule", "execute", "refresh")
UNSCOPED = "unscoped"
#: the service loop's parts that a step's host gap splits into
SERVE_PARTS = ("serve.admit", "serve.advance", "serve.retire")
#: every service span, the parts' children included
SERVE_SPANS = SERVE_PARTS + ("serve.resize", "serve.dispatch", "serve.flags",
                             "serve.extract", "serve.kill")
_PROGRAM_SPAN = ("serve.", "engine.")


def is_span(name: str) -> bool:
    """A host event a reading keeps: a harness span (``tracing.SPANS``)
    or a program span (``serve.*``, ``engine.*``)."""
    return name in tracing.SPANS or name.startswith(_PROGRAM_SPAN)


def stage_of(path: str) -> str:
    """The innermost stage scope in an op's name path."""
    stage = UNSCOPED
    for part in path.split("/"):
        if part in STAGES:
            stage = part
    return stage


def pick_path(paths) -> str:
    """The name path an op is read by, from ``(own, *fused)``
    (``xspace.op_paths``): its own where that holds a stage, else, for a
    fusion, one of the fused instructions' paths with the stage most of
    them hold (XLA names a fusion after one instruction, or none, and
    the pieces it splits a prefix sum into carry no name)."""
    own = paths[0] if paths else ""
    if stage_of(own) != UNSCOPED:
        return own
    staged = [q for q in paths[1:] if stage_of(q) != UNSCOPED]
    if not staged:
        return own
    top, _ = collections.Counter(map(stage_of, staged)).most_common(1)[0]
    return next(q for q in staged if stage_of(q) == top)


def innermost(intervals) -> list:
    """``(start, end, label)`` intervals cut into disjoint pieces, each
    instant labelled by the latest-started interval that holds it (the
    innermost, where intervals nest). The pieces cover the union."""
    pieces = []
    stack: list = []   # (end, label), the latest start on top
    now = -np.inf

    def run_to(t):
        nonlocal now
        while stack and now < t:
            end, label = stack[-1]
            if end <= now:
                stack.pop()
                continue
            upto = min(end, t)
            pieces.append((now, upto, label))
            now = upto
            if end <= t:
                stack.pop()

    for s, e, label in sorted(intervals, key=lambda x: (x[0], -x[1])):
        run_to(s)
        now = max(now, s)
        stack.append((e, label))
    run_to(np.inf)
    return pieces


@dataclasses.dataclass
class ScopedTrace(tracing.Trace):
    """A ``Trace`` whose ops carry their name paths (``paths[i]`` is
    ``ops[i]``'s; empty where unknown) and whose spans include the
    program's."""

    paths: list = None

    def __post_init__(self):
        super().__post_init__()
        self.paths = paths = self.paths or [""] * len(self.ops)
        stage = {p: stage_of(p) for p in set(paths)}
        by: dict = collections.defaultdict(list)
        for s, e, i in innermost(
                [(s, e, i) for i, (s, e, _) in enumerate(self.ops)]):
            by[stage[paths[i]]].append((s, e, ""))
            if stage[paths[i]] == UNSCOPED:
                program = self.ops[i][2].split(":")[0]
                by[f"{UNSCOPED}:{program}"].append((s, e, ""))
        # each stage's pieces as a trace of their own: its busy_in
        self._by = {k: tracing.Trace(v, []) for k, v in by.items()}

    def spans_named(self, name: str) -> list:
        """``(start, end)`` of each span called ``name`` inside the
        window."""
        lo, hi = self.window_ns
        return [(a, b) for a, b, n in self.spans
                if n == name and a >= lo and b <= hi]

    def stage_busy(self, stage: str, starts, ends) -> np.ndarray:
        """Device ns of ``stage`` inside each ``[starts[i], ends[i]]``:
        one of ``STAGES``, ``unscoped``, or ``unscoped:<program>`` for
        one program's unscoped ops."""
        got = self._by.get(stage)
        if got is None:
            return np.zeros(np.shape(starts))
        return got.busy_in(starts, ends)

    def unscoped_programs(self) -> list:
        return sorted(k for k in self._by if k.startswith(f"{UNSCOPED}:"))

    def idle_gaps(self, k: int = 10) -> list:
        """Device idle seconds inside the window, each idle instant put
        down to the innermost span the host was in (``between_spans``
        outside all of them)."""
        a, b = self.window_ns
        inside = [(s, e, n) for s, e, n in self.spans
                  if n != "window" and s >= a and e <= b]
        by: dict = collections.defaultdict(float)
        pieces = innermost(inside)
        if pieces:
            s = np.array([p[0] for p in pieces])
            e = np.array([p[1] for p in pieces])
            idle = (e - s) - self.busy_in(s, e)
            for (_, _, n), v in zip(pieces, idle):
                by[n] += float(v) / 1e9
        total = (b - a - float(self.busy_in(a, b))) / 1e9
        by["between_spans"] = total - sum(by.values())
        return sorted(([n, v] for n, v in by.items()),
                      key=lambda kv: -kv[1])[:k]


def host_spans(pd) -> list:
    """``(start, end, name)`` of the host events ``is_span`` keeps."""
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if is_span(ev.name)]


def load(trace_dir) -> ScopedTrace | None:
    """The newest trace under ``trace_dir``, as ``tracing.load`` reads
    it, with the program's spans and each op's name path; None where it
    holds no device op or no span."""
    import jax

    files = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    data = max(files, key=lambda p: p.stat().st_mtime).read_bytes()
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    ops, modules = [], []
    device_plane = None   # a cell holds one chip: the first device
    for plane in pd.planes:
        if not tracing._DEVICE_PLANE.match(plane.name) or \
                device_plane not in (None, plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                device_plane = plane.name
                ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                            tracing._op_name(ev)) for ev in line.events)
            elif line.name == "XLA Modules":
                modules.extend((ev.start_ns, ev.name) for ev in line.events)
    spans = host_spans(pd)
    if not ops or not spans:
        return None
    named = [(s, n.split("(")[0]) for s, n in modules]
    return ScopedTrace(tracing._by_module(ops, named), spans,
                       _paths(ops, modules, xspace.op_paths(data)))


def _paths(ops: list, modules: list, hlo: dict) -> list:
    """Each op's name path (``pick_path``), from the program it ran in:
    the ``XLA Modules`` event it falls in, found in ``hlo`` by its whole
    name (``jit_step(12)``), else by the part before ``(`` where one
    program has it."""
    if not modules:
        return [""] * len(ops)
    hlo = {prog: {op: pick_path(p) for op, p in table.items()}
           for prog, table in hlo.items()}
    short = collections.defaultdict(list)
    for name, table in hlo.items():
        short[name.split("(")[0]].append(table)
    modules = sorted(modules)
    tables = [hlo.get(n) or (short[n.split("(")[0]][0] if len(
        short[n.split("(")[0]]) == 1 else {}) for _, n in modules]
    lost = sorted({n for (_, n), t in zip(modules, tables) if not t})
    if lost:
        print(f"trace: no op paths for programs {lost[:5]}; the trace "
              f"file holds {sorted(hlo)[:5]}", file=sys.stderr)
    starts = np.array([s for s, _ in modules], np.float64)
    at = np.searchsorted(starts, [s for s, _, _ in ops], side="right") - 1
    return [tables[i].get(op, "") if i >= 0 else ""
            for (_, _, op), i in zip(ops, at)]


# ---- readers: bind with functools.partial in a metric file ------------

def _scoped(run):
    tr = getattr(run, "trace", None)
    return tr if isinstance(tr, ScopedTrace) else None


def _in_spans(tr, name):
    got = tr.spans_named(name)
    return (np.array([a for a, _ in got], float),
            np.array([b for _, b in got], float))


def stage_ms_per_step(run, stage):
    """Device ms of ``stage`` per service step (harness ``step``)."""
    tr = _scoped(run)
    if tr is None:
        return None
    s, e = _in_spans(tr, "step")
    if not s.size:
        return None
    return float(np.sum(tr.stage_busy(stage, s, e))) / s.size / 1e6


def stage_ms_per_tick(run, stage):
    """Device ms of ``stage`` inside ``engine.run`` per engine tick of
    the window's whole-graph calls."""
    tr = _scoped(run)
    if tr is None:
        return None
    s, e = _in_spans(tr, "engine.run")
    ticks = sum(a.info.get("ticks", 0) for a in run.window.answered)
    if not s.size or not ticks:
        return None
    return float(np.sum(tr.stage_busy(stage, s, e))) / ticks / 1e6


def gap_ms_per_step(run, span):
    """Device idle ms inside ``span`` (its children's included) per
    service step."""
    tr = _scoped(run)
    if tr is None:
        return None
    n = len(tr.spans_named("step"))
    dur, busy = tr.span_times(span)
    if not n or not dur.size:
        return None
    return float(np.sum(dur - busy)) / n / 1e6


def _stage_readers(read, names, cell):
    return {f"{s}_ms.{cell}": functools.partial(read, stage=s)
            for s in names}


def _gap_readers(cell):
    return {f"{p.split('.')[1]}_gap_ms.{cell}":
            functools.partial(gap_ms_per_step, span=p) for p in SERVE_PARTS}


#: the per-layer readings of each benchmark cell, under the names a
#: metric file ``bench/metrics/<name>.py`` would give them (binding the
#: reader as ``read``); they read something only from a ``ScopedTrace``
METRICS = {
    "bfs-g500-s18": _stage_readers(stage_ms_per_tick, STAGES[1:], "bfs"),
    "ppr-g500-s18-closed16": {
        **_stage_readers(stage_ms_per_step, STAGES, "closed"),
        **_gap_readers("closed")},
    "ppr-g500-s18-closed16-short": _gap_readers("short"),
}


# ---- the script: one traced run with the breakdown ---------------------

def breakdown(run, cell: str) -> dict:
    """The cell's ``METRICS``; device ms of every stage and unscoped
    program and idle ms in every service span, per step (per engine tick
    where the run made no step); the costliest ops of each stage; and the
    share of ops whose name path the trace file gave."""
    tr = run.trace
    solo = not tr.spans_named("step")
    per = stage_ms_per_tick if solo else stage_ms_per_step
    keys = STAGES + (UNSCOPED,) + tuple(tr.unscoped_programs())
    gaps = {n: gap_ms_per_step(run, n) for n in SERVE_SPANS}
    return {"metrics": {n: read(run)
                        for n, read in METRICS.get(cell, {}).items()},
            "per": "engine tick" if solo else "step",
            "device_ms": {k: per(run, k) for k in keys},
            "gap_ms": {n: v for n, v in gaps.items() if v is not None},
            "top_ops": top_ops_by_stage(tr),
            "ops_with_path": sum(map(bool, tr.paths)) / max(len(tr.paths), 1)}


def top_ops_by_stage(tr: ScopedTrace, k: int = 5) -> dict:
    """For each stage, the ``k`` leaf ops (as ``Trace.top_ops`` tells
    them from the loops that hold them) with most device seconds in the
    window."""
    a, b = tr.window_ns
    stage = {p: stage_of(p) for p in set(tr.paths)}
    order = sorted(range(len(tr.ops)), key=lambda i: tr.ops[i])
    tot: dict = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for j, i in enumerate(order):
        s, e, key = tr.ops[i]
        nxt = tr.ops[order[j + 1]] if j + 1 < len(order) else None
        parent = nxt is not None and nxt[0] < e and nxt[1] <= e
        if e > a and s < b and not parent:
            tot[stage[tr.paths[i]]][key] += (min(e, b) - max(s, a)) / 1e9
    return {st: sorted(([op, v] for op, v in got.items()),
                       key=lambda kv: -kv[1])[:k]
            for st, got in tot.items()}


def record_step(tr: ScopedTrace, path, source: str) -> None:
    """Write the window's median ``step`` (by duration) with its spans
    and ops, in whole ns from its start, op keys and paths as indices
    into tables (``read_step`` reads it back)."""
    steps = sorted(tr.spans_named("step"), key=lambda x: x[1] - x[0])
    a, b = steps[len(steps) // 2]
    spans = [[round(s - a), round(e - a), n] for s, e, n in tr.spans
             if a <= s and e <= b]
    keys: dict = {}
    paths: dict = {}
    ops = [[round(s - a), round(e - a), keys.setdefault(key, len(keys)),
            paths.setdefault(p, len(paths))]
           for (s, e, key), p in zip(tr.ops, tr.paths) if a <= s < b]
    pathlib.Path(path).write_text(json.dumps({
        "source": source, "keys": list(keys), "paths": list(paths),
        "spans": spans, "ops": ops}))


def read_step(path) -> ScopedTrace:
    """A step ``record_step`` wrote."""
    d = json.loads(pathlib.Path(path).read_text())
    return ScopedTrace([(s, e, d["keys"][k]) for s, e, k, _ in d["ops"]],
                       [tuple(x) for x in d["spans"]],
                       [d["paths"][i] for *_, i in d["ops"]])


def main(argv=None) -> int:
    import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--record", default=None,
                    help="write the window's median step here (JSON)")
    args = ap.parse_args(argv)
    args.trace, args.seconds = 1, bench_run.TRACE_S
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    kept: dict = {}

    class Tracer(bench_run.Tracer):
        """``run.py``'s tracer, reading the trace with ``load``."""

        def reduce(self):
            t0 = time.perf_counter()
            self.jax.profiler.stop_trace()
            t1 = time.perf_counter()
            try:
                return load(self.dir)
            finally:
                bench_run.log(f"trace: stop {t1 - t0:.2f} s, read "
                              f"{time.perf_counter() - t1:.2f} s")
                shutil.rmtree(self.dir, ignore_errors=True)

    @dataclasses.dataclass
    class Run(bench_run.Run):
        """``run.py``'s ``Run``, kept for the readings."""

        def __post_init__(self):
            kept["run"] = self

    bench_run.Tracer, bench_run.Run = Tracer, Run
    try:
        out = bench_run.run(args)
    except bench_run.BenchError as e:
        bench_run.log(f"bench: {e}")
        return 2
    run = kept.get("run")
    if run is not None and isinstance(run.trace, ScopedTrace):
        out["stages"] = breakdown(run, args.workload)
        if args.record and run.trace.spans_named("step"):
            record_step(run.trace, args.record,
                        f"the median service step of {args.workload} on a "
                        f"{out['device']['kind']}, seed {args.seed}, times "
                        "in ns from the step's start")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
