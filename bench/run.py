#!/usr/bin/env python
"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python bench/run.py --workload <name> --seed 1 \\
        --seconds 2 --trace 0 --cpu-rehearsal --scale 10

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, read by ``traffic.py``, which finds
its sources, arrivals and query kinds by name); each metric is read by
``bench/metrics/<metric>.py``. A run builds the configuration's graph,
warms up the cell's programs (set-up), drives the mix for ``--seconds``,
waits for the answers still due, reads the device's peak memory, frees
the program's state, and then checks answers against the plain
references in ``reference.py``.

Without a TPU, or with fewer chips than the cell asks for, the run fails
and prints no result, unless ``--cpu-rehearsal`` is given, which caps
the scale and names the CPU as the device.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import drivers  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_gen  # noqa: E402
from graph500 import graph500_tuples, plain_graph  # noqa: E402
from peaks import peaks  # noqa: E402

REHEARSAL_MAX_SCALE = 12
#: length of a ``--trace 1`` run's window (at least one call);
#: ``--seconds`` sets only the untraced runs' window. Stopping and
#: reading the profiler's trace takes 10-15 s per traced second on a
#: v5e host, so a trace of the whole untraced window would not end
#: within the 360 s a run has.
TRACE_S = 5.0


class BenchError(Exception):
    """The run cannot be made or measured as the cell asks."""


@dataclass
class Run:
    """What the metric readers see."""

    window: drivers.Window
    setup_s: float
    trace: object = None


class Tracer:
    """Host spans around each call into the system; in a traced run the
    profiler records from before the window's first call to after the
    drain's last, and the readers keep the ``window`` span's part."""

    def __init__(self, on: bool):
        import jax

        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.state = "idle" if on else "off"

    def start(self) -> None:
        if self.state == "idle":
            self.jax.profiler.start_trace(self.dir)
            self.state = "on"

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def reduce(self):
        import tracing

        t0 = time.perf_counter()
        self.jax.profiler.stop_trace()
        t1 = time.perf_counter()
        try:
            return tracing.load(self.dir)
        finally:
            log(f"trace: stop {t1 - t0:.2f} s, read "
                f"{time.perf_counter() - t1:.2f} s")
            shutil.rmtree(self.dir, ignore_errors=True)


class CompileCounter:
    """Compilations (and compile-cache loads) JAX reports, by phase."""

    def __init__(self):
        import jax

        self.events: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if "backend_compile" in event or "cache_retrieval" in event:
            self.events.append(event)


class Inputs:
    """What a run generates: the configuration's graph, from the
    configuration's ``graph_seed``, and from the run's seed the query
    stream, the arrivals and the sample of answers checked.

    The graph is the deployment's data set and the same in every run:
    its shape fixes the shapes of the compiled programs, so a graph per
    seed would compile in every run and change the work from seed to
    seed."""

    def __init__(self, config: dict, scale: int, seed: int):
        seq = np.random.SeedSequence(seed % (1 << 64))
        self.rng_src, self.rng_arrive, self.rng_sample = (
            np.random.default_rng(s) for s in seq.spawn(3))
        g = config["graph"]
        rng_graph = np.random.default_rng(g["graph_seed"])
        self.n = n = 1 << scale
        self.t_src, self.t_dst = graph500_tuples(
            scale, config["edgefactor"], g["a"], g["b"], g["c"], rng_graph)
        loops = self.t_src == self.t_dst
        #: tuple endpoints per vertex, self-loops left out
        self.endpoints = np.bincount(self.t_src[~loops], minlength=n) + \
            np.bincount(self.t_dst[~loops], minlength=n)
        self.nonisolated = np.flatnonzero(self.endpoints)
        self.isolated = np.flatnonzero(self.endpoints == 0)

    def warm_source(self) -> int:
        """A vertex with no edge where there is one: its query compiles
        the same programs and finishes in a tick or two."""
        return int((self.isolated if self.isolated.size
                    else self.nonisolated)[0])


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    traffic_gen.validate(mix)
    return bench, cell, config, mix


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run: Run):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def check_device(cell: dict, rehearsal: bool):
    import jax

    devs = jax.devices()
    if rehearsal:
        return devs[0]
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} (use "
                         "--cpu-rehearsal for a CPU run at a tiny scale)")
    if len(devs) < cell["chips"]:
        raise BenchError(f"the cell asks for {cell['chips']} chips and JAX "
                         f"found {len(devs)}")
    return devs[0]


def use_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(args) -> dict:
    bench, cell, config, mix = load_cell(args.workload)
    scale = config["scale"]
    if args.cpu_rehearsal:
        scale = args.scale or min(scale, REHEARSAL_MAX_SCALE)
        if scale > REHEARSAL_MAX_SCALE:
            raise BenchError(f"--cpu-rehearsal caps the scale at "
                             f"{REHEARSAL_MAX_SCALE}")
    elif args.scale not in (None, scale):
        raise BenchError("--scale is for --cpu-rehearsal only")
    dev = check_device(cell, args.cpu_rehearsal)
    import jax

    cache = use_cache()
    compiles = CompileCounter()
    from repro.core import (ContinuousService, EngineConfig, GraphSession,
                            ServeConfig)
    from repro.storage.csr import from_edges

    log(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    inp = Inputs(config, scale, args.seed)
    n, t_src, t_dst = inp.n, inp.t_src, inp.t_dst
    t1 = time.perf_counter()
    sess = GraphSession(
        from_edges(n, np.concatenate([t_src, t_dst]),
                   np.concatenate([t_dst, t_src])),
        EngineConfig(**config["engine"]))
    jax.block_until_ready(sess.engine.tables)
    t2 = time.perf_counter()
    log(f"graph: scale {scale}, {t_src.size} tuples, engine V "
        f"{sess.engine.V}, {inp.nonisolated.size} vertices with edges; "
        f"start {t0 - T_START:.2f} s, tuples {t1 - t0:.2f} s, build and "
        f"upload {t2 - t1:.2f} s")

    specs = mix["queries"]
    kinds = [traffic_gen.plugin("queries", q["kind"]) for q in specs]
    makers = [m.make(q, config) for m, q in zip(kinds, specs)]
    spec_of: dict = {}   # id(query) -> index of its spec

    def queries():
        for k, src in traffic_gen.Stream(mix, inp, inp.rng_src):
            q = makers[k](src)
            spec_of[id(q)] = k
            yield q

    warm = [make(inp.warm_source()) for make in makers]
    tracer = Tracer(bool(args.trace))
    seconds = TRACE_S if args.trace else args.seconds
    samples = [traffic_gen.Reservoir(mix.get("check_sample", 0),
                                     inp.rng_sample) for _ in specs]
    every = "check_sample" not in mix
    checked = [[] for _ in specs]   # (answer, source, result) compared
    longest = [[] for _ in specs]

    def on_answer(a: drivers.Answer) -> None:
        if a.error is None:
            k = spec_of[id(a.query)]
            a.info["ticks"] = a.result.metrics.ticks
            keep = (a, a.query.source, np.asarray(a.result.result))
            if every:
                checked[k].append(keep)
            else:
                samples[k].offer(keep)
                if not longest[k] or a.info["ticks"] > longest[k][0][
                        0].info["ticks"]:
                    longest[k][:] = [keep]
        a.result = None

    # ---- set-up: warm the cell's programs, then the window -------------
    if mix["driver"] == "solo":
        for q in warm:
            sess.run(q)
        setup_s = time.perf_counter() - T_START
        log(f"warm-up {T_START + setup_s - t2:.2f} s")
        n_compiles = len(compiles.events)
        tracer.start()
        win = drivers.run_solo(sess.run, queries(), seconds,
                               span=tracer.span, on_answer=on_answer)
    else:
        svc = ContinuousService(sess, serve=ServeConfig(**config["serve"]))
        for q in warm:
            for _ in range(svc.serve.max_capacity):
                svc.submit(q)
        svc.run_until_idle()
        setup_s = time.perf_counter() - T_START
        log(f"warm-up {T_START + setup_s - t2:.2f} s")
        n_compiles = len(compiles.events)
        if mix["driver"] == "closed":
            tracer.start()
            win = drivers.run_closed(svc, queries(), int(mix["clients"]),
                                     seconds, span=tracer.span,
                                     on_answer=on_answer)
        else:
            due = traffic_gen.arrivals(mix, seconds, inp.rng_arrive)
            tracer.start()
            win = drivers.run_open(svc, queries(), list(due), seconds,
                                   span=tracer.span, on_answer=on_answer)
        del svc
    in_window = len(compiles.events) - n_compiles
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None and dev.platform == "tpu":
        log(f"peak device memory {peak} bytes, "
            f"{100 * peak / peaks(dev.device_kind)['hbm_bytes']:.2f} % of "
            "the chip's HBM")
    trace = tracer.reduce() if args.trace else None
    del sess
    gc.collect()
    log(f"window: {win.attempted} queries due, {len(win.answered)} "
        f"answered, {win.calls} calls, ends {win.end:.3f} s; "
        f"compiles or cache loads in window: {in_window}")
    if win.lateness:
        late = np.asarray(win.lateness) * 1e3
        log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
            f"p99 {np.percentile(late, 99):.3f} max {late.max():.3f}")

    # ---- after the window: the plain references ------------------------
    t0 = time.perf_counter()
    pg = plain_graph(n, t_src, t_dst)
    checks: dict = {}
    n_checked = 0
    for kind, spec, kept, sample, top in zip(kinds, specs, checked,
                                             samples, longest):
        kept += sample.items + [x for x in top if all(
            x[0] is not y[0] for y in sample.items)]
        n_checked += len(kept)
        for name, (v, lim) in kind.check(pg, kept, spec, config,
                                         inp).items():
            if name not in checks or v > checks[name][0]:
                checks[name] = (v, lim)
    log(f"checked {n_checked} of {len(win.answered)} answers")
    log(f"references: {time.perf_counter() - t0:.2f} s")

    r = Run(win, setup_s, trace)
    metrics = {}
    for m in metrics_for(bench, args.workload, bool(args.trace)):
        val = read_metric(m["name"], r)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    ok = all(v <= lim for v, lim in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(ok and win.failed == 0 and win.attempted > 0),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        out["breakdown"] = {"device_ops": trace.top_ops(),
                            "idle_gaps": trace.idle_gaps()}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} limit {lim!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run without a TPU, at scale <= "
                         f"{REHEARSAL_MAX_SCALE}")
    ap.add_argument("--scale", type=int, default=None,
                    help="graph scale of a --cpu-rehearsal")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        out = run(args)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
