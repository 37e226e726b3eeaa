"""A whole run on the CPU at a tiny scale: the last line's contract, the
refusal without a TPU, a mix added as files alone, the control failing
the comparison, and ``correct`` coming out false under each fault the
timed path can have."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import control
import drivers
import run as bench_run

BENCH = pathlib.Path(bench_run.__file__).resolve().parent
ROOT = BENCH.parent
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 31 + 12345
TINY = ["--seconds", "1.5", "--cpu-rehearsal", "--scale", "8"]


def run_cli(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [(c, 0) for c in CELLS] +
                         [(CELLS[0], 1)])
def test_last_line_contract(cell, trace):
    p = run_cli(["--workload", cell, "--seed", str(SEED), "--trace",
                 str(trace), *TINY])
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_line(p)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[:5] == keys and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 1
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    group = bench["per_layer"] if trace else bench["end_to_end"]
    want = {m["name"] for m in group if cell in m.get("workloads", [cell])}
    # device-trace metrics are left out where the CPU has no device plane
    got = set(out["metrics"])
    assert got <= want
    if not trace:
        assert got == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"]
        assert name in p.stderr.strip().splitlines()[-len(out["checks"]):][
            list(out["checks"]).index(name)]


def test_setup_s_is_reported_by_every_cell():
    """``setup_s`` carries no ``workloads`` list, so every cell, those
    added later too, reports it beside one other end-to-end metric."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for cell in CELLS:
        names = {m["name"] for m in bench_run.metrics_for(bench, cell, False)}
        assert "setup_s" in names and len(names) >= 2


def test_refuses_without_tpu():
    p = run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def copy_benchmark(tmp_path, with_program: bool) -> pathlib.Path:
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    if with_program:
        (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path


def test_fails_with_only_the_benchmark_files(tmp_path):
    d = copy_benchmark(tmp_path, with_program=False)
    p = run_cli(["--workload", CELLS[0], "--seed", "1", "--trace", "0",
                 *TINY], cwd=d)
    assert p.returncode != 0
    assert not p.stdout.strip()


DUMMY_MIXES = {
    # PPR and BFS co-executing, hub-heavy sources
    "closed": ({"driver": "closed", "clients": 3,
                "queries": [{"kind": "ppr", "r_max": 1e-3, "share": 3},
                            {"kind": "bfs", "share": 1}],
                "sources": {"sampler": "degree_weighted"},
                "check_sample": 4},
               ["ppr_qps", "setup_s"]),
    # bursty open-loop arrivals
    "open": ({"driver": "open",
              "arrivals": {"process": "on_off", "rate_qps": 20,
                           "on_s": 0.5, "off_s": 0.25},
              "queries": [{"kind": "ppr", "r_max": 1e-3}],
              "sources": {"sampler": "uniform_nonisolated"},
              "check_sample": 4},
             ["latency_p90_ms", "setup_s"]),
}


@pytest.mark.parametrize("driver", sorted(DUMMY_MIXES))
def test_a_mix_added_as_files_alone(tmp_path, driver):
    mix, metrics = DUMMY_MIXES[driver]
    d = copy_benchmark(tmp_path, with_program=True)
    (d / "bench" / "traffic" / "dummy.json").write_text(json.dumps(mix))
    bench = json.loads((d / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "ppr-dummy", "config": "g500-s18-ppr",
        "traffic": "dummy", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in metrics and "workloads" in m:
            m["workloads"].append("ppr-dummy")
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    p = run_cli(["--workload", "ppr-dummy", "--seed", "3", "--trace", "0",
                 *TINY], cwd=d)
    assert p.returncode == 0, p.stderr[-3000:]
    out = last_line(p)
    assert out["correct"] is True
    assert set(out["metrics"]) == set(metrics)
    assert set(out["checks"]) == ({"ppr_push_violation",
                                   "bfs_mismatched_vertices"}
                                  if driver == "closed" else
                                  {"ppr_push_violation"})


def test_a_cell_added_as_files_alone(tmp_path):
    """A configuration, a per-layer metric and a cell added as new files
    and entries only: the short mix on a scale-8 PPR configuration, run
    at the configuration's own scale."""
    d = copy_benchmark(tmp_path, with_program=True)
    cfg = json.loads((d / "bench" / "configs" / "g500-s18-ppr.json")
                     .read_text())
    cfg.update(name="g500-s8-ppr", scale=8)
    (d / "bench" / "configs" / "g500-s8-ppr.json").write_text(
        json.dumps(cfg))
    (d / "bench" / "metrics" / "ticks_per_ppr.s8.py").write_text(
        "from readers import mean_ticks as read  # noqa: F401\n")
    bench = json.loads((d / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "g500-s8-ppr", "source": "test",
        "file": "bench/configs/g500-s8-ppr.json", "reduced": ["scale"],
        "why": "test"})
    bench["workloads"].append({
        "name": "ppr-s8-short", "config": "g500-s8-ppr",
        "traffic": "ppr-closed16-short", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ppr_qps", "latency_p90_ms"):
            m["workloads"].append("ppr-s8-short")
    bench["per_layer"].append({
        "name": "ticks_per_ppr.s8", "unit": "ticks", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ppr_qps", "workloads": ["ppr-s8-short"]})
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    want = {0: {"ppr_qps", "latency_p90_ms", "setup_s"},
            1: {"ticks_per_ppr.s8"}}
    for trace, metrics in want.items():
        p = run_cli(["--workload", "ppr-s8-short", "--seed", str(SEED),
                     "--trace", str(trace), "--seconds", "1.5",
                     "--cpu-rehearsal"], cwd=d)
        assert p.returncode == 0, p.stderr[-3000:]
        assert "graph: scale 8," in p.stderr
        out = last_line(p)
        assert out["correct"] is True
        assert set(out["metrics"]) == metrics


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(cell):
    # three seeds, as on the chip, at a scale a test run holds
    for seed in (11, 12, 13):
        r = control.readings(cell, seed, queries=4, scale=10)
        assert any(v > lim for v, lim in r.values()), (seed, r)


# ---- faults planted under the timed path --------------------------------

def _in_window(monkeypatch, fault):
    """Apply ``fault(svc_or_session_call)`` as the window starts, after
    set-up has warmed the programs."""
    for name in ("run_closed", "run_open"):
        real = getattr(drivers, name)

        def wrapped(svc, *a, _real=real, **k):
            fault(svc)
            return _real(svc, *a, **k)
        monkeypatch.setattr(drivers, name, wrapped)
    real_solo = drivers.run_solo

    def solo(call, *a, **k):
        return real_solo(fault(call), *a, **k)
    monkeypatch.setattr(drivers, "run_solo", solo)


def _unchanged_state(svc):
    for g in svc._groups.values():
        step = g.fns["step"]

        def same(carry, _step=step):
            _, *flags = _step(carry)
            return (carry, *flags)
        g.fns = dict(g.fns, step=same)


def _half_left_out(svc):
    step = svc.step
    svc.step = lambda: step()[::2]


def _answer_altered_ppr(svc):
    extract = svc._extract_row

    def altered(*a):
        res = extract(*a)
        res.result[np.argmax(res.result)] *= 1.001
        return res
    svc._extract_row = altered


def _solo_unchanged(call):
    """The engine's loop hands back the state it was given."""
    from repro.core.engine import Engine

    real = Engine.run

    def run(self, algo, frontier, state):
        _, metrics, trace = real(self, algo, frontier, state)
        return state, metrics, trace
    Engine.run = run
    return call


def _answer_altered_bfs(call):
    def run(q):
        res = call(q)
        res.result[np.argmax(res.result < 2 ** 30)] += 1
        return res
    return run


FAULTS = [(cell, fault)
          for cell in ("ppr-g500-s18-closed16", "ppr-g500-s18-closed16-short")
          for fault in (_unchanged_state, _half_left_out,
                        _answer_altered_ppr)] + [
    ("bfs-g500-s18", _solo_unchanged),
    ("bfs-g500-s18", _answer_altered_bfs)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_makes_correct_false(cell, fault, monkeypatch, capsys):
    from repro.core.engine import Engine

    monkeypatch.setattr(Engine, "run", Engine.run)   # restored after
    monkeypatch.setattr(drivers, "DRAIN_S", 3.0)
    _in_window(monkeypatch, fault)
    rc = bench_run.main(["--workload", cell, "--seed", str(SEED),
                         "--trace", "0", *TINY])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
