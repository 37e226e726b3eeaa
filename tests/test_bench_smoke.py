"""Tier-1 perf smoke: tools/bench_smoke.py runs a tiny-graph benchmark
subset and leaves a BENCH_smoke.json perf-trajectory point. Also the
entry points' other contracts on the CPU: chip_smoke.py's rehearsal
reaches its ok line and, without a TPU or the rehearsal flag, fails; the
compile cache goes where JAX_COMPILATION_CACHE_DIR says, or to one fixed
path in the checkout."""
import json
import os
import pathlib
import subprocess
import sys

import jax

from repro.compile_cache import CACHE_DIRNAME, use_compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_bench_smoke_writes_trajectory_point():
    out = ROOT / "BENCH_smoke.json"
    mq_out = ROOT / "BENCH_multi_query.json"
    svc_out = ROOT / "BENCH_service.json"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "bench_smoke.py"),
         str(out), str(mq_out), str(svc_out)],
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(out.read_text())
    assert data["failures"] == 0
    names = {r["name"] for r in data["results"]}
    assert any(n.startswith("fig10_") for n in names)
    assert any(n.startswith("device_tps") for n in names)
    # device-sweep acceptance: occupancy monotone in queue_depth
    mono = [r for r in data["results"]
            if r["name"].startswith("device_occ_monotone")]
    assert mono and all(r["derived"] == "ok" for r in mono)
    # concurrent-plane smoke: the Q=4 PPR point ran, its physical +
    # shared I/O exactly matches the run_many baseline, and the rows
    # were split into the dedicated multi-query artifact
    assert any(n.startswith("multiq_ppr_q04") for n in names)
    base = [r for r in data["results"]
            if r["name"].startswith("multiq_ppr_runmany_baseline")]
    assert base and all("conservation_ok" in r["derived"] for r in base)
    mq = json.loads(mq_out.read_text())
    assert mq["failures"] == 0
    assert {r["name"] for r in mq["results"]} == \
        {n for n in names if n.startswith("multiq_")}
    # aggregated-plane smoke (PR 6): the BFS/WCC aggregated rows ran,
    # reached the per-query plane's results, and passed the in-bench
    # gates (strict block-pass reduction at Q>=4, peak <= pool_slots)
    agg = [r for r in mq["results"] if "_agg_" in r["name"]]
    assert len(agg) >= 2 and all("results_ok" in r["derived"]
                                 for r in agg)
    # derived-only rows omit us_per_call rather than writing 0.0 —
    # every timed multi-query row here carries a real measurement
    assert all(r["us_per_call"] > 0 for r in mq["results"]
               if "us_per_call" in r)
    # serving-SLO smoke: the Poisson scenarios ran, each demonstrated
    # at least one mid-flight admission with zero idle-barrier ticks
    # (the in-bench gates raise on identity/conservation/monotonicity
    # violations, so green rows imply those held), and the rows landed
    # in the dedicated service artifact
    svc = json.loads(svc_out.read_text())
    assert svc["failures"] == 0
    svc_names = {r["name"] for r in svc["results"]}
    assert svc_names == {n for n in names if n.startswith("service_")}
    assert any(n.startswith("service_bfs_poisson") for n in svc_names)
    assert any(n.startswith("service_bfs_agg_poisson")
               for n in svc_names)
    assert any(n.startswith("service_hetero_poisson")
               for n in svc_names)
    for r in svc["results"]:
        assert "_midflight_0_" not in r["derived"], r
        assert "_idle_barriers_0" in r["derived"], r
        assert r["us_per_call"] > 0


def _chip_smoke(*args, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)


def test_chip_smoke_cpu_rehearsal_reaches_ok_line(tmp_path):
    cache = tmp_path / "jax_cache"
    # JAX keeps only programs that took a second or more to compile; at
    # scale 8 the CPU compiles the service step in about one, so every
    # program is kept here, else whether the cache is written is chance
    proc = _chip_smoke("--cpu-rehearsal", "--scale", "8",
                       env={"JAX_COMPILATION_CACHE_DIR": str(cache),
                            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS":
                                "0"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": 1}
    assert "admitted mid-flight" in proc.stdout
    # JAX_COMPILATION_CACHE_DIR is the only cache setting when set
    assert any(cache.iterdir())


def test_chip_smoke_without_tpu_fails():
    proc = _chip_smoke("--scale", "8")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_location(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache(ROOT) == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache(ROOT)
        assert path == str(ROOT.resolve() / CACHE_DIRNAME)
        assert jax.config.jax_compilation_cache_dir == path
        gitignore = (ROOT / ".gitignore").read_text().splitlines()
        assert f"{CACHE_DIRNAME}/" in gitignore
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
