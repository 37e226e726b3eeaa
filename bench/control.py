#!/usr/bin/env python
"""The control of a cell's comparison: the plain reference put in the
program's place, one step below what the configuration states, read by
the same comparison. It has to come out as not correct.

    python bench/control.py --workload ppr-g500-s18-closed16 --seeds 1,2,3
    python bench/control.py --workload bfs-g500-s18 --seeds 1,2,3

Each query kind's ``control`` in ``bench/queries/<kind>.py`` says what
its control is; it reads the sources the cell's run draws first for the
same seed. Needs no chip; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys

import run as bench_run
import traffic
from graph500 import plain_graph


def readings(workload: str, seed: int, queries: int, scale: int | None
             ) -> dict:
    """The control's numbers ``{name: (value, limit)}``, the worst over
    the mix's specs, for the first ``queries`` queries of ``seed``."""
    _, _, config, mix = bench_run.load_cell(workload)
    inp = bench_run.Inputs(config, scale or config["scale"], seed)
    g = plain_graph(inp.n, inp.t_src, inp.t_dst)
    drawn = list(itertools.islice(traffic.Stream(mix, inp, inp.rng_src),
                                  queries))
    out: dict = {}
    for k, spec in enumerate(mix["queries"]):
        srcs = [s for j, s in drawn if j == k]
        if not srcs:
            continue
        got = traffic.plugin("queries", spec["kind"]).control(
            g, srcs, spec, config)
        for name, (v, lim) in got.items():
            if name not in out or v > out[name][0]:
                out[name] = (v, lim)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--queries", type=int, default=24,
                    help="queries per seed (the cell's check sample)")
    ap.add_argument("--scale", type=int, default=None,
                    help="graph scale (default: the configuration's)")
    args = ap.parse_args()
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(args.workload, seed, args.queries, args.scale)
        fails = any(v > lim for v, lim in r.values())
        failed_all &= fails
        print(json.dumps({"seed": seed, **{k: {"value": v, "limit": lim}
                                           for k, (v, lim) in r.items()},
                          "control_fails": fails}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
