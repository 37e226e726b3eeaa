"""The PageRank cell on the CPU at a tiny scale: ``correct`` comes out
false under each fault its timed path can have. The cell's last-line
contract and its control are tested with every cell's in
``test_run.py``."""
import dataclasses
import json

import numpy as np
import pytest

import drivers
import run as bench_run
from test_run import SEED, TINY, _in_window, _solo_unchanged

CELL = "pagerank-g500-s16"


def _answer_altered(call):
    """One answer's largest entry scaled by 1.001."""
    def run(q):
        res = call(q)
        res.result[np.argmax(res.result)] *= 1.001
        return res
    return run


def _coarse_r_max(call):
    """Answers computed at ten times the cell's ``r_max``."""
    def run(q):
        return call(dataclasses.replace(q, r_max=10 * q.r_max))
    return run


FAULTS = [_solo_unchanged, _answer_altered, _coarse_r_max]


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_fault_makes_correct_false(fault, monkeypatch, capsys):
    from repro.core.engine import Engine

    monkeypatch.setattr(Engine, "run", Engine.run)   # restored after
    monkeypatch.setattr(drivers, "DRAIN_S", 3.0)
    _in_window(monkeypatch, fault)
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                         "--trace", "0", *TINY])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False
