"""What the metric files read. Each ``bench/metrics/<name>.py`` binds
one of these as ``read``; ``read(run)`` returns the metric's value, or
None where the run holds nothing to read (then the metric is left out).

``run`` is ``run.Run``: the driver's ``window``, ``setup_s``, and with
``--trace 1`` the reduced ``trace`` of the window (None where the
profiler saw no device). Readings from the trace cover the driver's
``window`` span alone: a closed loop's steps there all run with as many
rows as it has clients, and the drain after the close is left out.
"""
from __future__ import annotations

import numpy as np


def latency_p90_ms(run):
    lat = [a.latency for a in run.window.answered]
    return float(np.percentile(lat, 90)) * 1e3 if lat else None


def setup_s(run):
    return run.setup_s


def answers_per_s(run):
    """Answers that came back inside the window, over its length."""
    w = run.window
    n = sum(a.done <= w.seconds for a in w.answered)
    return n / w.seconds if n else None


def teps(run):
    """Input tuples traversed by the calls of the window, over the time
    up to the end of the last one."""
    w = run.window
    edges = [a.info.get("edges") for a in w.answered]
    if not edges or any(e is None for e in edges):
        return None
    return sum(edges) / w.end


def mean_ticks(run):
    t = [a.info["ticks"] for a in run.window.answered if "ticks" in a.info]
    return float(np.mean(t)) if t else None


def idle_share_pct(run):
    tr = run.trace
    if tr is None or tr.window_s() <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())


def _per_step(run, name):
    if run.trace is None:
        return None
    dur, busy = run.trace.span_times(name)
    return (dur, busy) if dur.size else None


def step_busy_ms(run):
    """Device busy per service step."""
    got = _per_step(run, "step")
    return None if got is None else float(np.mean(got[1])) / 1e6


def step_host_gap_ms(run):
    """Host time per service step in which the device was idle."""
    got = _per_step(run, "step")
    return None if got is None else float(np.mean(got[0] - got[1])) / 1e6


def tick_busy_ms(run):
    """Device busy per engine tick of the whole-graph calls."""
    got = _per_step(run, "bfs_call")
    if got is None:
        return None
    ticks = sum(a.info.get("ticks", 0) for a in run.window.answered)
    return float(np.sum(got[1])) / 1e6 / ticks if ticks else None
