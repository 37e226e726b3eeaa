"""The profiler trace of a run, reduced to what the metrics read.

A trace holds the device's operations (``/device:TPU:<n>``, line
``XLA Ops``) and the harness's own host spans (``jax.profiler.
TraceAnnotation`` around each call into the system: ``step``,
``submit``, ``retire``, ``bfs_call``, and ``window`` over the driver's window
up to its close), on one clock. Device busy time is the union of the
operations' intervals. The traced window is the ``window`` span where
there is one, else from the first harness span's start to the last
one's end; every reading is taken inside it.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

import numpy as np

#: the host spans the harness records (see ``drivers.py``)
SPANS = ("step", "submit", "retire", "bfs_call", "window")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Trace:
    ops: list        # (start_ns, end_ns, "<program>:<op>") on the device
    spans: list      # (start_ns, end_ns, name) of harness host spans

    def __post_init__(self):
        iv = sorted((s, e) for s, e, _ in self.ops)
        merged: list[list[float]] = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        m = np.asarray(merged, np.float64).reshape(-1, 2)
        self._starts, self._ends = m[:, 0], m[:, 1]
        self._cum = np.concatenate([[0.0], np.cumsum(m[:, 1] - m[:, 0])])
        self.spans = sorted(self.spans)

    @property
    def window_ns(self) -> tuple[float, float]:
        marked = [(s, e) for s, e, n in self.spans if n == "window"]
        if marked:
            return marked[0]
        return (min(s for s, _, _ in self.spans),
                max(e for _, e, _ in self.spans))

    def busy_before(self, t) -> np.ndarray:
        """Device busy nanoseconds before time(s) ``t``."""
        t = np.asarray(t, np.float64)
        i = np.searchsorted(self._starts, t, side="right")
        inside = np.clip(t - self._starts[np.maximum(i - 1, 0)], 0,
                         self._ends[np.maximum(i - 1, 0)] -
                         self._starts[np.maximum(i - 1, 0)])
        return self._cum[np.maximum(i - 1, 0)] + np.where(i > 0, inside,
                                                          0.0)

    def busy_in(self, start, end) -> np.ndarray:
        return self.busy_before(end) - self.busy_before(start)

    def span_times(self, name: str) -> tuple[np.ndarray, np.ndarray]:
        """``(duration_ns, device_busy_ns)`` of each span called
        ``name`` inside the window."""
        lo, hi = self.window_ns
        inside = [(a, b) for a, b, n in self.spans
                  if n == name and a >= lo and b <= hi]
        s = np.array([a for a, _ in inside], float)
        e = np.array([b for _, b in inside], float)
        return e - s, self.busy_in(s, e)

    def busy_s(self) -> float:
        a, b = self.window_ns
        return float(self.busy_in(a, b)) / 1e9

    def window_s(self) -> float:
        a, b = self.window_ns
        return (b - a) / 1e9

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` operation keys with most device seconds inside the
        window. Only leaf operations count: a ``while`` or ``cond`` that
        holds other operations would count their time twice."""
        a, b = self.window_ns
        tot: dict = collections.defaultdict(float)
        ops = sorted(self.ops)
        for i, (s, e, key) in enumerate(ops):
            parent = i + 1 < len(ops) and ops[i + 1][0] < e and \
                ops[i + 1][1] <= e
            if e > a and s < b and not parent:
                tot[key] += (min(e, b) - max(s, a)) / 1e9
        return sorted(([k_, v] for k_, v in tot.items()),
                      key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Device idle seconds inside the window, by the harness span
        the host was in (``between_spans`` for the driver's own loop)."""
        a, b = self.window_ns
        by: dict = collections.defaultdict(float)
        for name in {n for _, _, n in self.spans} - {"window"}:
            dur, busy = self.span_times(name)
            by[name] += float(np.sum(dur - busy)) / 1e9
        idle = (b - a - float(self.busy_in(a, b))) / 1e9
        by["between_spans"] = idle - sum(by.values())
        return sorted(([n, v] for n, v in by.items()),
                      key=lambda kv: -kv[1])[:k]


def _stat(ev, name):
    for key, val in ev.stats:
        if key == name:
            return val
    return None


def _op_name(ev) -> str:
    """The op's name, e.g. ``fusion.12``: on a TPU the event's name is the
    whole HLO instruction."""
    return str(_stat(ev, "hlo_op") or ev.name.split(" = ")[0].lstrip("%"))


def load(trace_dir) -> Trace | None:
    """The newest trace under ``trace_dir``; None where it holds no
    device operation or no harness span (e.g. a CPU rehearsal)."""
    import jax

    paths = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=lambda p: p.stat().st_mtime)
    pd = jax.profiler.ProfileData.from_file(str(path))
    ops, spans, modules = [], [], []
    device_plane = None   # a cell holds one chip: the first device
    for plane in pd.planes:
        device = bool(_DEVICE_PLANE.match(plane.name))
        if device and device_plane not in (None, plane.name):
            continue
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                device_plane = plane.name
                ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                            _op_name(ev)) for ev in line.events)
            elif device and line.name == "XLA Modules":
                modules.extend((ev.start_ns, ev.name.split("(")[0])
                               for ev in line.events)
            elif not device and plane.name.startswith("/host:"):
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events
                             if ev.name in SPANS)
    if not ops or not spans:
        return None
    return Trace(_by_module(ops, modules), spans)


def _by_module(ops: list, modules: list) -> list:
    """Prefix each op with the program that ran it (the ``XLA Modules``
    event it falls in): op names repeat across programs."""
    if not modules:
        return ops
    modules.sort()
    starts = np.array([s for s, _ in modules], np.float64)
    at = np.searchsorted(starts, [s for s, _, _ in ops], side="right") - 1
    return [(s, e, f"{modules[i][1]}:{k}" if i >= 0 else k)
            for (s, e, k), i in zip(ops, at)]
