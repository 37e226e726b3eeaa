"""The one traffic generator: a mix's data file in, the query stream
and its arrival times out, all drawn from the run's seed.

Keys of a mix (``bench/traffic/<name>.json``):

* ``driver``: ``solo`` | ``closed`` | ``open`` (see ``drivers.py``),
  with ``clients`` (closed) or ``arrivals`` (open);
* ``queries``: a list of query specs, each ``{"kind": <kind>, ...}``
  with the kind's own parameters and an optional ``share`` (default 1):
  each query of the stream draws its spec by share from the seed;
* ``sources``: ``{"sampler": <name>, ...}`` with the sampler's own
  parameters;
* ``arrivals`` (open): ``{"process": <name>, ...}`` with the process's
  own parameters;
* ``check_sample``: answers of each spec drawn from the seed for the
  comparison (the longest-running one always among them); left out,
  every answer is compared.

A sampler, an arrival process and a query kind are looked up by name in
``bench/sources/<name>.py``, ``bench/arrivals/<name>.py`` and
``bench/queries/<kind>.py``: a new one is a file of its own.

* ``sources/<name>.py``: ``stream(params, inputs, rng)``, an endless
  iterator of source vertices;
* ``arrivals/<name>.py``: ``due(params, seconds, rng)``, ascending due
  times in ``[0, seconds)``;
* ``queries/<kind>.py``: ``make(spec, config)``, a function from a
  source to the program's query; ``check(plain, kept, spec, config,
  inputs)``, the comparison's numbers ``{name: (value, limit)}`` over
  the kept ``(answer, source, result)``; ``control(plain, sources, spec,
  config)``, the same numbers for the reference one step below the
  configuration's precision.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
DRIVERS = ("solo", "closed", "open")


def _path(kind: str, name: str) -> pathlib.Path:
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind} named {name!r} (looked for {path})")
    return path


def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
        _path(kind, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def validate(mix: dict) -> None:
    if mix.get("driver") not in DRIVERS:
        raise ValueError(f"traffic driver must be one of {DRIVERS}")
    specs = mix.get("queries")
    if not specs or any(float(q.get("share", 1)) <= 0 for q in specs):
        raise ValueError("traffic queries must be a list of specs with "
                         "shares > 0")
    for q in specs:
        _path("queries", q["kind"])
    _path("sources", mix.get("sources", {}).get("sampler", ""))
    if mix["driver"] == "closed" and int(mix.get("clients", 0)) < 1:
        raise ValueError("a closed mix needs clients >= 1")
    if mix["driver"] == "open":
        _path("arrivals", mix.get("arrivals", {}).get("process", ""))


class Stream:
    """The mix's queries, from the seed: ``next()`` gives ``(spec index,
    source)``."""

    def __init__(self, mix: dict, inputs, rng: np.random.Generator):
        params = mix["sources"]
        self.sources = plugin("sources", params["sampler"]).stream(
            params, inputs, rng)
        shares = np.array([float(q.get("share", 1)) for q in
                           mix["queries"]])
        self.shares = shares / shares.sum()
        self.rng = rng

    def __iter__(self):
        return self

    def __next__(self) -> tuple[int, int]:
        k = 0 if self.shares.size == 1 else int(
            self.rng.choice(self.shares.size, p=self.shares))
        return k, next(self.sources)


def arrivals(mix: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    params = mix["arrivals"]
    return np.asarray(plugin("arrivals", params["process"]).due(
        params, seconds, rng), np.float64)


class Reservoir:
    """A uniform sample of ``k`` items from a stream, from the seed."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items: list = []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.items[j] = item
