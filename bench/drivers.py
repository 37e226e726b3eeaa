"""Load drivers: how queries reach the system under test, and the
accounting of when each was due and when it was answered.

All times are seconds from the window's start on the driver's clock,
which a test replaces with a fake one. A query's latency runs from when
it was due (open loop: its scheduled arrival; closed loop: when its
client sent it) to the end of the call that answered it.

* ``solo``: one call at a time, back to back, started while the window
  is open; every started call runs to its end and counts.
* ``closed``: ``clients`` callers, each sending its next query as soon
  as its previous answer comes back, while the window is open.
* ``open``: queries due at fixed times whatever the system does; the
  generator submits each as soon as it sees it due, and records how late
  it was.

Queries due inside the window that are still running when it closes are
waited for, up to ``DRAIN_S`` past the close; one that never comes is a
failure. The ``window`` span covers the window up to its close (solo:
to the end of its last call), so a trace's readers leave out the drain,
where a closed loop runs with fewer rows than it has clients.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

#: how long past the window's close a due answer is waited for
DRAIN_S = 60.0


@dataclasses.dataclass
class Answer:
    index: int            # position in the query stream
    query: Any
    due: float            # when the query was due (window seconds)
    done: float | None = None   # when its answer came back
    result: Any = None    # what the system returned (None once dropped)
    error: str | None = None
    info: dict = dataclasses.field(default_factory=dict)  # counts read

    @property
    def latency(self) -> float:
        return self.done - self.due


@dataclasses.dataclass
class Window:
    """What one driver run saw."""

    answers: list[Answer]
    seconds: float          # the window's length as planned
    end: float              # when the last counted work ended
    calls: int = 0          # calls into the system (steps or runs)
    lateness: list[float] = dataclasses.field(default_factory=list)

    @property
    def attempted(self) -> int:
        return len(self.answers)

    @property
    def answered(self) -> list[Answer]:
        return [a for a in self.answers if a.done is not None]

    @property
    def failed(self) -> int:
        return sum(a.done is None or a.error is not None
                   for a in self.answers)


def _no_span(name):
    return contextlib.nullcontext()


def run_solo(call: Callable, queries, seconds: float,
             clock: Callable[[], float] = time.perf_counter,
             span=_no_span, on_answer=None) -> Window:
    """``call(query)`` back to back while the window is open."""
    t0 = clock()
    answers = []
    with span("window"):
        for i, q in enumerate(queries):
            now = clock() - t0
            if now >= seconds:
                break
            a = Answer(i, q, now)
            try:
                with span("bfs_call"):
                    a.result = call(q)
            except RuntimeError as e:
                a.error = repr(e)
            a.done = clock() - t0
            answers.append(a)
            if on_answer is not None:
                on_answer(a)
    end = answers[-1].done if answers else clock() - t0
    return Window(answers, seconds, end, calls=len(answers))


def _step(svc, live: dict, win: Window, t0: float, clock, span,
          on_answer) -> tuple[list, float]:
    """One service step and the answers it retired."""
    with span("step"):
        retired = svc.step()
    win.calls += 1
    now = clock() - t0
    with span("retire"):
        done = []
        for h in retired:
            a = live.pop(id(h))
            a.done = now
            a.result = h.result()
            done.append(a)
            if on_answer is not None:
                on_answer(a)
    return done, now


def run_closed(svc, queries, clients: int, seconds: float,
               clock: Callable[[], float] = time.perf_counter,
               span=_no_span, on_answer=None) -> Window:
    """``clients`` closed-loop callers on ``svc`` (``submit``/``step``)."""
    stream = iter(enumerate(queries))
    answers, live = [], {}
    win = Window(answers, seconds, 0.0)
    t0 = clock()

    def send(now):
        i, q = next(stream)
        a = Answer(i, q, now)
        with span("submit"):
            live[id(svc.submit(q))] = a
        answers.append(a)

    with span("window"):
        for _ in range(clients):
            send(0.0)
        while live and clock() - t0 < seconds:
            done, now = _step(svc, live, win, t0, clock, span, on_answer)
            for _ in done:
                if now < seconds:
                    send(now)
    while live and clock() - t0 < seconds + DRAIN_S:
        _step(svc, live, win, t0, clock, span, on_answer)
    win.end = max([a.done for a in win.answered], default=clock() - t0)
    return win


def run_open(svc, queries, due: list[float], seconds: float,
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep,
             span=_no_span, on_answer=None) -> Window:
    """Open-loop arrivals at the window times ``due`` (ascending, all
    inside ``[0, seconds)``)."""
    answers = [Answer(i, q, t) for i, (q, t) in
               enumerate(zip(queries, due))]
    win = Window(answers, seconds, 0.0)
    live = {}
    nxt = 0
    t0 = clock()

    def serve(until):
        nonlocal nxt
        while (nxt < len(answers) or live) and clock() - t0 < until:
            now = clock() - t0
            while nxt < len(answers) and answers[nxt].due <= now:
                a = answers[nxt]
                win.lateness.append(now - a.due)
                with span("submit"):
                    live[id(svc.submit(a.query))] = a
                nxt += 1
            if not live:
                sleep(max(0.0, answers[nxt].due - (clock() - t0)))
                continue
            _step(svc, live, win, t0, clock, span, on_answer)

    with span("window"):
        serve(seconds)
    serve(seconds + DRAIN_S)
    win.end = max([a.done for a in win.answered], default=clock() - t0)
    return win
