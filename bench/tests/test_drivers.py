"""The drivers' accounting on a fake clock and a fake service: every
query due in the window is counted, latency runs from its due time, and
answers still running at the close are waited for."""
import pytest

import drivers


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Handle:
    def __init__(self, q):
        self.q = q

    def result(self):
        return f"answer {self.q}"


class FakeService:
    """Each query takes ``work`` steps; a step costs ``dt`` seconds."""

    def __init__(self, clock, work, dt, lose=()):
        self.clock, self.work, self.dt = clock, work, dt
        self.live = {}
        self.inflight = []   # queries in flight at each step
        self.lose = set(lose)   # queries whose answer never comes

    @property
    def pending(self):
        return len(self.live)

    def submit(self, q):
        h = Handle(q)
        self.live[h] = self.work(q)
        return h

    def step(self):
        self.inflight.append(len(self.live))
        self.clock.t += self.dt
        out = []
        for h in list(self.live):
            self.live[h] -= 1
            if self.live[h] <= 0 and h.q not in self.lose:
                out.append(h)
                del self.live[h]
        return out


def test_closed_counts_drained_tail_and_latency_from_send():
    clock = Clock()
    svc = FakeService(clock, work=lambda q: 10, dt=0.1)
    w = drivers.run_closed(svc, iter(range(100)), clients=2, seconds=2.5,
                           clock=clock)
    # each client answers every 1.0 s; sends at 0, 1, 2 (2.0 < 2.5),
    # the queries sent at 2.0 finish at 3.0, after the close, and count
    assert w.attempted == 6 and w.failed == 0
    assert [a.due for a in w.answers] == pytest.approx([0, 0, 1, 1, 2, 2])
    assert all(a.latency == pytest.approx(1.0) for a in w.answers)
    assert w.end == pytest.approx(3.0)
    assert svc.inflight[0] == 2


def test_closed_window_span_holds_only_full_steps():
    """Every step inside the ``window`` span runs with as many queries
    in flight as there are clients; the drain comes after it."""
    import contextlib

    clock = Clock()
    svc = FakeService(clock, work=lambda q: 3 + q % 4, dt=0.1)
    spans = []

    @contextlib.contextmanager
    def span(name):
        start = clock.t
        yield
        spans.append((name, start, clock.t))

    w = drivers.run_closed(svc, iter(range(1000)), clients=4, seconds=3.0,
                           clock=clock, span=span)
    (lo, hi), = [(a, b) for n, a, b in spans if n == "window"]
    steps = [(a, b) for n, a, b in spans if n == "step"]
    inside = [i for i, (a, b) in enumerate(steps) if a >= lo and b <= hi]
    assert inside == list(range(len(inside))) and len(inside) > 10
    assert all(svc.inflight[i] == 4 for i in inside)
    assert len(steps) > len(inside)          # the drain ran after it
    assert w.failed == 0


def test_open_latency_from_due_time_including_queue_and_lateness():
    clock = Clock()
    svc = FakeService(clock, work=lambda q: 3, dt=0.5)
    due = [0.0, 0.2, 4.0]
    w = drivers.run_open(svc, iter("abc"), due, seconds=5.0, clock=clock,
                         sleep=clock.sleep)
    # "a" submitted at 0 (on time), "b" seen due only after the first
    # step (0.5 s, 0.3 s late), "c" after an idle sleep to 4.0
    assert w.attempted == 3 and w.failed == 0
    assert w.lateness == pytest.approx([0.0, 0.3, 0.0])
    lat = {a.query: a.latency for a in w.answers}
    assert lat["a"] == pytest.approx(1.5)
    assert lat["b"] == pytest.approx(2.0 - 0.2)
    assert lat["c"] == pytest.approx(1.5)


def test_answer_that_never_comes_is_failed(monkeypatch):
    monkeypatch.setattr(drivers, "DRAIN_S", 2.0)
    clock = Clock()
    svc = FakeService(clock, work=lambda q: 2, dt=0.5, lose={1})
    w = drivers.run_open(svc, iter(range(3)), [0.0, 0.1, 0.2],
                         seconds=1.0, clock=clock, sleep=clock.sleep)
    assert w.attempted == 3
    assert w.failed == 1
    assert [a.index for a in w.answers if a.done is None] == [1]


def test_solo_runs_every_started_call_to_its_end():
    clock = Clock()

    def call(q):
        clock.t += 0.4
        return q

    w = drivers.run_solo(call, iter(range(10)), seconds=1.0, clock=clock)
    assert [a.due for a in w.answers] == pytest.approx([0.0, 0.4, 0.8])
    assert w.end == pytest.approx(1.2)
