"""The trace reduction on a small trace: device busy as a union of
intervals, busy and host gap per span, top operations, idle gaps by the
host span they fall in."""
import pytest

import tracing


def small_trace():
    # two steps: [0, 10] and [20, 32] ns; a submit at [12, 14]
    ops = [(1, 4, "fusion"), (3, 6, "fusion"),      # overlap: busy 1..6
           (8, 9, "copy"), (21, 29, "fusion"), (30, 40, "copy")]
    spans = [(0, 10, "step"), (12, 14, "submit"), (20, 32, "step")]
    return tracing.Trace(ops, spans)


def test_window_and_busy_union():
    tr = small_trace()
    assert tr.window_ns == (0, 32)
    # busy inside [0, 32]: 1..6, 8..9, 21..29, 30..32
    assert tr.busy_in(0, 32) == pytest.approx(5 + 1 + 8 + 2)
    assert tr.busy_s() == pytest.approx(16e-9)
    assert tr.window_s() == pytest.approx(32e-9)


def test_per_span_busy_and_host_gap():
    dur, busy = small_trace().span_times("step")
    assert list(dur) == [10, 12]
    assert list(busy) == pytest.approx([6, 10])


def test_top_ops_clip_to_window_and_skip_parents():
    top = small_trace().top_ops()
    assert top[0] == ["fusion", pytest.approx((3 + 3 + 8) * 1e-9)]
    assert top[1] == ["copy", pytest.approx(3e-9)]
    # a loop holding two ops counts only the ops inside it
    tr = tracing.Trace([(0, 10, "while"), (1, 3, "a"), (4, 9, "b")],
                       [(0, 10, "step")])
    assert dict(tr.top_ops()) == {"a": pytest.approx(2e-9),
                                  "b": pytest.approx(5e-9)}


def test_idle_gaps_by_host_span():
    gaps = dict(small_trace().idle_gaps())
    assert gaps["step"] == pytest.approx((4 + 2) * 1e-9)
    assert gaps["submit"] == pytest.approx(2e-9)
    # window idle 16 ns, 8 of them inside spans
    assert gaps["between_spans"] == pytest.approx(8e-9)


def test_cpu_trace_without_device_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("step"):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    assert tracing.load(tmp_path) is None


def test_recorded_step_on_a_tpu():
    """One recorded service step (16 live PPR rows, TPU v5e): the device
    ran 5.5 ms of the 15.2 ms the host spent in ``step``."""
    import json
    import pathlib

    d = json.loads((pathlib.Path(__file__).parent / "data" /
                    "trace_step.json").read_text())
    tr = tracing.Trace([tuple(o) for o in d["ops"]],
                       [tuple(s) for s in d["spans"]])
    assert tr.window_s() == pytest.approx(0.01520442)
    assert tr.busy_s() == pytest.approx(0.00554687)
    dur, busy = tr.span_times("step")
    assert list(dur) == pytest.approx([15190980])
    assert list(busy) == pytest.approx([5546870])
    top = tr.top_ops(3)
    assert top[0] == ["dynamic_update_slice.130",
                      pytest.approx(0.000455286)]
    assert sum(v for _, v in tr.top_ops(10 ** 6)) <= tr.busy_s() + 1e-12
    gaps = dict(tr.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(tr.window_s() - tr.busy_s())
    assert gaps["step"] == pytest.approx(0.00964411)


def test_window_span_leaves_out_the_drain():
    # the window closes at 15 ns; the step at [20, 32] is the drain's
    ops = [(1, 4, "fusion"), (21, 29, "fusion")]
    spans = [(0, 15, "window"), (0, 10, "step"), (20, 32, "step")]
    tr = tracing.Trace(ops, spans)
    assert tr.window_ns == (0, 15)
    dur, busy = tr.span_times("step")
    assert list(dur) == [10] and list(busy) == [3]
    assert tr.busy_s() == pytest.approx(3e-9)
    gaps = dict(tr.idle_gaps())
    assert "window" not in gaps
    assert gaps["step"] == pytest.approx(7e-9)
    assert gaps["between_spans"] == pytest.approx(5e-9)
