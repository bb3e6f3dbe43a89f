"""The trace reduction on small hand-made traces."""
import pytest

from bench.trace_reduce import (Interval, Trace, module_name, op_name,
                                reduce, union)


def _trace():
    ops = [
        # two ops overlapping by 2 ns: busy counts the overlap once
        Interval(10, 20, "fusion.1", "worker_program"),
        Interval(18, 25, "copy.2", "worker_program"),
        Interval(40, 70, "custom-call.3", "master_program"),
        # outside the window: not counted
        Interval(200, 300, "fusion.9", "master_program"),
    ]
    modules = [Interval(10, 25, "worker_program"),
               Interval(40, 70, "master_program"),
               Interval(200, 300, "master_program")]
    spans = [
        Interval(5, 50, "bench/call"),
        Interval(50, 100, "bench/call"),
        Interval(6, 12, "worker/launch"),
        Interval(26, 45, "master/decode"),
    ]
    return Trace(ops=ops, modules=modules, spans=spans)


def test_busy_is_a_union_of_intervals():
    s = reduce(_trace())
    assert s.window_s == pytest.approx(95e-9)
    assert s.busy_s == pytest.approx((15 + 30) * 1e-9)
    assert s.idle_share == pytest.approx(1 - 45 / 95)
    assert union([Interval(0, 5, "a"), Interval(1, 3, "b"),
                  Interval(5, 6, "c"), Interval(8, 9, "d")]) == [(0, 6),
                                                                 (8, 9)]


def test_module_time_per_call():
    s = reduce(_trace())
    assert s.n_calls == 2
    assert s.module_s["worker_program"] == pytest.approx(15e-9)
    assert s.module_s["master_program"] == pytest.approx(30e-9)
    assert s.module_ms_per_call("master_program") == pytest.approx(
        1e3 * 15e-9)
    assert s.module_ms_per_call("fold_program") is None
    assert s.top_ops[0][0] == "master_program/custom-call.3"


def test_idle_gaps_are_labelled_by_the_innermost_open_span():
    s = reduce(_trace())
    idle = dict(s.idle_by_label)
    # gaps 5..10, 25..40 and 70..100; worker/launch is open over 6..10,
    # master/decode over 26..40, bench/call alone over the rest
    assert idle["bench/call"] == pytest.approx((1 + 1 + 30) * 1e-9)
    assert idle["worker/launch"] == pytest.approx(4e-9)
    assert idle["master/decode"] == pytest.approx(14e-9)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_no_call_span_is_an_error():
    with pytest.raises(ValueError):
        reduce(Trace(ops=[], modules=[], spans=[]))


@pytest.mark.parametrize("raw, name", [
    ("jit_master_program(17)", "master_program"),
    ("jit_worker_program", "worker_program"),
    ("fold_program.3", "fold_program"),
])
def test_module_name(raw, name):
    assert module_name(raw) == name


def test_op_name():
    assert op_name("%fusion.2 = f32[8]{0} fusion(f32[8] %x), kind=kLoop"
                   ) == "fusion.2"
    assert op_name("copy-done") == "copy-done"
