"""The trace reduction on hand-made planes: busy is the union of op
intervals per chip, idle gaps are named by the benchmark span open at
their middle, kernel time sums a kernel's events, and the markers clip
everything to the traced span."""
import types

import pytest

from bench.harness import readers, trace


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def _profile(marks=()):
    dev0 = _plane("/device:TPU:0", [
        ("XLA Ops", [_ev("fusion.1", 0, 100),
                     _ev("%_greedy_round.1 = (f32[1,49152]) custom-call("
                         "f32[49152,512] %pad.0, f32[1,49152] %b, "
                         "f32[8,512] %pad.4)", 50, 100),
                     _ev("%pad.0 = f32[49152,512]{1,0} pad(f32[49000,512]"
                         "{1,0} %x.1, f32[] %c)", 150, 10),
                     _ev("%pad.4 = f32[8,512]{1,0} pad(f32[1,512]{1,0} %c,"
                         " f32[] %z)", 160, 10),
                     _ev("fusion.2", 400, 100)]),
        ("XLA Modules", [_ev("jit_step", 0, 1000)])])
    dev1 = _plane("/device:TPU:1", [
        ("XLA Ops", [_ev("fusion.1", 0, 300)])])
    host = _plane("/host:CPU", [
        ("python", [_ev("bench.select", 120, 300), _ev("other", 0, 5)]
         + [_ev(name, t, 1) for name, t in marks])])
    return types.SimpleNamespace(planes=[host, dev1, dev0])


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    red = trace.reduce_profile(_profile(), chips=2, window_s=1e-6)
    # chip 0: [0, 170) and [400, 500) -> 270 ns; chip 1: 300 ns
    assert red.busy_s == pytest.approx((270 + 300) / 2 * 1e-9)
    assert red.chips == 2
    one = trace.reduce_profile(_profile(), chips=1, window_s=1e-6)
    assert one.busy_s == pytest.approx(270e-9)


def test_idle_gaps_are_named_by_the_open_span():
    red = trace.reduce_profile(_profile(), chips=1, window_s=1e-6)
    assert red.idle_gaps == [("select", pytest.approx(230e-9))]


def test_kernel_time_and_events():
    red = trace.reduce_profile(_profile(), chips=2, window_s=1e-6,
                               keep_events=("_greedy_round", "%pad"))
    assert red.kernel_s("_greedy_round") == pytest.approx(100e-9)
    (name,) = [n for n in red.op_events if "custom-call(" in n]
    pads = readers.padded_rows(red, 512)
    assert pads == {49152: 49000, 8: 1}
    assert readers.round_shape(name, 512, pads) == (49000, 1)
    assert red.top_ops(2) == [("fusion", pytest.approx(500e-9)),
                              ("_greedy_round", pytest.approx(100e-9))]


def test_markers_clip_the_window_and_the_ops():
    marks = ((trace.MARK_START, 60), (trace.MARK_STOP, 450))
    red = trace.reduce_profile(_profile(marks), chips=2)
    assert red.window_s == pytest.approx(390e-9)
    # chip 0: [60, 170) and [400, 450) -> 160 ns; chip 1: [60, 300) -> 240
    assert red.busy_s == pytest.approx((160 + 240) / 2 * 1e-9)
    # the greedy round [50, 150) straddles the start: its time is clipped
    # and its event is not kept for work accounting
    assert red.kernel_s("_greedy_round") == pytest.approx(90e-9)
    assert not [n for n in red.op_events if "_greedy_round" in n]
    # gaps: [170, 400) under select; the span's edges are idle too
    assert dict(red.idle_gaps) == {"select": pytest.approx(230e-9)}
    assert red.busy_s <= red.window_s


def test_a_trace_without_markers_needs_its_window():
    with pytest.raises(ValueError):
        trace.reduce_profile(_profile(), chips=1)
