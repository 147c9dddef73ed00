"""The program-span helper and the nine readers that use it, on hand-made
recorder events and profiler planes: host-clock readers take the events
of the window before the profiler, the device-trace readers the first
chip's idle time under the program's annotations, clipped to the
tracer's markers."""
import types
from pathlib import Path

import pytest

from bench.harness import program_spans as ps
from bench.harness import spec as spec_lib
from bench.harness import trace

ROOT = Path(__file__).resolve().parents[2]


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def _profile(marks=True):
    dev0 = _plane("/device:TPU:0", [
        ("XLA Ops", [_ev("fusion.1", 150, 100), _ev("fusion.2", 400, 100),
                     _ev("fusion.3", 900, 200)]),
        ("XLA Modules", [_ev("jit_step", 0, 2000)])])
    dev1 = _plane("/device:TPU:1", [("XLA Ops", [_ev("fusion.1", 0, 2000)])])
    rpc = [_ev("alaas.rpc", 110, 750), _ev("alaas.query", 120, 730),
           _ev("bench.select", 105, 800)]
    pick = [_ev("alaas.select.greedy", 200, 400),
            _ev("alaas.select.greedy", 700, 100)]
    fwd = [_ev("alaas.embed.forward", 140, 60)]
    host = _plane("/host:CPU", [
        ("rpc-w1", rpc), ("rpc-w2", pick), ("batcher", fwd),
        ("python", [_ev(trace.MARK_START, 100, 1),
                    _ev(trace.MARK_STOP, 1000, 1)] if marks else [])])
    return types.SimpleNamespace(planes=[host, dev1, dev0])


def test_stretch_clips_to_the_markers_and_reads_the_first_chip():
    st = ps.stretch_of(_profile())
    assert (st.lo, st.hi) == (100, 1000)
    assert st.busy == [(150, 250), (400, 500), (900, 1000)]
    assert {n for _, _, n in st.spans} == {
        "alaas.rpc", "alaas.query", "alaas.select.greedy",
        "alaas.embed.forward"}
    assert ps.stretch_of(_profile(marks=False)) is None


def test_idle_share_under_a_span():
    st = ps.stretch_of(_profile())
    # greedy covers [200, 600) and [700, 800): 500 ns, busy 50 + 100
    assert st.idle_share_under("alaas.select.greedy") == pytest.approx(0.7)
    # forward [140, 200): idle [140, 150)
    assert st.idle_share_under("alaas.embed.forward") == pytest.approx(
        10 / 60)
    assert st.idle_share_under("alaas.push") is None


def test_idle_by_innermost_span():
    idle = ps.stretch_of(_profile()).idle_by_span()
    # 600 ns idle in [100, 1000): the innermost open span names each piece
    assert idle == {
        "other": pytest.approx(50e-9),
        "alaas.rpc": pytest.approx(20e-9),
        "alaas.embed.forward": pytest.approx(10e-9),
        "alaas.query": pytest.approx(170e-9),
        "alaas.select.greedy": pytest.approx(350e-9)}
    assert sum(idle.values()) == pytest.approx(600e-9)


def _event(name, t0, t1, value=None):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, value=value)


EVENTS = [
    _event("alaas.query", 1, 5),                  # before the window
    _event("h2d_bytes", 2, 2, 9e6),
    _event("h2d_bytes", 12, 12, 1e6), _event("compile.s", 20, 20, 0.5),
    _event("alaas.select.greedy", 15, 27), _event("select.picks", 27, 27, 100),
    _event("select.d2h_syncs", 27, 27, 202), _event("alaas.query", 11, 28),
    _event("h2d_bytes", 32, 32, 2e6), _event("compile.s", 40, 40, 0.25),
    _event("alaas.select.greedy", 35, 50), _event("select.picks", 50, 50, 100),
    _event("select.d2h_syncs", 50, 50, 202), _event("alaas.query", 31, 54),
    _event("h2d_bytes", 57, 57, 3e6), _event("compile.s", 70, 70, 1.0),
    _event("alaas.select.greedy", 60, 78), _event("select.picks", 78, 78, 100),
    _event("select.d2h_syncs", 78, 78, 202), _event("alaas.query", 56, 79),
    _event("alaas.embed.batch_wait", 20, 20.1),
    _event("alaas.embed.batch_wait", 41, 41.3),
    _event("alaas.embed.batch_wait", 65, 65.5),   # after the profiler start
    _event("alaas.embed.forward", 21, 21.5),
    _event("alaas.embed.forward", 42, 42.25),
]


def _recorder(events, dropped=0):
    return types.SimpleNamespace(
        events=lambda: list(events),
        snapshot=lambda: {"counters": {"trace.dropped": dropped}})


def _run(cell):
    return types.SimpleNamespace(
        spec=types.SimpleNamespace(root=ROOT, name=cell),
        window=(10.0, 90.0), traced_from=60.0,
        round_log=[(10, 30, 0, 0), (30, 55, 0, 0), (55, 80, 0, 0)],
        push_log=[(20, 1000), (40, 1000), (70, 1000)],
        trace_reduction=object())


def _read(name, run):
    return spec_lib.module(ROOT, "layer_metrics", name).read(run)


@pytest.mark.parametrize("name,cell,value", [
    # two rounds ended before the profiler: greedy 12 + 15 s
    ("pick_loop_s.round", "cifar10_resnet18.round", 13.5),
    # queries 17 + 23 s less their loops
    ("query_prep_s.round", "cifar10_resnet18.round", 6.5),
    ("compile_s.round", "cifar10_resnet18.round", 0.375),
    # the whole window, traced part included
    ("syncs_per_pick.round", "cifar10_resnet18.round", 606 / 300),
    ("h2d_mb_per_query.round", "cifar10_resnet18.round", 2.0),
    # 2,000 rows acknowledged before the profiler
    ("batch_wait_s.push", "cifar10_resnet18.push", 0.2),
    ("forward_s.push", "cifar10_resnet18.push", 0.375),
])
def test_host_clock_readers(monkeypatch, name, cell, value):
    monkeypatch.setattr(ps, "recorder", lambda: _recorder(EVENTS))
    assert _read(name, _run(cell)) == pytest.approx(value)
    # a ring that dropped an event of the window reads nothing
    monkeypatch.setattr(ps, "recorder", lambda: _recorder(EVENTS[2:], 5))
    assert _read(name, _run(cell)) is None
    # drops older than the window do not matter
    monkeypatch.setattr(ps, "recorder", lambda: _recorder(EVENTS, 5))
    assert _read(name, _run(cell)) == pytest.approx(value)
    # a program without the recorder reads nothing, and does not raise
    monkeypatch.setattr(ps, "recorder", lambda: None)
    assert _read(name, _run(cell)) is None


@pytest.mark.parametrize("name,cell,value", [
    ("pick_loop_idle_share.round", "cifar10_resnet18.round", 0.7),
    ("forward_idle_share.push", "cifar10_resnet18.push", 10 / 60),
])
def test_device_trace_readers(monkeypatch, name, cell, value):
    seen = []

    def load(path):
        seen.append(path)
        return _profile()

    monkeypatch.setattr(trace, "load", load)
    run = _run(cell)
    assert _read(name, run) == pytest.approx(value)
    assert seen == [str(ROOT / "bench" / ".cache" / "trace" / cell)]
    # an untraced run reads nothing
    run = _run(cell)
    run.trace_reduction = None
    assert _read(name, run) is None
    # a program without annotations reads nothing
    monkeypatch.setattr(trace, "load", lambda path: _profile(marks=False))
    assert _read(name, _run(cell)) is None
