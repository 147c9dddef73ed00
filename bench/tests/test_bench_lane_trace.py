"""The per-chip pick-loop helper and the three readers of the four-chip
round, on hand-made profiler planes and recorder events: merge and kernel
time per chip under the loop's annotations, clipped to the tracer's
markers, and nothing read where the program has no merge, no loop or no
counter."""
import types
from pathlib import Path

import pytest

from bench.harness import lane_trace
from bench.harness import program_spans as ps
from bench.harness import spec as spec_lib
from bench.harness import trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "cifar10_resnet18_x4.round"
KERNEL = ("%alaas._greedy_round.4 = (f32[1,12544]{1,0:T(1,128)S(1)}, "
          "f32[1,6272]{1,0}) custom-call(f32[12544,512]{1,0} %x, "
          "f32[1,12544]{1,0} %m)")
MERGE = ("%all-reduce.5 = s32[2056]{0:T(1024)S(1)} "
         "all-reduce(s32[2056]{0:T(1024)S(1)} %fusion.3), channel_id=2")
AFTER = "%reshape.170 = s32[4,514]{1,0} reshape(s32[2056]{0} %all-reduce.5)"


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def _plane(name, events):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name="XLA Ops", events=events)])


def _profile(chips=4, merge=True, loops=True):
    """Chip i runs a loop op over [200, 800): kernel calls of 100 + 10 i
    ns at 250 and 400, a merge of 50 ns after each; an op outside the
    loop at 900. The loop's annotations cover [200, 600) and [700, 800);
    the markers [100, 1000)."""
    planes = []
    for i in range(chips):
        k = 100 + 10 * i
        evs = [_ev("%while.1 = (s32[]) while(s32[] %t)", 200, 600),
               _ev(KERNEL, 250, k), _ev(KERNEL, 400, k),
               _ev(AFTER, 950, 10), _ev(KERNEL, 900, 50)]
        if merge:
            evs += [_ev(MERGE, 250 + k, 50), _ev(MERGE, 400 + k, 50)]
        planes.append(_plane(f"/device:TPU:{i}", evs))
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="w", events=[
            _ev("alaas.select.greedy", 200, 400),
            _ev("alaas.select.greedy", 700, 100)] if loops else []),
        types.SimpleNamespace(name="python", events=[
            _ev(trace.MARK_START, 100, 1), _ev(trace.MARK_STOP, 1000, 1)])])
    return types.SimpleNamespace(planes=[host] + planes[::-1])


@pytest.mark.parametrize("op,code,collective", [
    (KERNEL, "custom-call", False), (MERGE, "all-reduce", True),
    (AFTER, "reshape", False),
    ("%all-gather.7 = f32[4,4]{1,0} all-gather(f32[1,4]{1,0} %m)",
     "all-gather", True),
    ("%all-reduce-start = (s32[8]{0}, s32[8]{0}) all-reduce-start(s32[8] %a)",
     "all-reduce-start", True),
])
def test_opcode_and_collectives(op, code, collective):
    assert lane_trace.opcode(op) == code
    assert lane_trace.is_collective(op) == collective


def test_chips_read_under_the_loop():
    got = lane_trace.chips(_profile(), 4)
    assert len(got) == 4
    for i, c in enumerate(got):
        k = 100 + 10 * i
        # the loop's own op covers the annotations: busy 400 + 100 ns
        assert c["busy_s"] == pytest.approx(500e-9)
        # both merges end by 580, inside [200, 600)
        assert c["merge_s"] == pytest.approx(100e-9)
        assert c["kernel_s"] == pytest.approx(2 * k * 1e-9)
    assert lane_trace.chips(_profile(loops=False), 4) is None
    assert lane_trace.chips(_profile(), 2) == got[:2]


def _run():
    return types.SimpleNamespace(
        spec=types.SimpleNamespace(root=ROOT, name=CELL),
        devices=[0, 1, 2, 3], trace_reduction=object(),
        window=(10.0, 90.0), traced_from=60.0)


def _read(name, run):
    return spec_lib.module(ROOT, "layer_metrics", name).read(run)


def test_merge_share_and_lane_skew(monkeypatch):
    seen = []

    def load(path):
        seen.append(path)
        return _profile()

    monkeypatch.setattr(trace, "load", load)
    run = _run()
    assert _read("merge_share.round_x4", run) == pytest.approx(0.2)
    kernel = [200 + 20 * i for i in range(4)]
    assert _read("lane_skew.round_x4", run) == pytest.approx(
        max(kernel) / (sum(kernel) / 4))
    assert seen == [str(ROOT / "bench" / ".cache" / "trace" / CELL)]
    # a loop on one chip of four: no merge, all the kernel time on chip 0
    monkeypatch.setattr(trace, "load", lambda p: types.SimpleNamespace(
        planes=_profile(merge=False).planes[:2] + [
            _plane(f"/device:TPU:{i}", []) for i in (1, 2, 3)]))
    assert _read("merge_share.round_x4", _run()) is None
    assert _read("lane_skew.round_x4", _run()) == pytest.approx(4.0)
    # an untraced run reads nothing
    run = _run()
    run.trace_reduction = None
    assert _read("merge_share.round_x4", run) is None
    assert _read("lane_skew.round_x4", run) is None


def _event(name, t0, t1, value=None):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, value=value)


def _recorder(events):
    return types.SimpleNamespace(
        events=lambda: list(events),
        snapshot=lambda: {"counters": {"trace.dropped": 0}})


def test_loop_devices(monkeypatch):
    evs = [_event("alaas.query", 11, 20), _event("alaas.query", 31, 40),
           _event("select.loop_devices", 19, 19, 4),
           _event("select.loop_devices", 39, 39, 4)]
    monkeypatch.setattr(ps, "recorder", lambda: _recorder(evs))
    assert _read("loop_devices.round_x4", _run()) == pytest.approx(4.0)
    # a program that does not count the loop's devices reads nothing
    monkeypatch.setattr(ps, "recorder", lambda: _recorder(evs[:2]))
    assert _read("loop_devices.round_x4", _run()) is None
    monkeypatch.setattr(ps, "recorder", lambda: None)
    assert _read("loop_devices.round_x4", _run()) is None
