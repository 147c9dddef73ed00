"""retrain_compile_s.round on a recorded ring: the compiles charged to the
server's retrain spans, per round before the profiler started; compiles
under any other span, and after the profiler started, are left out."""
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.harness import program_spans as ps
from bench.harness import spec as spec_lib

ROOT = Path(__file__).resolve().parents[2]
CELL = "cifar10_resnet18.round"


def _compile(parent, t, s):
    return types.SimpleNamespace(name="compile.s", t0=t, t1=t, value=s,
                                 parent=parent)


RING = [
    _compile("alaas.retrain.fit", 5, 9.0),        # before the window
    _compile("alaas.retrain.fit", 12, 1.5),
    _compile("alaas.retrain.eval", 14, 0.25),
    _compile("alaas.query", 20, 4.0),
    _compile("alaas.retrain", 40, 0.5),
    _compile(None, 45, 2.0),
    types.SimpleNamespace(name="h2d_bytes", t0=46, t1=46, value=1e6,
                          parent="alaas.retrain"),
    _compile("alaas.retrain.fit", 70, 8.0),       # after the profiler start
]


def _recorder(events):
    return types.SimpleNamespace(
        events=lambda: list(events),
        snapshot=lambda: {"counters": {"trace.dropped": 0}})


def _run(window, traced_from, round_log):
    return types.SimpleNamespace(
        spec=types.SimpleNamespace(root=ROOT, name=CELL), window=window,
        traced_from=traced_from, round_log=round_log, trace_reduction=None)


def _read(run):
    return spec_lib.module(ROOT, "layer_metrics",
                           "retrain_compile_s.round").read(run)


def test_reads_retrain_compiles_per_untraced_round(monkeypatch):
    run = _run((10.0, 90.0), 60.0,
               [(10, 30, 0, 0), (30, 55, 0, 0), (55, 80, 0, 0)])
    monkeypatch.setattr(ps, "recorder", lambda: _recorder(RING))
    # two rounds ended before the profiler: 1.5 + 0.25 + 0.5 s
    assert _read(run) == pytest.approx(1.125)
    # no round before the profiler reads nothing
    assert _read(_run((10.0, 90.0), 20.0, run.round_log)) is None
    # a program without the recorder reads nothing, and does not raise
    monkeypatch.setattr(ps, "recorder", lambda: None)
    assert _read(run) is None


def test_reads_the_program_recorder():
    from repro.common import telemetry
    shape = (3, 7, 11)          # a program no other test compiles
    t0 = time.perf_counter()
    with telemetry.span("retrain"), telemetry.span("retrain.fit"):
        jax.jit(lambda a: a * 2 + 1)(jnp.ones(shape)).block_until_ready()
    t1 = time.perf_counter()
    with telemetry.span("query"):
        jax.jit(lambda a: a * 3 - 1)(jnp.ones(shape)).block_until_ready()
    t2 = time.perf_counter()
    evs = [e for e in telemetry.events()
           if e.name == "compile.s" and t0 <= e.t1 <= t2]
    fit = sum(e.value for e in evs if e.t1 <= t1)
    assert fit > 0 and sum(e.value for e in evs if e.t1 > t1) > 0
    run = _run((t0, t2 + 1.0), t2 + 1.0, [(t0, t2, 0, 0)])
    assert _read(run) == pytest.approx(fit)
