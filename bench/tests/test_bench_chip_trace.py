"""The trace reduction on a small trace recorded on a TPU v5e: three
greedy rounds over a (3000, 512) pool under a ``bench.select`` span and one
lc scoring under ``bench.query`` (recorded by running
``repro.kernels.pairwise.ops.greedy_round`` and
``repro.kernels.uncertainty.ops.probs_scores`` under
``jax.profiler.trace``)."""
from pathlib import Path

import pytest

from bench.harness import readers, trace

DATA = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_profile(trace.load(str(DATA.parent)), chips=1,
                                window_s=1.0)


def test_the_chip_was_busy(red):
    assert red.chips == 1
    assert 0 < red.busy_s < 1.0


def test_greedy_round_calls_and_their_shapes(red):
    pads = readers.padded_rows(red, 512)
    calls = [n for n in red.op_events
             if "_greedy_round" in n and "custom-call(" in n]
    assert calls
    assert sum(len(red.op_events[n]) for n in calls) == 3
    for name in calls:
        assert readers.round_shape(name, 512, pads) == (3000, 1)
    assert red.kernel_s("_greedy_round") > 0


def test_idle_gaps_are_named_by_the_benchmark_spans(red):
    names = {name for name, _ in red.idle_gaps}
    assert names <= {"select", "query", "other"}
    assert "select" in names
