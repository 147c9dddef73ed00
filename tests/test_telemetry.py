"""The in-program recorder (repro.common.telemetry): span nesting and self
time, parent and request id across the threads the server hands work to,
the ring's bound, the off switch, compile attribution, upload counts and
no update lost between threads."""
import collections
import concurrent.futures as cf
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import telemetry
from repro.core.selection import replica_map
from repro.service.batcher import DynamicBatcher
from repro.service.pipeline import Stage, StagePipeline


def _since(t0, name=None):
    return [e for e in telemetry.events()
            if e.t1 >= t0 and (name is None or e.name == name)]


def _delta(before, after, key):
    return after["counters"].get(key, 0) - before["counters"].get(key, 0)


def test_nesting_parent_request_and_self_time():
    s0 = telemetry.snapshot()["spans"]
    t0 = time.perf_counter()
    with telemetry.span("t.outer", request_id="r-1"):
        time.sleep(0.01)
        with telemetry.span("t.inner"):
            time.sleep(0.02)
    s1 = telemetry.snapshot()["spans"]
    (outer,) = _since(t0, "alaas.t.outer")
    (inner,) = _since(t0, "alaas.t.inner")
    assert outer.parent is None and inner.parent == "alaas.t.outer"
    assert outer.request_id == inner.request_id == "r-1"
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1

    def grew(name, key):
        return s1[name][key] - s0.get(name, {}).get(key, 0)

    assert grew("alaas.t.outer", "count") == 1
    assert grew("alaas.t.outer", "total_s") == pytest.approx(
        outer.t1 - outer.t0)
    # self time: the outer span less its child on the same thread
    assert grew("alaas.t.outer", "self_s") == pytest.approx(
        (outer.t1 - outer.t0) - (inner.t1 - inner.t0))
    assert grew("alaas.t.inner", "self_s") == pytest.approx(
        inner.t1 - inner.t0)


def test_span_records_when_its_body_raises():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with telemetry.span("t.raises"):
            raise ValueError("boom")
    assert len(_since(t0, "alaas.t.raises")) == 1
    assert telemetry.context() is None


def test_context_rides_the_pipeline_threads():
    def stage(name):
        def fn(x):
            with telemetry.span(f"t.stage_{name}"):
                return x + 1
        return Stage(name, fn)

    t0 = time.perf_counter()
    with telemetry.span("t.push", request_id="r-pipe"):
        out = StagePipeline([stage("a"), stage("b")]).run([1, 2, 3])
    assert out == [3, 4, 5]
    evs = _since(t0, "alaas.t.stage_a") + _since(t0, "alaas.t.stage_b")
    assert len(evs) == 6
    (push,) = _since(t0, "alaas.t.push")
    assert {e.parent for e in evs} == {"alaas.t.push"}
    assert {e.request_id for e in evs} == {"r-pipe"}
    assert push.thread not in {e.thread for e in evs}


def test_context_rides_the_batcher_thread():
    def fn(stacked, n):
        with telemetry.span("t.forward"):
            return [stacked[i] for i in range(n)]

    b = DynamicBatcher(fn, max_batch=8, timeout_s=0.01, pad_to_max=True)
    before = telemetry.snapshot()
    t0 = time.perf_counter()
    try:
        with telemetry.span("t.infer", request_id="r-batch"):
            out = b.score([np.full(2, i, np.float32) for i in range(3)])
    finally:
        b.close()
    after = telemetry.snapshot()
    assert [o[0] for o in out] == [0, 1, 2]
    (fwd,) = _since(t0, "alaas.t.forward")
    (wait,) = _since(t0, "alaas.embed.batch_wait")
    assert fwd.parent == wait.parent == "alaas.t.infer"
    assert fwd.request_id == wait.request_id == "r-batch"
    # three rows short of a batch: the batcher waits out its timeout
    assert wait.t1 - wait.t0 >= 0.009
    assert _delta(before, after, "embed.batches") == 1
    assert _delta(before, after, "embed.rows_valid") == 3
    assert _delta(before, after, "embed.rows_computed") == 8


def test_context_rides_the_shard_pool():
    def fn(i):
        with telemetry.span("t.shard"):
            return i * 2

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(3) as ex, \
            telemetry.span("t.query", request_id="r-shard"):
        assert replica_map(fn, range(3), ex) == [0, 2, 4]
    evs = _since(t0, "alaas.t.shard")
    assert len(evs) == 3
    assert {e.parent for e in evs} == {"alaas.t.query"}
    assert {e.request_id for e in evs} == {"r-shard"}


def test_ring_is_bounded_and_counts_drops(monkeypatch):
    monkeypatch.setattr(telemetry, "_ring", collections.deque(maxlen=4))
    before = telemetry.snapshot()
    for i in range(10):
        with telemetry.span(f"t.ring{i}"):
            pass
    after = telemetry.snapshot()
    assert [e.name for e in telemetry.events()] == [
        f"alaas.t.ring{i}" for i in range(6, 10)]
    assert _delta(before, after, "trace.dropped") == 6
    assert after["ring"]["events"] == 4


def test_disabled_recorder_records_nothing():
    before = telemetry.snapshot()
    n0 = len(telemetry.events())
    telemetry.set_enabled(False)
    try:
        with telemetry.span("t.off", request_id="r-off"):
            assert telemetry.context() is None
            telemetry.count("t.off_counter", 5)
            telemetry.h2d(np.zeros(4, np.float32))
    finally:
        telemetry.set_enabled(True)
    after = telemetry.snapshot()
    assert len(telemetry.events()) == n0
    assert "alaas.t.off" not in after["spans"]
    assert after["counters"] == before["counters"]


def test_compiles_are_charged_to_the_open_span():
    before = telemetry.snapshot()
    t0 = time.perf_counter()
    salt = time.perf_counter_ns() % 1000003     # a program never compiled
    with telemetry.span("t.compiling"):
        jax.jit(lambda x: x * 3.0 + salt)(jnp.ones(5)).block_until_ready()
    after = telemetry.snapshot()
    assert _delta(before, after, "compile.count") >= 1
    assert _delta(before, after, "compile.s") > 0
    got = after["compiles"]["alaas.t.compiling"]
    assert got["count"] >= 1 and got["s"] > 0
    evs = _since(t0, "compile.s")
    assert evs and {e.parent for e in evs} == {"alaas.t.compiling"}


def test_h2d_counts_host_uploads_only():
    before = telemetry.snapshot()
    dev = telemetry.h2d(np.zeros((3, 5), np.float64), jnp.float32)
    assert dev.dtype == jnp.float32
    telemetry.h2d(dev)                      # already on the device
    after = telemetry.snapshot()
    assert _delta(before, after, "h2d_bytes") == 3 * 5 * 4


def test_concurrent_counts_and_spans_lose_no_update():
    threads, per = (os.cpu_count() or 2) + 2, 200   # more threads than cores
    before = telemetry.snapshot()

    def work():
        for _ in range(per):
            with telemetry.span("t.stress"):
                telemetry.count("t.stress_counter")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    after = telemetry.snapshot()
    assert _delta(before, after, "t.stress_counter") == threads * per
    assert (after["spans"]["alaas.t.stress"]["count"]
            - before["spans"].get("alaas.t.stress", {}).get("count", 0)
            ) == threads * per
