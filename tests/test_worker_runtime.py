"""Shard-worker runtime (distributed.worker): supervision, failure
injection, kill-recovery bit-identity, and the fault_tolerance bugfixes.

The fault matrix is the tentpole contract: a worker killed during EMBED,
PROPOSE, or INGEST-DRAIN is detected, its shard recovers (columns reset,
re-embedded from raw + content keys on retry), and the session's
selections stay bit-identical to a clean run — with the restart and
recovery counters surfaced through ``stats()``.
"""
import time

import numpy as np
import pytest

from repro.distributed.elastic import largest_mesh_shape
from repro.distributed.fault_tolerance import (SimulatedFailure,
                                               StragglerMonitor, supervise)
from repro.distributed.worker import (PhaseFailureInjector, ShardWorkerPool,
                                      WorkerDeath)
from repro.service.config import ALServiceConfig
from repro.service.server import ALServer


# ---------------------------------------------------------------------------
# pool-level supervision (no AL service involved)
# ---------------------------------------------------------------------------

def test_map_runs_items_on_lanes_and_counts_tasks():
    pool = ShardWorkerPool(3, backoff_s=0.0)
    try:
        out = pool.map(lambda x: x * 2, [1, 2, 3])
        assert out == [2, 4, 6]
        st = pool.stats()
        assert st["tasks"] == 3 and st["restarts"] == 0
        assert st["lanes"] == 3
    finally:
        pool.shutdown()


def test_injected_death_restarts_lane_and_retries():
    inj = PhaseFailureInjector({"embed": [1]})
    pool = ShardWorkerPool(2, injector=inj, backoff_s=0.0)
    deaths = []
    try:
        ex = pool.scoped("embed", on_death=deaths.append)
        assert ex.map(lambda x: x + 10, [1, 2]) == [11, 12]
        st = pool.stats()
        assert st["restarts"] == 1
        assert st["generations"] == [0, 1]     # item 1 rode lane 1
        assert deaths == [1]                   # recovery hook saw the shard
        assert inj.fired == [("embed", 1)]
    finally:
        pool.shutdown()


def test_injector_fires_once_per_scheduled_index():
    inj = PhaseFailureInjector({"p": [0]})
    with pytest.raises(SimulatedFailure):
        inj.maybe_fail("p")
    inj.maybe_fail("p")            # index 1: clean
    inj.maybe_fail("q")            # other phases: never scheduled


def test_death_every_attempt_exhausts_bounded_retries():
    # attempts consume phase indices 0,1,2 — all scheduled to die
    inj = PhaseFailureInjector({"embed": [0, 1, 2]})
    pool = ShardWorkerPool(1, injector=inj, max_retries=2, backoff_s=0.0)
    try:
        with pytest.raises(WorkerDeath, match="after 3 attempts"):
            pool.scoped("embed").map(lambda x: x, [0])
    finally:
        pool.shutdown()


def test_hung_task_detected_by_timeout_and_retried():
    calls = []
    pool = ShardWorkerPool(1, timeout_s=0.2, backoff_s=0.0)

    def fn(x):
        calls.append(x)
        if len(calls) == 1:
            time.sleep(1.2)        # hang well past the timeout
        return x + 1

    try:
        assert pool.map(fn, [5]) == [6]
        st = pool.stats()
        assert st["restarts"] == 1 and st["generations"] == [1]
    finally:
        pool.shutdown()


def test_task_raising_timeouterror_propagates_not_retried():
    # a task's own TimeoutError must not be mistaken for a hang
    def fn(x):
        raise TimeoutError("from the task itself")

    pool = ShardWorkerPool(1, backoff_s=0.0)
    try:
        with pytest.raises(TimeoutError, match="from the task itself"):
            pool.map(fn, [0])
        assert pool.stats()["restarts"] == 0
    finally:
        pool.shutdown()


def test_kill_marks_lane_dead_probe_detects_next_task_recovers():
    pool = ShardWorkerPool(2, backoff_s=0.0)
    try:
        pool.kill(0)
        assert pool.probe() == [False, True]
        deaths = []
        out = pool.scoped("shard", on_death=deaths.append).map(
            lambda x: x, ["a", "b"])
        assert out == ["a", "b"]
        assert deaths == [0]
        assert pool.probe() == [True, True]    # restarted lane is live
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# fault_tolerance bugfixes (satellites)
# ---------------------------------------------------------------------------

def test_straggler_outlier_during_warmup_does_not_poison_ema():
    mon = StragglerMonitor(threshold=2.5, alpha=0.5, warmup=5)
    mon.observe(0, 1.0)
    mon.observe(1, 1.0)
    assert mon.observe(2, 100.0) is None      # warmup: no event...
    assert mon.ema == pytest.approx(1.0)      # ...and no EMA poisoning
    for s in range(3, 8):
        mon.observe(s, 1.0)
    ev = mon.observe(8, 100.0)                # past warmup: real event
    assert ev is not None and ev.ratio > 2.5
    assert mon.ema == pytest.approx(1.0)      # outlier still never folds
    assert len(mon.events) == 1


def test_supervise_reports_straggler_events_from_monitor():
    mon = StragglerMonitor(threshold=2.0, warmup=1)
    state = {"step": 0}

    def train_round(start):
        for s in range(start, 4):
            mon.observe(s, 10.0 if s == 3 else 0.01)
            state["step"] = s + 1
        return 4

    rep = supervise(train_round, total_steps=4,
                    latest_step=lambda: state["step"], monitor=mon)
    assert rep.straggler_events == len(mon.events) == 1
    assert rep.restarts == 0
    # and without a monitor the field is an honest 0, not a dead field
    state["step"] = 0
    rep0 = supervise(train_round, total_steps=4,
                     latest_step=lambda: state["step"])
    assert rep0.straggler_events == 0


def test_largest_mesh_shape_validates_inputs():
    with pytest.raises(ValueError, match="model_parallel"):
        largest_mesh_shape(8, model_parallel=0)
    with pytest.raises(ValueError, match="model_parallel"):
        largest_mesh_shape(8, model_parallel=-2)
    with pytest.raises(ValueError, match="n_devices"):
        largest_mesh_shape(0, model_parallel=1)
    assert largest_mesh_shape(8, 4) == (2, 4)
    assert largest_mesh_shape(6, 4) == (2, 3)   # clamped to a divisor
    assert largest_mesh_shape(4, 9) == (1, 4)   # model > n clamps to n


# ---------------------------------------------------------------------------
# fault-injection matrix against the AL service (the tentpole contract)
# ---------------------------------------------------------------------------

def _pool(n=36, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 8, 8, 3)).astype(np.float32)


def _build(injector=None, **cfg_kw):
    cfg = ALServiceConfig(replicas=3, batch_size=8, worker_backoff_s=0.0,
                          **cfg_kw)
    srv = ALServer(config=cfg, failure_injector=injector)
    keys = srv.push_data(list(_pool()))
    srv.label(keys[:6], [0, 1, 0, 1, 0, 1])
    srv.train_and_eval()
    return srv, keys


@pytest.fixture(scope="module")
def clean_selection():
    srv, _ = _build()
    return {s: srv.query(6, strategy=s, rng_seed=7)["keys"]
            for s in ("coreset", "mc")}


@pytest.mark.parametrize("phase", ["embed", "propose"])
def test_kill_during_query_phase_recovers_bit_identical(phase,
                                                        clean_selection):
    inj = PhaseFailureInjector({phase: [0]})
    srv, _ = _build(injector=inj)
    for strat in ("coreset", "mc"):
        got = srv.query(6, strategy=strat, rng_seed=7)["keys"]
        assert got == clean_selection[strat], (
            f"kill during {phase} diverged the {strat} selection")
    st = srv.stats()
    assert inj.fired and st["workers"]["restarts"] >= 1
    assert st["worker_recoveries"] >= 1
    assert st["workers"]["straggler_events"] == len(
        srv.shard_runtime().monitor.events)


def test_kill_during_ingest_drain_loses_no_rows(clean_selection):
    inj = PhaseFailureInjector({"ingest": [0]})
    cfg = ALServiceConfig(replicas=3, batch_size=8, worker_backoff_s=0.0)
    srv = ALServer(config=cfg, failure_injector=inj)
    tickets = [srv.push_data([x], asynchronous=True) for x in _pool()]
    srv.flush()
    uniq = {k for t in tickets for k in t.keys}
    st = srv.stats()
    assert inj.fired == [("ingest", 0)]
    assert st["pool"] == len(uniq), "kill during ingest drain lost rows"
    assert st["workers"]["restarts"] >= 1
    # and the recovered pool still selects exactly like the clean run
    srv.label([t.keys[0] for t in tickets[:6]], [0, 1, 0, 1, 0, 1])
    srv.train_and_eval()
    got = srv.query(6, strategy="coreset", rng_seed=7)["keys"]
    assert got == clean_selection["coreset"]


def test_recovery_reembeds_from_raw_when_cache_evicted(clean_selection):
    # a 1-byte embedding cache evicts everything: the reset shard can only
    # rebuild through the raw copies + content keys — the data layer's
    # re-embed path — and must still match the clean selection
    inj = PhaseFailureInjector({"embed": [0]})
    srv, _ = _build(injector=inj, cache_bytes=1)
    got = srv.query(6, strategy="coreset", rng_seed=7)["keys"]
    assert got == clean_selection["coreset"]
    assert srv.stats()["worker_recoveries"] >= 1
