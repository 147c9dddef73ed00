"""Service layer: pipeline overlap, cache, batcher, config, server, TCP."""
import threading
import time

import numpy as np
import pytest

from repro.common import telemetry
from repro.data.synthetic import image_pool
from repro.service.batcher import DynamicBatcher, bucket_size
from repro.service.cache import EmbeddingCache, content_key
from repro.service.client import ALClient, serve_tcp
from repro.service.config import ALServiceConfig, parse_yaml
from repro.service.pipeline import Stage, StagePipeline
from repro.service.server import ALServer


# --------------------------------------------------------------- pipeline --
def test_pipeline_overlap_beats_serial():
    """3 stages x 10 items x 10ms: serial ~300ms, pipelined ~>=120ms."""
    def mk():
        return [Stage(n, lambda x, n=n: (time.sleep(0.01), x)[1])
                for n in ("a", "b", "c")]

    items = list(range(10))
    p1 = StagePipeline(mk())
    t0 = time.perf_counter()
    out1 = p1.run_serial(items)
    t_serial = time.perf_counter() - t0
    p2 = StagePipeline(mk())
    t0 = time.perf_counter()
    out2 = p2.run(items)
    t_pipe = time.perf_counter() - t0
    assert out1 == items and out2 == items
    assert t_pipe < t_serial * 0.75, (t_pipe, t_serial)


def test_pipeline_preserves_order_and_stats():
    sq = Stage("sq", lambda x: x * x)
    p = StagePipeline([sq])
    assert p.run(list(range(20))) == [x * x for x in range(20)]
    assert p.stats()[0]["items"] == 20


def test_pipeline_propagates_errors():
    def boom(x):
        raise ValueError("boom")
    p = StagePipeline([Stage("b", boom)])
    with pytest.raises(ValueError):
        p.run([1])


def test_pipeline_midstage_error_no_deadlock():
    """A mid-stage exception with bounded queues and many queued items:
    upstream stages must be torn down (not left blocked on a full queue)
    and run() must raise the original error instead of deadlocking."""
    def mid(x):
        if x == 10:
            raise ValueError("boom@10")
        return x

    stages = [Stage("a", lambda x: x), Stage("b", mid),
              Stage("c", lambda x: x)]
    p = StagePipeline(stages, max_queue=2)
    result = {}

    def drive():
        try:
            p.run(list(range(200)))
            result["outcome"] = "returned"
        except ValueError as e:
            result["outcome"] = f"raised:{e}"
        except BaseException as e:  # pragma: no cover - diagnostic
            result["outcome"] = f"other:{e!r}"

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "pipeline deadlocked after mid-stage exception"
    assert result["outcome"] == "raised:boom@10"


def test_pipeline_feeder_error_no_deadlock():
    """The items ITERABLE raising mid-iteration (lazy loader hits a bad
    record) must abort the pipeline like a stage error — not strand the
    workers waiting on an input queue that will never see a sentinel."""
    def gen():
        for i in range(50):
            if i == 7:
                raise OSError("bad record")
            yield i

    p = StagePipeline([Stage("a", lambda x: x)], max_queue=2)
    result = {}

    def drive():
        try:
            p.run(gen())
            result["outcome"] = "returned"
        except OSError:
            result["outcome"] = "raised"

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "pipeline deadlocked after feeder exception"
    assert result["outcome"] == "raised"


def test_pipeline_error_in_last_stage_no_deadlock():
    """Same, with the FAILING stage at the end: the feeder and both live
    stages are parked on bounded queues when the error hits."""
    def last(x):
        time.sleep(0.001)
        if x == 5:
            raise RuntimeError("tail")
        return x

    p = StagePipeline([Stage("a", lambda x: x), Stage("z", last)],
                      max_queue=1)
    done = []

    def drive():
        with pytest.raises(RuntimeError):
            p.run(iter(range(500)))
        done.append(True)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and done


# ------------------------------------------------------------------ cache --
def test_cache_hit_miss_lru():
    c = EmbeddingCache(max_bytes=10 * 8 * 4)      # ~10 float32[8]
    arrs = {f"k{i}": np.full(8, i, np.float32) for i in range(15)}
    for k, v in arrs.items():
        c.put(k, v)
    assert c.stats()["bytes"] <= 10 * 8 * 4
    assert c.get("k14") is not None               # recent survives
    assert c.get("k0") is None                    # evicted (no spill)
    assert c.stats()["misses"] >= 1


def test_cache_spill_roundtrip(tmp_path):
    c = EmbeddingCache(max_bytes=4 * 8 * 4, spill_dir=str(tmp_path))
    for i in range(10):
        c.put(f"k{i}", np.full(8, i, np.float32))
    v = c.get("k0")                               # evicted -> spilled -> back
    assert v is not None and v[0] == 0
    assert c.stats()["spills"] >= 1


def test_cache_spill_runs_outside_lock(tmp_path):
    """Compression + disk writes must never happen while holding the cache
    lock (readers would stall behind every spill)."""
    c = EmbeddingCache(max_bytes=4 * 8 * 4, spill_dir=str(tmp_path))
    lock_held_during_spill = []
    orig = c._spill

    def spy(key, value):
        lock_held_during_spill.append(c._lock.locked())
        orig(key, value)

    c._spill = spy
    for i in range(10):
        c.put(f"k{i}", np.full(8, i, np.float32))
    assert lock_held_during_spill, "expected evictions to spill"
    assert not any(lock_held_during_spill)
    assert c.get("k0") is not None                # spilled entries retrievable


def test_content_key_stability():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    assert content_key(a) == content_key(a.copy())
    assert content_key(a) != content_key(a.T.copy())
    assert content_key(a) != content_key(a.astype(np.float64))


def test_cache_require_raises_clear_keyerror():
    """A no-spill-dir eviction makes get() return None; require() must turn
    that into an actionable KeyError instead of letting np.stack crash."""
    c = EmbeddingCache(max_bytes=2 * 8 * 4)
    for i in range(6):
        c.put(f"k{i}", np.full(8, i, np.float32))
    assert c.get("k0") is None
    with pytest.raises(KeyError, match="evicted .* spill_dir"):
        c.require("k0")
    np.testing.assert_array_equal(c.require("k5"), np.full(8, 5, np.float32))


# ---------------------------------------------------------------- batcher --
def test_bucket_size():
    assert [bucket_size(n, 64) for n in (1, 2, 3, 5, 33, 64, 200)] == \
        [1, 2, 4, 8, 64, 64, 64]


def test_batcher_timeout_flush():
    """Fewer items than max_batch must still flush once timeout_s elapses —
    the batcher may not hold a partial batch waiting for a full one."""
    b = DynamicBatcher(lambda stacked, n: [stacked[i] for i in range(n)],
                       max_batch=64, timeout_s=0.02)
    before = telemetry.snapshot()["counters"]
    try:
        t0 = time.perf_counter()
        futs = [b.submit(np.full(4, i, np.float32)) for i in range(3)]
        outs = [f.result(timeout=2.0) for f in futs]
        dt = time.perf_counter() - t0
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, np.full(4, i, np.float32))
        assert dt < 1.0, f"timeout flush took {dt:.3f}s"
        after = telemetry.snapshot()["counters"]
        # one partial batch, one flush
        assert after["embed.batches"] - before.get("embed.batches", 0) == 1
        assert (after["embed.rows_valid"]
                - before.get("embed.rows_valid", 0)) == 3
    finally:
        b.close()


def test_batcher_close_serves_pending():
    """close() with requests still queued must drain them (every future
    resolves) before the worker thread exits — no dropped work."""
    def slow(stacked, n):
        time.sleep(0.02)
        return [stacked[i] * 2 for i in range(n)]

    b = DynamicBatcher(slow, max_batch=4, timeout_s=0.5)
    xs = [np.full(4, i, np.float32) for i in range(12)]
    futs = [b.submit(x) for x in xs]
    b.close()                                  # pending batches still queued
    assert not b._thread.is_alive()
    for i, f in enumerate(futs):
        assert f.done(), f"future {i} dropped on close"
        np.testing.assert_array_equal(f.result(timeout=0), xs[i] * 2)


def test_batcher_batches_and_results():
    seen = []

    def fn(stacked, n):
        seen.append((stacked.shape[0], n))
        return [stacked[i] * 2 for i in range(n)]

    b = DynamicBatcher(fn, max_batch=8, timeout_s=0.02)
    xs = [np.full(4, i, np.float32) for i in range(20)]
    out = b.score(xs)
    b.close()
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, xs[i] * 2)
    assert all(s[0] in (1, 2, 4, 8) for s in seen)   # pow-2 buckets
    assert max(s[1] for s in seen) > 1               # actually batched


# ----------------------------------------------------------------- config --
def test_yaml_subset_parser_paper_example():
    text = """
name: "IMG_CLASSIFICATION"
version: 0.1
active_learning:
  strategy:
    type: "auto"
  model:
    name: "resnet18"
    hub_name: "pytorch/vision:release/0.12"
    batch_size: 1
  device: CPU
al_worker:
  protocol: "grpc"
  host: "0.0.0.0"
  port: 60035
  replicas: 1
"""
    d = parse_yaml(text)
    assert d["name"] == "IMG_CLASSIFICATION"
    assert d["active_learning"]["strategy"]["type"] == "auto"
    assert d["active_learning"]["model"]["batch_size"] == 1
    assert d["al_worker"]["port"] == 60035
    cfg = ALServiceConfig.from_dict(d)
    assert cfg.strategy == "auto" and cfg.model_name == "resnet18"
    assert cfg.port == 60035


def test_yaml_lists():
    d = parse_yaml("xs:\n  - 1\n  - 2\nys:\n  - a: 1\n  - b: 2\n")
    assert d["xs"] == [1, 2]
    assert d["ys"][0] == {"a": 1}


def test_yaml_prefilter_and_spill_knobs():
    text = """
active_learning:
  prefilter: true
  prefilter_slack: 0.1
  prefilter_clusters: 32
  prefilter_min_rows: 128
al_worker:
  replicas: 3
  shard_ram_bytes: 4096
  shard_spill_dir: "/tmp/spill"
"""
    cfg = ALServiceConfig.from_dict(parse_yaml(text))
    assert cfg.prefilter is True and cfg.prefilter_slack == 0.1
    assert cfg.prefilter_clusters == 32 and cfg.prefilter_min_rows == 128
    assert cfg.shard_ram_bytes == 4096
    assert cfg.shard_spill_dir == "/tmp/spill"
    # defaults: gate off (the oracle), unlimited RAM (no spill)
    d = ALServiceConfig()
    assert d.prefilter is False and d.shard_ram_bytes == 0
    assert d.shard_spill_dir is None


def test_yaml_strategy_state_and_standing_knobs():
    """The standing-query / persisted-state knobs round-trip through the
    YAML subset, and both default ON (their ``false`` settings are the
    bit-identity oracles, not the production path)."""
    text = """
active_learning:
  strategy_state_cache: false
  standing_replay: false
"""
    cfg = ALServiceConfig.from_dict(parse_yaml(text))
    assert cfg.strategy_state_cache is False
    assert cfg.standing_replay is False
    d = ALServiceConfig()
    assert d.strategy_state_cache is True and d.standing_replay is True


def test_yaml_transformer_model_knobs():
    """The transformer-backend knobs round-trip through the YAML subset
    under ``active_learning.model`` (the committed configs/*.yml files
    exercise the same schema end to end)."""
    text = """
active_learning:
  model:
    name: transformer
    batch_size: 8
    block_size: 32
    seq_len: 96
    pooling: last
    modality: audio
    input_dim: 12
"""
    cfg = ALServiceConfig.from_dict(parse_yaml(text))
    assert cfg.model_name == "transformer"
    assert cfg.model_block_size == 32 and cfg.model_seq_len == 96
    assert cfg.model_pooling == "last" and cfg.model_modality == "audio"
    assert cfg.model_input_dim == 12
    d = ALServiceConfig()
    assert (d.model_block_size, d.model_seq_len, d.model_pooling,
            d.model_modality, d.model_input_dim) == (64, 128, "mean",
                                                     "text", 0)


def test_yaml_shard_worker_knobs():
    """The shard-worker runtime knobs round-trip through the YAML subset
    under ``al_worker``; defaults are a 30s presumed-dead timeout and 2
    bounded retries."""
    text = """
al_worker:
  replicas: 3
  timeout_s: 5.5
  retries: 4
  backoff_s: 0.25
"""
    cfg = ALServiceConfig.from_dict(parse_yaml(text))
    assert cfg.replicas == 3
    assert cfg.worker_timeout_s == 5.5
    assert cfg.worker_retries == 4 and cfg.worker_backoff_s == 0.25
    d = ALServiceConfig()
    assert (d.worker_timeout_s, d.worker_retries,
            d.worker_backoff_s) == (30.0, 2, 0.05)


def test_yaml_overload_serving_knobs():
    """The overload-safe-serving knobs round-trip through the YAML subset:
    admission (nested map incl. per-tenant fairness weights), socket
    idle/send timeouts, and the bounded-ingest cap/policy. Defaults keep
    every overload behaviour OFF — the bit-identity oracle."""
    text = """
al_worker:
  idle_timeout_s: 12.5
  send_timeout_s: 3.5
  ingest_max_rows: 1024
  ingest_max_bytes: 1048576
  ingest_policy: shed
  admission:
    enabled: true
    max_inflight: 32
    tenant_rate: 50.0
    tenant_burst: 16
    weights:
      tenant_a: 3.0
      tenant_b: 1
"""
    cfg = ALServiceConfig.from_dict(parse_yaml(text))
    assert cfg.admission is True
    assert cfg.admission_max_inflight == 32
    assert cfg.admission_tenant_rate == 50.0
    assert cfg.admission_tenant_burst == 16.0
    assert cfg.fairness_weights == {"tenant_a": 3.0, "tenant_b": 1.0}
    assert cfg.idle_timeout_s == 12.5 and cfg.send_timeout_s == 3.5
    assert cfg.ingest_max_rows == 1024
    assert cfg.ingest_max_bytes == 1048576
    assert cfg.ingest_policy == "shed"
    d = ALServiceConfig()
    assert d.admission is False and d.fairness_weights is None
    assert d.idle_timeout_s == 0.0 and d.send_timeout_s == 30.0
    assert (d.ingest_max_rows, d.ingest_max_bytes) == (0, 0)
    assert d.ingest_policy == "block"


# ----------------------------------------------------------------- server --
@pytest.fixture(scope="module")
def pool():
    X, Y = image_pool(240, seed=0)
    EX, EY = image_pool(120, seed=1)
    return X, Y, EX, EY


def _server(pool):
    X, Y, EX, EY = pool
    srv = ALServer(ALServiceConfig(batch_size=32))
    keys = srv.push_data(list(X))
    key2y = dict(zip(keys, Y))
    srv.attach_oracle(lambda ks: [key2y[k] for k in ks], EX, EY)
    return srv, keys, key2y


def test_server_round_improves_over_init(pool):
    srv, keys, key2y = _server(pool)
    res = srv.query(budget=60, strategy="lc")
    assert len(set(res["keys"])) == 60
    srv.label(res["keys"], [key2y[k] for k in res["keys"]])
    acc = srv.train_and_eval()
    assert acc > 0.2     # 10-class problem, must beat chance by 2x


def test_server_cache_hits_on_repush(pool):
    srv, keys, _ = _server(pool)
    h0 = srv.cache.stats()
    srv.push_data(list(pool[0][:50]))             # same content -> all cached
    assert srv.cache.stats()["entries"] == h0["entries"]


def test_server_pshea_auto(pool):
    srv, keys, key2y = _server(pool)
    res = srv.query(budget=120, strategy="auto", target_accuracy=0.99)
    assert res["strategy"] in ("lc", "mc", "rc", "es", "kcg", "coreset",
                               "dbal")
    assert len(res["eliminated"]) >= 1
    assert res["stop_reason"] in ("budget_exhausted", "target_accuracy",
                                  "converged", "max_rounds")


def test_server_pshea_hybrid_registry(pool):
    """auto_candidates="hybrid" races the weighted fused-round hybrids in
    the PSHEA agent alongside the paper's seven."""
    from repro.core.strategies.zoo import HYBRIDS, PAPER_SEVEN
    X, Y, EX, EY = pool
    srv = ALServer(ALServiceConfig(batch_size=32, auto_candidates="hybrid"))
    keys = srv.push_data(list(X))
    key2y = dict(zip(keys, Y))
    srv.attach_oracle(lambda ks: [key2y[k] for k in ks], EX, EY)
    res = srv.query(budget=150, strategy="auto", target_accuracy=0.99)
    assert res["strategy"] in PAPER_SEVEN + HYBRIDS
    assert set(res["history"]) == set(PAPER_SEVEN + HYBRIDS)
    # a candidate-set typo must fail loudly, not degrade to the default
    bad = ALServer(ALServiceConfig(auto_candidates="hybrids"))
    with pytest.raises(ValueError):
        bad._auto_candidates()


def test_tcp_roundtrip(pool):
    srv, keys, key2y = _server(pool)
    rpc = serve_tcp(srv)
    cli = ALClient(url=f"127.0.0.1:{rpc.port}")
    try:
        st = cli.stats()
        assert st["pool"] == 240
        res = cli.query(5, "mc")
        assert len(res["keys"]) == 5
        cli.label(res["keys"], [key2y[k] for k in res["keys"]])
        acc = cli.train_eval()
        assert 0.0 <= acc <= 1.0
    finally:
        cli.close()
        rpc.stop()


def test_tcp_query_spans_and_counters(pool):
    """A coreset query over TCP leaves one span tree under its
    ``alaas.rpc`` span, counts its picks, its host syncs (two scalar reads
    per proposal: the warm start's and one per pick) and the bytes of
    every upload, and ``stats()`` carries the recorder's snapshot."""
    X = pool[0]
    srv = _mlp_server()
    rpc = serve_tcp(srv)
    cli = ALClient(url=f"127.0.0.1:{rpc.port}", session="new")
    n, n_lab, budget, d = 120, 12, 9, 32
    try:
        keys = cli.push_data(list(X[:n]))
        cli.label(keys[:n_lab], [i % 10 for i in range(n_lab)])
        cli.train_eval()
        t0 = time.perf_counter()
        out = cli.query(budget, "coreset", rng_seed=3)
        st = cli.stats()
    finally:
        cli.close()
        rpc.stop()
    assert len(out["keys"]) == budget
    (query,) = [e for e in telemetry.events()
                if e.t1 >= t0 and e.name == "alaas.query"]
    evs = [e for e in telemetry.events() if e.request_id == query.request_id]
    parents = {e.name: e.parent for e in evs if e.value is None}
    assert parents == {
        "alaas.rpc": None, "alaas.query": "alaas.rpc",
        "alaas.artifact.refresh": "alaas.query",
        "alaas.select.gather": "alaas.query",
        "alaas.select.warm_state": "alaas.query",
        "alaas.select.greedy": "alaas.query"}

    def counted(name):
        return sum(e.value for e in evs if e.name == name)

    assert counted("select.picks") == budget
    assert counted("select.d2h_syncs") == 2 * (budget + 1)
    assert counted("h2d_bytes") == 4 * (
        n * d                   # probs refresh: the pool's feats
        + n_lab * d             # the labeled embeddings
        + n * d + n_lab * d     # warm state: pool feats and the centers
        + (n - n_lab) * d       # the unlabeled view the loop reads
        + (n - n_lab)           # its min-dists
        + budget)               # each pick's winner mask
    assert st["trace"]["spans"]["alaas.select.greedy"]["count"] >= 1
    assert st["trace"]["counters"]["select.picks"] >= budget


def test_pipelined_push_equals_serial_push(pool):
    X = list(pool[0][:64])
    s1 = ALServer(ALServiceConfig(batch_size=16))
    k1 = s1.push_data(X, pipelined=True)
    s2 = ALServer(ALServiceConfig(batch_size=16))
    k2 = s2.push_data(X, pipelined=False)
    assert k1 == k2
    f1 = np.stack([s1.cache.get(k) for k in k1])
    f2 = np.stack([s2.cache.get(k) for k in k2])
    np.testing.assert_allclose(f1, f2, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- sessions --
def _mlp_server(**cfg):
    """Cheap multi-tenant server (random-projection backend, no resnet)."""
    from repro.service.backends import MLPBackend
    return ALServer(ALServiceConfig(batch_size=16, **cfg),
                    backend=MLPBackend(in_dim=192, feat_dim=32))


def test_sessions_are_isolated(pool):
    X, Y = pool[0], pool[1]
    srv = _mlp_server()
    a = srv.create_session()
    b = srv.create_session()
    ka = srv.push_data(list(X[:40]), session=a)
    kb = srv.push_data(list(X[40:70]), session=b)
    assert srv.stats(session=a)["pool"] == 40
    assert srv.stats(session=b)["pool"] == 30
    assert srv.stats()["pool"] == 0                   # default untouched
    srv.label(ka[:10], Y[:10], session=a)
    assert srv.stats(session=a)["labeled"] == 10
    assert srv.stats(session=b)["labeled"] == 0
    res = srv.query(budget=5, strategy="lc", session=b)
    assert set(res["keys"]) <= set(kb)                # b never sees a's pool
    assert srv.train_and_eval(session=a) >= 0.0
    assert srv.train_and_eval(session=b) == 0.0       # b has no labels


def test_session_lifecycle_errors():
    srv = _mlp_server()
    with pytest.raises(KeyError, match="unknown session"):
        srv.query(1, strategy="lc", session="nope")
    with pytest.raises(ValueError):
        srv.create_session("default")                 # already exists
    with pytest.raises(ValueError):
        srv.close_session("default")                  # cannot close default
    sid = srv.create_session()
    srv.close_session(sid)
    assert sid not in srv.session_ids()


def test_tcp_sessions_isolated(pool):
    X = pool[0]
    srv = _mlp_server()
    rpc = serve_tcp(srv)
    url = f"127.0.0.1:{rpc.port}"
    a = ALClient(url=url, session="new")
    b = ALClient(url=url, session="new")
    try:
        a.push_data(list(X[:24]))
        b.push_data(list(X[24:40]))
        assert a.stats()["pool"] == 24
        assert b.stats()["pool"] == 16
        assert a.session != b.session
        res = a.query(4, "mc")
        assert len(res["keys"]) == 4
    finally:
        a.close()
        b.close()
        rpc.stop()
    assert srv.session_ids() == ["default"]           # close() cleaned up


def test_tcp_disconnect_reclaims_session(pool):
    """A client that vanishes without close_session must not leak its
    server-side session (raw pool copies and all)."""
    srv = _mlp_server()
    rpc = serve_tcp(srv)
    try:
        cli = ALClient(url=f"127.0.0.1:{rpc.port}", session="new")
        cli.push_data(list(pool[0][:8]))
        assert len(srv.session_ids()) == 2
        cli._rpc.close()                              # crash: no close_session
        deadline = time.time() + 5
        while len(srv.session_ids()) > 1 and time.time() < deadline:
            time.sleep(0.02)
        assert srv.session_ids() == ["default"]
    finally:
        rpc.stop()


# --------------------------------------------------------- artifact cache --
def test_artifact_cache_invalidation_matrix(pool):
    """The incremental invalidation matrix: repeated queries hit; a push
    delta-builds only the appended rows; label invalidates NOTHING (the
    unlabeled set is a query-time mask); train_and_eval refreshes probs
    only, with zero re-embeds."""
    X, Y = pool[0], pool[1]
    srv = _mlp_server()
    keys = srv.push_data(list(X[:60]))
    sess = srv.session()

    srv.query(budget=5, strategy="lc")
    assert sess.artifact_builds == 1
    assert (sess.full_builds, sess.delta_builds) == (1, 0)
    srv.query(budget=5, strategy="mc")
    srv.query(budget=5, strategy="kcg")
    assert sess.artifact_builds == 1                  # hits across strategies

    srv.push_data(list(pool[2][:4]))                  # new rows -> delta
    e0 = srv.embed_rows
    srv.query(budget=5, strategy="lc")
    assert sess.artifact_builds == 2
    assert (sess.full_builds, sess.delta_builds) == (1, 1)
    assert sess._columns[0].feats_rows == 64          # extended in place
    assert srv.embed_rows == e0                       # delta came from cache

    srv.label(keys[:10], Y[:10])                      # label -> NO rebuild
    srv.query(budget=5, strategy="lc")
    assert sess.artifact_builds == 2
    assert sess.labels_version == 1

    srv.train_and_eval()                              # new head -> probs only
    e1 = srv.embed_rows
    srv.query(budget=5, strategy="lc")
    assert sess.artifact_builds == 3
    assert sess.probs_refreshes == 1
    assert srv.embed_rows == e1                       # zero re-embeds
    srv.query(budget=5, strategy="es")
    assert sess.artifact_builds == 3

    st = srv.stats()                                  # observability payload
    assert st["artifacts"]["builds"] == 3
    assert st["artifacts"]["shard_builds"] == [3]
    assert st["artifacts"]["full_builds"] == 1
    assert st["artifacts"]["delta_builds"] == 1
    assert st["artifacts"]["probs_refreshes"] == 1
    assert st["labels_version"] == 1
    assert st["embeds"]["rows"] == 64                 # 60 + 4 pushed rows
    assert st["cache"]["hits"] > 0


def test_query_on_fully_labeled_pool_returns_empty(pool):
    """Regression: with every pool row labeled, budget clamps to 0 and the
    unsharded path used to crash embedding strategies (.at[0] on a (0,)
    selection buffer) instead of returning an empty selection like the
    sharded path."""
    X, Y = pool[0], pool[1]
    for replicas in (1, 3):
        srv = _mlp_server(replicas=replicas)
        keys = srv.push_data(list(X[:12]))
        srv.label(keys, Y[:12])
        for strategy in ("lc", "kcg"):
            res = srv.query(budget=4, strategy=strategy)
            assert res["keys"] == [] and res["indices"] == []


def test_artifact_cache_off_matches_on(pool):
    """Cache on/off must produce bit-identical selections (both build over
    the full pool; off just doesn't memoize)."""
    X, Y = pool[0], pool[1]
    picks = {}
    for cached in (True, False):
        srv = _mlp_server(artifact_cache=cached)
        keys = srv.push_data(list(X[:80]))
        srv.label(keys[:12], Y[:12])
        srv.train_and_eval()
        picks[cached] = {
            s: srv.query(budget=8, strategy=s, rng_seed=3)["keys"]
            for s in ("lc", "kcg", "coreset")}
    assert picks[True] == picks[False]
    srv_off = _mlp_server(artifact_cache=False)
    srv_off.push_data(list(X[:30]))
    sess = srv_off.session()
    srv_off.query(budget=4, strategy="lc")
    srv_off.query(budget=4, strategy="lc")
    assert sess.artifact_builds == 2                  # one build per query


def test_tiny_cache_recomputes_evicted_embeddings(pool):
    """Regression: with cache_bytes smaller than the pool and no spill dir,
    eviction used to make EmbeddingCache.get return None and crash
    np.stack inside query/train paths; the session now recomputes from its
    raw copies (or raises a clear KeyError)."""
    X, Y = pool[0], pool[1]
    srv = _mlp_server(cache_bytes=10 * 32 * 4)        # ~10 of 60 feats fit
    keys = srv.push_data(list(X[:60]))
    assert srv.cache.stats()["entries"] < 60          # eviction happened
    res = srv.query(budget=6, strategy="lc")          # full-pool artifacts
    assert len(res["keys"]) == 6
    srv.label(keys[:20], Y[:20])
    acc = srv.train_and_eval()                        # labeled-feats path
    assert 0.0 <= acc <= 1.0
    # raw copy gone AND evicted -> clear KeyError, not a np.stack crash
    sess = srv.session()
    missing = [k for k in keys if srv.cache.get(k) is None]
    if missing:
        del sess._raw[missing[0]]
        with pytest.raises(KeyError, match="evicted"):
            sess._feats_for([missing[0]])
