"""Replica sharding: every zoo strategy's sharded selection must be
bit-identical to ``replicas=1`` across shard counts and ragged pools,
including the empty-shard edge; plus the merge primitives themselves and
the evicted-embedding recompute path under sharding."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.selection import (ShardView, gather_rows, locate_row,
                                  replica_of, replica_top_k)
from repro.core.strategies.zoo import SHARDED_COMPLETE, ZOO
from repro.data.synthetic import image_pool
from repro.service.backends import MLPBackend
from repro.service.config import ALServiceConfig
from repro.service.server import ALServer

REPLICAS = (1, 2, 3, 7)
STRATEGIES = sorted(ZOO)


def _mlp_server(replicas, **cfg):
    return ALServer(ALServiceConfig(batch_size=16, replicas=replicas, **cfg),
                    backend=MLPBackend(in_dim=192, feat_dim=32))


def _make_shards(feats, probs, keys, replicas):
    """Hash-partition a pool the way the session does: shard-local rows
    keep global order."""
    shards = []
    for s in range(replicas):
        g = np.asarray([i for i, k in enumerate(keys)
                        if replica_of(k, replicas) == s], np.int64)
        shards.append(ShardView(feats=feats[g] if g.size else feats[:0],
                                probs=probs[g] if g.size else probs[:0],
                                gidx=g))
    return shards


# ----------------------------------------------------- merge primitives --
def test_replica_of_stable_and_in_range():
    keys = [f"key-{i}" for i in range(200)]
    for r in (1, 2, 3, 7):
        shards = [replica_of(k, r) for k in keys]
        assert all(0 <= s < r for s in shards)
        assert shards == [replica_of(k, r) for k in keys]  # deterministic
    # every shard of a reasonably sized pool is populated at small R
    assert set(replica_of(k, 3) for k in keys) == {0, 1, 2}


def test_replica_top_k_matches_lax_top_k_with_ties():
    rng = np.random.default_rng(0)
    # coarse quantization manufactures many exact float ties
    scores = (rng.integers(0, 5, size=97) / 4.0).astype(np.float32)
    keys = [f"t{i}" for i in range(97)]
    feats = rng.standard_normal((97, 4)).astype(np.float32)
    single_v, single_i = jax.lax.top_k(jnp.asarray(scores), 10)
    for r in REPLICAS:
        shards = _make_shards(feats, feats, keys, r)
        sc = [jnp.asarray(scores[np.asarray(s.gidx)]) for s in shards]
        gidx, vals = replica_top_k(shards, sc, 10)
        assert gidx.tolist() == np.asarray(single_i).tolist(), r
        assert vals.tolist() == np.asarray(single_v).tolist(), r


def test_locate_and_gather_rows():
    rng = np.random.default_rng(1)
    feats = rng.standard_normal((31, 8)).astype(np.float32)
    keys = [f"g{i}" for i in range(31)]
    shards = _make_shards(feats, feats, keys, 4)
    rows = [0, 30, 17, 17, 5]
    np.testing.assert_array_equal(gather_rows(shards, rows), feats[rows])
    for g in rows:
        si, li = locate_row(shards, g)
        assert int(shards[si].gidx[li]) == g
    with pytest.raises(IndexError):
        locate_row(shards, 31)


# ------------------------------------------- strategy-level equivalence --
@pytest.fixture(scope="module")
def pool_artifacts():
    """A ragged-size pool with probs/embeddings + labeled rows."""
    rng = np.random.default_rng(7)
    N, d, C = 61, 16, 10
    feats = rng.standard_normal((N, d)).astype(np.float32)
    logits = rng.standard_normal((N, C)).astype(np.float32)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits)))
    labeled = rng.standard_normal((7, d)).astype(np.float32)
    keys = [f"pool-{i}" for i in range(N)]
    return feats, probs, labeled, keys


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_strategy_bit_identical(strategy, pool_artifacts):
    feats, probs, labeled, keys = pool_artifacts
    strat = ZOO[strategy]
    budget = 6
    single = np.asarray(strat.select(
        jax.random.PRNGKey(3), budget,
        probs=jnp.asarray(probs) if "probs" in strat.needs else None,
        embeddings=jnp.asarray(feats) if "embeddings" in strat.needs
        else None,
        labeled_embeddings=(jnp.asarray(labeled)
                            if "embeddings" in strat.needs else None)))
    for r in REPLICAS:
        sharded = np.asarray(strat.select_sharded(
            jax.random.PRNGKey(3), budget,
            _make_shards(feats, probs, keys, r),
            labeled_embeddings=(jnp.asarray(labeled)
                                if "embeddings" in strat.needs else None)))
        assert sharded.tolist() == single.tolist(), \
            f"{strategy} diverged at replicas={r}"


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_strategy_empty_shard_edge(strategy):
    """Pool smaller than the shard count: some shards are empty and must
    neither crash nor perturb the merge."""
    rng = np.random.default_rng(11)
    N, d, C = 5, 16, 10
    feats = rng.standard_normal((N, d)).astype(np.float32)
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(rng.standard_normal((N, C)).astype(np.float32))))
    keys = [f"tiny-{i}" for i in range(N)]
    shards = _make_shards(feats, probs, keys, 7)
    assert any(s.n == 0 for s in shards), "edge requires an empty shard"
    strat = ZOO[strategy]
    single = np.asarray(strat.select(
        jax.random.PRNGKey(9), 3,
        probs=jnp.asarray(probs) if "probs" in strat.needs else None,
        embeddings=jnp.asarray(feats) if "embeddings" in strat.needs
        else None,
        labeled_embeddings=None))
    sharded = np.asarray(strat.select_sharded(jax.random.PRNGKey(9), 3,
                                              shards))
    assert sharded.tolist() == single.tolist()


# ------------------------------------------------ the device pick loop --
FAMILIES = ("seeded", "warm", "static", "gumbel")


@pytest.fixture
def captured(monkeypatch):
    """Every ``replica_greedy_select`` call records its per-slot merged
    scores into the returned list."""
    from repro.core import selection
    real, got = selection.replica_greedy_select, []

    def recording(*args, **kwargs):
        cap = []
        kwargs["capture"] = cap
        out = real(*args, **kwargs)
        got.append(cap)
        return out

    monkeypatch.setattr(selection, "replica_greedy_select", recording)
    return got


def _flat_family(family, rng, budget, feats, labeled):
    """The single-pool selection and the scores its loop ranks each pick
    by, replayed with the flat path's own round."""
    from repro.core.strategies.diversity import k_center_greedy
    from repro.core.strategies.hybrid import kmeans_pp_sample
    from repro.kernels.pairwise import ops
    x = jnp.asarray(feats)
    N = x.shape[0]
    w = jnp.asarray(np.linspace(0.2, 1.0, N), jnp.float32)
    init = jnp.asarray(labeled) if family in ("warm", "static") else None
    if family == "gumbel":
        sel = np.asarray(kmeans_pp_sample(rng, x, budget))
        keys = jax.random.split(rng, budget + 1)

        def weight(j):
            return jnp.exp(jax.random.gumbel(keys[j], (N,), jnp.float32))
    else:
        sel = np.asarray(k_center_greedy(
            rng, budget, x, init_centers=init,
            weights=w if family == "static" else None))

        def weight(j):
            return w if family == "static" else None
    if init is None:
        mind = ops.sq_dist_to_center(x, x[sel[0]]).at[sel[0]].set(-1.0)
        start = 1
    else:
        mind, start = ops.warm_start_min_dist(x, init), 0
    scores = []
    for j in range(start, budget):
        scores.append(float(ops.masked_weighted_score(mind, weight(j))[sel[j]]))
        mind = ops.greedy_round(x, mind, x[sel[j]][None, :],
                                jnp.asarray([sel[j]], jnp.int32),
                                weights=weight(j))[0]
    return sel, scores, w


def _sharded_family(family, rng, budget, shards, labeled, w):
    from repro.core.strategies.diversity import sharded_k_center
    from repro.core.strategies.hybrid import sharded_kmeans_pp
    if family == "gumbel":
        return sharded_kmeans_pp(
            rng, [jnp.asarray(s.feats) for s in shards], shards, budget)
    return sharded_k_center(
        rng, budget, shards,
        init_centers=(jnp.asarray(labeled)
                      if family in ("warm", "static") else None),
        weights_list=([w[jnp.asarray(s.gidx)] for s in shards]
                      if family == "static" else None))


@pytest.mark.parametrize("pool", ["ragged", "empty_shard"])
@pytest.mark.parametrize("family", FAMILIES)
def test_device_loop_matches_flat_path(family, pool, pool_artifacts,
                                       captured):
    """The device pick loop's selections and captured per-slot scores equal
    the flat ``k_center_greedy`` / ``kmeans_pp_sample`` bitwise: unweighted
    (seeded and warm), static-weight and Gumbel-weight rounds, at every
    shard count, and with empty shards."""
    feats, probs, labeled, keys = pool_artifacts
    if pool == "empty_shard":
        feats, probs, keys = feats[:5], probs[:5], keys[:5]
    budget = 4 if pool == "empty_shard" else 9
    rng = jax.random.PRNGKey(13)
    sel, scores, w = _flat_family(family, rng, budget, feats, labeled)
    for r in REPLICAS:
        shards = _make_shards(feats, probs, keys, r)
        if pool == "empty_shard" and r == 7:
            assert any(s.n == 0 for s in shards)
        del captured[:]
        got = _sharded_family(family, rng, budget, shards, labeled, w)
        assert np.asarray(got).tolist() == sel.tolist(), (family, r)
        (cap,) = captured
        assert np.asarray(cap, np.float32).tobytes() == \
            np.asarray(scores, np.float32).tobytes(), (family, r)


def _counted(name, fn):
    from repro.common import telemetry
    before = telemetry.snapshot()["counters"].get(name, 0)
    fn()
    return telemetry.snapshot()["counters"].get(name, 0) - before


@pytest.fixture(scope="module")
def wide_pool():
    rng = np.random.default_rng(21)
    feats = rng.standard_normal((150, 16)).astype(np.float32)
    keys = [f"wide-{i}" for i in range(150)]
    labeled = rng.standard_normal((5, 16)).astype(np.float32)
    return _make_shards(feats, feats, keys, 3), jnp.asarray(labeled)


def _warm_query(shards, labeled, budget):
    from repro.core.strategies.diversity import sharded_k_center
    return sharded_k_center(jax.random.PRNGKey(0), budget, shards,
                            init_centers=labeled)


def test_device_loop_syncs_do_not_grow_with_budget(wide_pool):
    """One query reads its selection back once, whatever its budget."""
    syncs = {b: _counted("select.d2h_syncs",
                         lambda b=b: _warm_query(*wide_pool, b))
             for b in (1, 64)}
    assert syncs == {1: 1, 64: 1}


def test_device_loop_compiles_once_per_pool_shape(wide_pool):
    """After a budget-1 query, a budget-64 query over the same shards
    compiles nothing: the budget is a traced bound of one program."""
    _warm_query(*wide_pool, 1)
    assert _counted("compile.count",
                    lambda: _warm_query(*wide_pool, 64)) == 0


def test_every_zoo_strategy_has_a_sharded_path():
    assert SHARDED_COMPLETE
    assert all(ZOO[s].sharded_fn is not None for s in ZOO)


# --------------------------------------------- server-level equivalence --
@pytest.fixture(scope="module")
def servers():
    """One server per shard count, identically populated (same pushes,
    labels and head training), over two ragged pool sizes."""
    X, Y = image_pool(53, seed=5)
    out = {}
    for r in REPLICAS:
        srv = _mlp_server(r)
        keys = srv.push_data(list(X))
        srv.label(keys[:11], Y[:11])
        srv.train_and_eval()
        out[r] = srv
    return out


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_server_query_bit_identical_across_replicas(strategy, servers):
    ref = servers[1].query(budget=5, strategy=strategy, rng_seed=4)
    for r in REPLICAS[1:]:
        res = servers[r].query(budget=5, strategy=strategy, rng_seed=4)
        assert res["keys"] == ref["keys"], f"replicas={r}"
        assert res["indices"] == ref["indices"], f"replicas={r}"


def test_server_budget_exceeding_pool_across_replicas(servers):
    """budget > unlabeled clamps identically on every shard count."""
    ref = servers[1].query(budget=500, strategy="lc", rng_seed=0)
    assert len(ref["keys"]) == 53 - 11
    for r in REPLICAS[1:]:
        res = servers[r].query(budget=500, strategy="lc", rng_seed=0)
        assert res["keys"] == ref["keys"]


def test_sharded_artifact_cache_hits_and_invalidation():
    X, Y = image_pool(30, seed=6)
    srv = _mlp_server(3)
    keys = srv.push_data(list(X))
    sess = srv.session()
    srv.query(budget=4, strategy="lc")
    srv.query(budget=4, strategy="kcg")
    assert sess.artifact_builds == 1          # per-shard set built once
    srv.label(keys[:6], Y[:6])                # label: NO shard invalidated
    srv.query(budget=4, strategy="lc")
    assert sess.artifact_builds == 1
    X2, _ = image_pool(6, seed=16)
    new_keys = srv.push_data(list(X2))        # delta: only touched shards
    touched = {replica_of(k, 3) for k in new_keys}
    before = [c.builds for c in sess._columns]
    srv.query(budget=4, strategy="lc")
    assert sess.artifact_builds == 2
    after = [c.builds for c in sess._columns]
    assert {si for si in range(3) if after[si] > before[si]} == touched
    assert all(after[si] == before[si]
               for si in range(3) if si not in touched)


def test_sharded_tiny_cache_recomputes_evicted_embeddings():
    """Eviction under sharding: per-shard artifact builds recompute evicted
    embeddings from the session's raw copies instead of crashing."""
    X, Y = image_pool(60, seed=8)
    srv = _mlp_server(3, cache_bytes=10 * 32 * 4)   # ~10 of 60 feats fit
    keys = srv.push_data(list(X))
    assert srv.cache.stats()["entries"] < 60        # eviction happened
    res = srv.query(budget=6, strategy="lc")
    assert len(res["keys"]) == 6
    res = srv.query(budget=6, strategy="kcg")
    assert len(set(res["keys"])) == 6
    srv.label(keys[:20], Y[:20])
    assert 0.0 <= srv.train_and_eval() <= 1.0


PROBS_STRATEGIES = sorted(s for s in ZOO if "probs" in ZOO[s].needs)


@pytest.fixture
def uncertainty_interpret(monkeypatch):
    """Every probs score runs the uncertainty kernel in interpret mode, as
    ``impl="auto"`` runs the compiled kernel on the TPU. Traces cached
    under the old dispatch are dropped on the way in and out."""
    from repro.kernels.uncertainty import ops as unc_ops
    monkeypatch.setattr(unc_ops, "_resolve", lambda impl: "interpret")
    jax.clear_caches()
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("strategy", PROBS_STRATEGIES)
def test_kernel_scores_with_empty_shard(strategy, uncertainty_interpret):
    """A shard with no unlabeled rows hands the uncertainty kernel a (0, C)
    probs block; the kernel path must score it as empty and the selection
    must match replicas=1."""
    X, Y = image_pool(24, seed=3)
    srvs = {r: _mlp_server(r) for r in (1, 3)}
    keys = {r: s.push_data(list(X)) for r, s in srvs.items()}
    # label every row of shard 0 plus a few others, so shard 0 is empty
    lab = [i for i, k in enumerate(keys[3]) if replica_of(k, 3) == 0]
    lab += [i for i in range(len(X)) if i not in lab][:3]
    assert 0 < len(lab) < len(X)
    for r, s in srvs.items():
        s.label([keys[r][i] for i in lab], Y[lab])
        s.train_and_eval()
    res = {r: s.query(budget=4, strategy=strategy, rng_seed=2)
           for r, s in srvs.items()}
    assert len(res[1]["keys"]) == 4
    assert res[3]["keys"] == res[1]["keys"]


# ------------------------------------- the pick loop across four devices --
FOUR_DEVICE_PROGRAM = r'''
import json
import sys
import numpy as np
import jax
import jax.numpy as jnp
sys.path.insert(0, "tests")
from test_sharding import _flat_family, _make_shards, _sharded_family
from repro.common import telemetry
from repro.core import selection
from repro.core.strategies.diversity import sharded_k_center
from repro.data.synthetic import image_pool
from repro.service.backends import MLPBackend
from repro.service.client import ALClient
from repro.service.config import ALServiceConfig
from repro.service.server import ALServer

devs = jax.devices()
assert len(devs) == 4
rng = np.random.default_rng(7)
N, d = 61, 16
FEATS = rng.standard_normal((N, d)).astype(np.float32)
LABELED = rng.standard_normal((7, d)).astype(np.float32)
KEYS = [f"pool-{i}" for i in range(N)]
out = {}

placed = []
real_loop = selection._lane_loop


def recording(x, mind, gidx, statics, wfs, bounds, **kw):
    res = real_loop(x, mind, gidx, statics, wfs, bounds, **kw)
    placed.append({
        "operands": [[str(s.device) for s in a.addressable_shards]
                     for a in (x, mind, gidx)],
        "rows": [s.data.shape[0] for s in x.addressable_shards],
        "outputs": [sorted(str(dv) for dv in r.sharding.device_set)
                    for r in res]})
    return res


selection._lane_loop = recording


def shards_of(feats, keys, pinned):
    shards = _make_shards(feats, feats, keys, 4)
    for s, dev in zip(shards, devs):
        s.device = dev if pinned else None
    return shards


def captured(family, key, budget, shards, w):
    cap = []
    real = selection.replica_greedy_select

    def capturing(*a, **kw):
        kw["capture"] = cap
        return real(*a, **kw)

    selection.replica_greedy_select = capturing
    try:
        got = _sharded_family(family, key, budget, shards, LABELED, w)
    finally:
        selection.replica_greedy_select = real
    return np.asarray(got).tolist(), cap


for family in ("seeded", "warm", "static", "gumbel"):
    for pool, n, budget in (("ragged", N, 9), ("empty_shard", 5, 4)):
        key = jax.random.PRNGKey(13)
        sel, scores, w = _flat_family(family, key, budget, FEATS[:n],
                                      LABELED)
        res = {"flat": [np.asarray(sel).tolist(), scores], "empty": None}
        for name, pinned in (("one_device", False), ("four_devices", True)):
            shards = shards_of(FEATS[:n], KEYS[:n], pinned)
            res["empty"] = any(s.n == 0 for s in shards)
            del placed[:]
            res[name] = captured(family, key, budget, shards, w)
            res[name + "_programs"] = len(placed)
        out[f"{family}-{pool}"] = res

# placement and compiles of one warm query over pinned shards
shards = shards_of(FEATS, KEYS, True)
del placed[:]


def warm(budget):
    return sharded_k_center(jax.random.PRNGKey(0), budget, shards,
                            init_centers=jnp.asarray(LABELED))


warm(1)
out["placement"] = placed[0]
out["shard_rows"] = [s.n for s in shards]
c0 = telemetry.snapshot()["counters"].get("compile.count", 0)
warm(40)
out["compiles_budget_40"] = (
    telemetry.snapshot()["counters"].get("compile.count", 0) - c0)

# replicas 4 over the client, lanes pinned one per device, vs replicas 1
X, Y = image_pool(80, seed=5)
served = {}
loop_devices = {}
for r in (1, 4):
    srv = ALServer(ALServiceConfig(batch_size=16, replicas=r),
                   backend=MLPBackend(in_dim=192, feat_dim=32))
    cli = ALClient(local=srv)
    keys = cli.push_data(list(X))
    cli.label(keys[:13], Y[:13].tolist())
    cli.train_eval()
    c0 = telemetry.snapshot()["counters"].get("select.loop_devices", 0)
    served[r] = {s: cli.query(7, s, rng_seed=3)["keys"]
                 for s in ("coreset", "kcg", "badge", "weighted_kcenter")}
    loop_devices[r] = (
        telemetry.snapshot()["counters"].get("select.loop_devices", 0) - c0)
    cli.close()
out["served_equal"] = {s: served[4][s] == served[1][s] for s in served[1]}
out["served_loop_devices"] = loop_devices
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def four_devices():
    """One run of ``FOUR_DEVICE_PROGRAM`` on four forced host devices (a
    subprocess: the device count is fixed when JAX starts)."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = ('import os\nos.environ["XLA_FLAGS"] = '
            '"--xla_force_host_platform_device_count=4"\n'
            + FOUR_DEVICE_PROGRAM)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd=root,
                       env=dict(os.environ, PYTHONPATH="src",
                                JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("pool", ["ragged", "empty_shard"])
@pytest.mark.parametrize("family", FAMILIES)
def test_four_device_loop_matches_flat_path(family, pool, four_devices):
    """Shards pinned one per device run the loop as one program across the
    four devices; its selections and captured scores equal the flat path's
    and the single-device loop's bitwise, weights absent, static or per
    slot, on ragged shards and with an empty one."""
    res = four_devices[f"{family}-{pool}"]
    assert res["four_devices_programs"] == 1
    assert res["one_device_programs"] == 0
    if pool == "empty_shard":
        assert res["empty"]
    sel, scores = res["flat"]
    for name in ("one_device", "four_devices"):
        got, cap = res[name]
        assert got == sel, (name, got, sel)
        assert np.asarray(cap, np.float32).tobytes() == \
            np.asarray(scores, np.float32).tobytes(), name


def test_four_device_loop_operands_on_lanes(four_devices):
    """Each shard's pool rows, min-dists and row indices sit on its own
    device, padded to one layout; the selection comes back replicated."""
    p = four_devices["placement"]
    devs = [f"TFRT_CPU_{i}" for i in range(4)]
    for shards in p["operands"]:
        assert sorted(shards) == devs
        assert shards == p["operands"][0]
    assert len(set(p["rows"])) == 1
    assert p["rows"][0] >= max(four_devices["shard_rows"])
    assert p["outputs"] == [devs, devs]


def test_four_device_loop_compiles_once_per_pool_shape(four_devices):
    """A budget-40 query after a budget-1 one over the same pinned shards
    compiles nothing."""
    assert four_devices["compiles_budget_40"] == 0


def test_four_device_replicas_4_served_like_replicas_1(four_devices):
    """replicas=4 with its lanes pinned to four devices selects the same
    keys over ALClient as replicas=1, and each of its k-center-lineage
    queries spans four devices."""
    assert all(four_devices["served_equal"].values()), \
        four_devices["served_equal"]
    assert four_devices["served_loop_devices"]["4"] == 4 * 4
