"""Smoke run of the ALaaS main path on a TPU, through its entry points.

    python chip_smoke.py               # one chip: ResNet-18 AL rounds over TCP
    python chip_smoke.py --four-chips  # replicas=4 vs replicas=1, four chips

One chip: an ``ALServer`` with the ``resnet18`` scorer at its published
widths (64/128/256/512) serves an ``ALClient`` over TCP. The client pushes
a seeded CIFAR-10-shaped pool (10,000 x 32x32x3, a fifth of the training
set), labels 500 random rows, trains, queries ``lc`` and ``coreset`` (256
each), labels the coreset picks, retrains and queries ``kcg``. The ``lc``
and ``coreset`` selections are checked against the plain references of
``kernels/*/ref.py`` on the server's own features and probs, and the
lowered selection ops must hold the compiled Pallas kernels
(``tpu_custom_call``).

Four chips: a replicas=4 and a replicas=1 server get the same pushes,
labels and queries. Their selections must be equal, the replicas=4
lanes' feature outputs must sit on four distinct chips, and its coreset
pick loop must be one program across the four: each lane's shard of the
pool, min-dists and row indices on its lane's chip, the selection on
every chip, ``select.loop_devices`` 4 (1 at replicas=1). Then a seeded
49,000 x 512 pool's warm coreset selection (1,000 of it) over four shards
on four chips must equal the one-shard, one-chip selection bit for bit.

Every phase prints its wall time and XLA compile count (smoke output, not
metrics). Any failure exits non-zero; without a TPU the script exits
non-zero before any phase. On success the last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

POOL_ROWS = 10_000
IMAGE_HW = 32
WARM_LABELS = 500
BUDGET = 256
PUSH_CHUNK = 1_000
BATCH = 256
LC_TIE_TOL = 1e-5           # lc sets may differ only this close to the cut
RADIUS_RTOL = 1e-3          # coreset covering radius vs the reference


class PhaseLog:
    """Wall time and XLA backend compiles per phase."""

    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    @contextlib.contextmanager
    def phase(self, name):
        c0, s0, t0 = self.compiles, self.compile_s, time.perf_counter()
        print(f"[{name}] start", flush=True)
        yield
        print(f"[{name}] wall_s={time.perf_counter() - t0:.3f} "
              f"compiles={self.compiles - c0} "
              f"compile_s={self.compile_s - s0:.3f}", flush=True)


def _push(client, x, asynchronous=False):
    """Push ``x`` in chunks. Async pushes embed on the ingest queue, which
    at replicas > 1 fans shards out to their lanes; ``flush`` is the
    barrier."""
    keys = []
    for s in range(0, len(x), PUSH_CHUNK):
        out = client.push_data(list(x[s:s + PUSH_CHUNK]),
                               asynchronous=asynchronous)
        keys += out.keys if asynchronous else out
    if asynchronous:
        client.flush()
    return keys


def _serve(cfg):
    from repro.service.client import ALClient, serve_tcp
    from repro.service.server import ALServer
    srv = ALServer(cfg)
    rpc = serve_tcp(srv)
    client = ALClient(url=f"127.0.0.1:{rpc.port}")
    return srv, rpc, client


def _snapshot(srv, keys):
    """The server's own (feats, probs) rows for ``keys``, from its pinned
    artifact columns (replicas=1: one shard)."""
    import numpy as np
    feats_l, probs_l, _, index = srv.session()._artifact_snapshot()
    rows = np.asarray([index[k][1] for k in keys], np.int64)
    return (np.asarray(feats_l[0][rows], np.float32),
            np.asarray(probs_l[0][rows], np.float32))


def check_lc(picked, unl_keys, probs):
    """Server lc top-k == reference top-k, up to rows within LC_TIE_TOL of
    the k-th score."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.uncertainty import ref
    score = np.asarray(ref.probs_scores_ref(jnp.asarray(probs), "lc"))
    order = np.argsort(-score, kind="stable")
    cut = score[order[BUDGET - 1]]
    pos = {k: i for i, k in enumerate(unl_keys)}
    got = {pos[k] for k in picked}
    want = set(order[:BUDGET].tolist())
    diff = sorted(got ^ want)
    worst = max((abs(float(score[i]) - float(cut)) for i in diff),
                default=0.0)
    print(f"  lc: picked={len(got)} differ={len(diff)} "
          f"max|score-cut| of differing rows={worst:.3g}", flush=True)
    if len(got) != BUDGET or worst > LC_TIE_TOL:
        raise AssertionError(f"lc selection disagrees with the reference "
                             f"({len(diff)} rows differ, worst {worst})")


def _ref_min_dist(x, centers, block=1024):
    import jax
    import jax.numpy as jnp
    from repro.kernels.pairwise import ref
    fn = jax.jit(ref.pairwise_min_dist_ref)
    return jnp.concatenate([fn(x, centers[s:s + block])[:, None]
                            for s in range(0, centers.shape[0], block)],
                           axis=1).min(axis=1)


def check_coreset(picked, unl_keys, feats_unl, feats_lab):
    """Server coreset picks == the reference greedy loop's, or a covering
    radius within RADIUS_RTOL of it (rounding may reorder near-ties)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.pairwise import ref
    pos = {k: i for i, k in enumerate(unl_keys)}
    got = [pos[k] for k in picked]
    with jax.default_matmul_precision("highest"):
        u = jnp.asarray(feats_unl)
        mind0 = _ref_min_dist(u, jnp.asarray(feats_lab))
        step = jax.jit(ref.greedy_round_ref)
        mind, nxt, want = mind0, int(jnp.argmax(mind0)), []
        for _ in range(BUDGET):
            want.append(nxt)
            mind, n, _ = step(u, mind, u[nxt][None, :],
                              jnp.asarray([nxt], jnp.int32))
            nxt = int(n)

        def radius(sel):
            d = _ref_min_dist(u, u[jnp.asarray(sel)])
            return float(jnp.max(jnp.minimum(mind0, d)))

        r_got, r_want = radius(got), radius(want)
    same = got == want
    split = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                 None)
    rel = abs(r_got - r_want) / max(r_want, 1e-30)
    print(f"  coreset: identical_picks={same} first_divergence={split} "
          f"radius={r_got:.6g} ref_radius={r_want:.6g} rel={rel:.3g}",
          flush=True)
    if len(set(got)) != BUDGET or not (same or rel <= RADIUS_RTOL):
        raise AssertionError(f"coreset covering radius {r_got} vs "
                             f"reference {r_want} (rel {rel})")


def kernel_proof(n_unl, n_pool, d, classes):
    """The compiled kernels are in the lowered selection ops at the smoke's
    shapes: the k-center round, the warm-start fold and the lc scores."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.pairwise import ops
    from repro.kernels.uncertainty import ops as unc_ops
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    r_block = ops.autotuned_blocks(n_pool, d).r_block
    lowered = {
        "greedy_round R=1": jax.jit(ops.greedy_round).lower(
            sds((n_unl, d), f32), sds((n_unl,), f32), sds((1, d), f32),
            sds((1,), i32)),
        f"warm-start fold R={r_block}": jax.jit(ops.greedy_round).lower(
            sds((n_pool, d), f32), sds((n_pool,), f32),
            sds((r_block, d), f32), sds((r_block,), i32)),
        "lc scores": jax.jit(lambda p: unc_ops.probs_scores(p, "lc")).lower(
            sds((n_unl, classes), f32)),
    }
    for name, lo in lowered.items():
        found = "tpu_custom_call" in lo.as_text()
        print(f"  {name}: tpu_custom_call={found}", flush=True)
        if not found:
            raise AssertionError(f"{name}: no Pallas kernel in the program")


def one_chip(log, seed):
    import numpy as np
    from repro.data.synthetic import image_pool
    from repro.service.config import ALServiceConfig
    x, y = image_pool(POOL_ROWS, hw=IMAGE_HW, seed=seed)
    rng = np.random.default_rng(seed)
    with log.phase("serve resnet18 over tcp"):
        srv, rpc, cli = _serve(ALServiceConfig(model_name="resnet18",
                                               batch_size=BATCH))
    try:
        with log.phase(f"push {POOL_ROWS}x{IMAGE_HW}x{IMAGE_HW}x3"):
            keys = _push(cli, x)
            st = cli.stats()
            print(f"  embeds={st['embeds']}", flush=True)
            if st["pool"] != POOL_ROWS:
                raise AssertionError(f"pool holds {st['pool']} rows")
        with log.phase(f"label {WARM_LABELS} + train_and_eval"):
            lab = rng.choice(POOL_ROWS, WARM_LABELS, replace=False)
            cli.label([keys[i] for i in lab], y[lab].tolist())
            print(f"  train accuracy={cli.train_eval():.4f}", flush=True)
        labeled = {keys[i] for i in lab}
        unl = [k for k in keys if k not in labeled]
        with log.phase(f"query lc budget={BUDGET}"):
            lc = cli.query(BUDGET, "lc")["keys"]
        with log.phase(f"query coreset budget={BUDGET}"):
            cs = cli.query(BUDGET, "coreset")["keys"]
            st = cli.stats()
            print(f"  artifacts={st['artifacts']}", flush=True)
            print(f"  embeds={st['embeds']}", flush=True)
        with log.phase("check lc + coreset against kernels/*/ref.py"):
            feats_unl, probs_unl = _snapshot(srv, unl)
            feats_lab, _ = _snapshot(srv, [keys[i] for i in lab])
            check_lc(lc, unl, probs_unl)
            check_coreset(cs, unl, feats_unl, feats_lab)
        with log.phase("kernels in the compiled programs"):
            kernel_proof(len(unl), POOL_ROWS, feats_unl.shape[1],
                         probs_unl.shape[1])
        with log.phase("label coreset picks + retrain"):
            pos = {k: i for i, k in enumerate(keys)}
            cli.label(cs, [int(y[pos[k]]) for k in cs])
            print(f"  train accuracy={cli.train_eval():.4f}", flush=True)
        with log.phase(f"query kcg budget={BUDGET}"):
            kcg = cli.query(BUDGET, "kcg")["keys"]
            fresh = set(unl) - set(cs)
            if len(set(kcg)) != BUDGET or not set(kcg) <= fresh:
                raise AssertionError("kcg picked labeled or repeated rows")
            st = cli.stats()
            print(f"  artifacts={st['artifacts']}", flush=True)
            print(f"  embeds={st['embeds']}", flush=True)
    finally:
        cli.close()
        rpc.stop()


def four_chips(log, seed):
    import jax
    import numpy as np
    from repro.common import telemetry
    from repro.core import selection
    from repro.data.synthetic import image_pool
    from repro.service.config import ALServiceConfig
    x, y = image_pool(POOL_ROWS, hw=IMAGE_HW, seed=seed)
    lab = np.random.default_rng(seed).choice(POOL_ROWS, WARM_LABELS,
                                             replace=False)
    feat_devs, loops = set(), []
    with log.phase("serve replicas=4 and replicas=1 over tcp"):
        s4, rpc4, c4 = _serve(ALServiceConfig(
            model_name="resnet18", batch_size=BATCH, replicas=4))
        s1, rpc1, c1 = _serve(ALServiceConfig(
            model_name="resnet18", batch_size=BATCH))
    feat = s4.backend._feat

    def feat_recorded(params, batch):
        out = feat(params, batch)
        feat_devs.update(out.devices())
        return out

    s4.backend._feat = feat_recorded
    lane_loop = selection._lane_loop

    def loop_recorded(pool, mind, gidx, *args, **kwargs):
        out = lane_loop(pool, mind, gidx, *args, **kwargs)
        loops.append({
            "operands": [[s.device for s in a.addressable_shards]
                         for a in (pool, mind, gidx)],
            "outputs": [r.sharding.device_set for r in out]})
        return out

    selection._lane_loop = loop_recorded
    try:
        sel, spans = {}, {}
        for name, cli in (("replicas=4", c4), ("replicas=1", c1)):
            with log.phase(f"{name}: push, label, train, lc + coreset"):
                keys = _push(cli, x, asynchronous=True)
                cli.label([keys[i] for i in lab], y[lab].tolist())
                cli.train_eval()
                sel[name] = {"lc": cli.query(BUDGET, "lc")["keys"]}
                c0 = telemetry.snapshot()["counters"].get(
                    "select.loop_devices", 0)
                sel[name]["coreset"] = cli.query(BUDGET, "coreset")["keys"]
                spans[name] = telemetry.snapshot()["counters"].get(
                    "select.loop_devices", 0) - c0
                print(f"  workers={cli.stats()['workers']}", flush=True)
        with log.phase("compare replicas=4 with replicas=1"):
            for s in ("lc", "coreset"):
                same = sel["replicas=4"][s] == sel["replicas=1"][s]
                print(f"  {s}: replicas=4 == replicas=1: {same}", flush=True)
                if not same:
                    raise AssertionError(f"{s} selections differ")
            print(f"  feature outputs on: {sorted(str(d) for d in feat_devs)}",
                  flush=True)
            if len(feat_devs) != 4:
                raise AssertionError("feature outputs are not on 4 chips")
            lanes = s4.shard_runtime().devices
            print(f"  lane devices: {[str(d) for d in lanes]}", flush=True)
            print(f"  coreset pick loop devices: {spans}", flush=True)
            if spans != {"replicas=4": 4, "replicas=1": 1}:
                raise AssertionError("the replicas=4 pick loop must span "
                                     "four devices, replicas=1 one")
            if len(loops) != 1:
                raise AssertionError(f"{len(loops)} pick loops across "
                                     f"devices, expected the coreset's")
            for i, devs in enumerate(loops[0]["operands"]):
                print(f"  pick loop operand {i} shards on: "
                      f"{[str(d) for d in devs]}", flush=True)
                if devs != lanes:
                    raise AssertionError("each lane's shard must sit on "
                                         "its own chip")
            if set(lanes) != set(jax.devices()):
                raise AssertionError("lanes do not cover the host's chips")
            if any(d != set(lanes) for d in loops[0]["outputs"]):
                raise AssertionError("the loop's selection is not on "
                                     "every lane's chip")
    finally:
        selection._lane_loop = lane_loop
        for cli, rpc in ((c4, rpc4), (c1, rpc1)):
            cli.close()
            rpc.stop()


def lanes_direct(log, seed, rows=49_000, d=512, labeled=1_000,
                 budget=1_000):
    """A seeded ``rows`` x ``d`` pool hash-sharded four ways, one shard
    per chip, against the same pool as one shard on one chip: the warm
    coreset selection and its per-pick scores must agree bit for bit.
    Prints each loop's time once compiled."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.selection import ShardView, replica_of
    from repro.core.strategies.diversity import sharded_k_center
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((rows, d)).astype(np.float32)
    init = jnp.asarray(rng.standard_normal((labeled, d)).astype(np.float32))
    keys = [f"row-{i}" for i in range(rows)]
    devs = jax.devices()[:4]
    parts = [np.asarray([i for i, k in enumerate(keys)
                         if replica_of(k, 4) == s], np.int64)
             for s in range(4)]
    layouts = {
        "1 shard, 1 chip": [ShardView(feats=feats, probs=None,
                                      gidx=np.arange(rows))],
        "4 shards, 4 chips": [ShardView(feats=feats[g], probs=None, gidx=g,
                                        device=devs[s])
                              for s, g in enumerate(parts)]}
    got = {}
    for name, shards in layouts.items():
        with log.phase(f"warm coreset {budget} of {rows}x{d}: {name}"):
            for _ in range(2):          # the second call runs compiled
                t0 = time.perf_counter()
                sel = np.asarray(sharded_k_center(
                    jax.random.PRNGKey(seed), budget, shards,
                    init_centers=init))
                wall = time.perf_counter() - t0
            got[name] = sel
            print(f"  compiled query: {wall:.4f} s, shard rows "
                  f"{[s.n for s in shards]}", flush=True)
    same = np.array_equal(*got.values())
    print(f"  4 chips == 1 chip: {same}", flush=True)
    if not same:
        raise AssertionError("the four-chip selection differs")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the replicas=4 vs replicas=1 phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.common.device import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    log = PhaseLog(jax)
    try:
        if args.four_chips:
            four_chips(log, args.seed)
            lanes_direct(log, args.seed)
        else:
            one_chip(log, args.seed)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
