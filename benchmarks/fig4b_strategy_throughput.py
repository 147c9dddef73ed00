"""Paper Fig. 4b — selection throughput (images/s through the query path)
per strategy; uncertainty strategies are near-free while Core-Set's greedy
min-dist loop is the heavy one, matching the paper's ordering.

``run_micro`` is the fused-vs-unfused greedy-selection microbenchmark: it
drives k-center rounds from Python under ``ops.track_ops()`` so the HBM-pass
accounting can verify the fused round costs exactly ONE (N, d) pool read per
selected center, and that fused/unfused pick identical centers."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import make_pool, make_server, row

STRATEGIES = ["random", "lc", "mc", "rc", "es", "kcg", "coreset", "dbal",
              "badge", "margin_density", "weighted_kcenter"]

MICRO_N, MICRO_D, MICRO_B = 4096, 64, 64


def _greedy_select(x, budget, round_fn, weights=None):
    """Seed with row 0, then ``budget - 1`` greedy rounds driven from
    Python (so op accounting sees every round)."""
    import jax.numpy as jnp
    from repro.kernels.pairwise import ops
    mind = ops.sq_dist_to_center(x, x[0]).at[0].set(-1.0)
    sel = [0]
    score = (mind if weights is None
             else ops.masked_weighted_score(mind, weights))
    nxt = jnp.argmax(score).astype(jnp.int32)
    for _ in range(budget - 1):
        sel.append(int(nxt))
        mind, nxt, _ = round_fn(x, mind, nxt)
    return sel


def run_micro() -> list:
    import jax.numpy as jnp
    from repro.kernels.pairwise import ops

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(MICRO_N, MICRO_D)), jnp.float32)

    def fused(x, mind, i):
        return ops.greedy_round(x, mind, x[i][None, :], i[None])

    def unfused(x, mind, i):
        return ops.greedy_round_unfused(x, mind, x[i], i)

    out = []
    sels, timings, reads = {}, {}, {}
    for name, fn in (("fused", fused), ("unfused", unfused)):
        _greedy_select(x, MICRO_B, fn)            # warm up jits
        with ops.track_ops() as stats:
            t0 = time.perf_counter()
            sels[name] = _greedy_select(x, MICRO_B, fn)
            timings[name] = time.perf_counter() - t0
        reads[name] = dict(stats)

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    match = sum(a == b for a, b in zip(sels["fused"], sels["unfused"]))
    if not on_tpu and sels["fused"] != sels["unfused"]:
        # CPU ref paths share the exact distance formula -> bit parity
        raise AssertionError("fused selection diverged from unfused: "
                             f"{sels['fused'][:8]} vs {sels['unfused'][:8]}")
    if match < 0.95 * MICRO_B:
        # TPU: kernel uses the matmul identity, the unfused baseline the
        # broadcast diff — allow ulp-level argmax flips, not divergence
        raise AssertionError(f"fused/unfused selections diverged: "
                             f"{match}/{MICRO_B} match")
    rpc = reads["fused"]["embedding_reads"] / MICRO_B
    if rpc != 1.0:
        raise AssertionError(
            "fused greedy round must read the pool exactly once per center, "
            f"got {rpc:.2f}")

    for name in ("fused", "unfused"):
        st = reads[name]
        out.append(row(
            f"fig4b_micro/greedy_{name}", timings[name] * 1e6 / MICRO_B,
            f"emb_reads_per_center={st['embedding_reads'] / MICRO_B:.2f}"
            f"|vector_streams={st['vector_streams']}"
            f"|hbm_mb={st['hbm_bytes'] / 1e6:.1f}"))
    # wall-clock on the CPU ref impl is dispatch-bound; the HBM-pass ledger
    # above is the tracked metric (the fusion win is the TPU Pallas path)
    out.append(row("fig4b_micro/speedup", 0.0,
                   f"wall_x={timings['unfused'] / timings['fused']:.2f}"
                   f"|hbm_mb_saved="
                   f"{(reads['unfused']['hbm_bytes'] - reads['fused']['hbm_bytes']) / 1e6:.1f}"
                   f"|parity={match}/{MICRO_B}"))

    # Weighted hybrid round: the SAME fused pass with per-row uncertainty
    # weights (the margin_density / weighted_kcenter / BADGE substrate) —
    # must also cost exactly ONE pool read per selected center.
    w = jnp.asarray(rng.uniform(0.05, 1.0, size=(MICRO_N,)), jnp.float32)

    def weighted(x, mind, i):
        return ops.greedy_round(x, mind, x[i][None, :], i[None], weights=w)

    _greedy_select(x, MICRO_B, weighted, weights=w)        # warm up jits
    with ops.track_ops() as stats:
        t0 = time.perf_counter()
        sel_w = _greedy_select(x, MICRO_B, weighted, weights=w)
        dt_w = time.perf_counter() - t0
        st_w = dict(stats)
    wrpc = st_w["embedding_reads"] / MICRO_B
    if wrpc != 1.0:
        raise AssertionError(
            "weighted hybrid round must read the pool exactly once per "
            f"center, got {wrpc:.2f}")
    if len(set(sel_w)) != MICRO_B:
        raise AssertionError("weighted selections are not unique")
    out.append(row(
        f"fig4b_micro/greedy_weighted", dt_w * 1e6 / MICRO_B,
        f"emb_reads_per_center={wrpc:.2f}"
        f"|vector_streams={st_w['vector_streams']}"
        f"|hbm_mb={st_w['hbm_bytes'] / 1e6:.1f}"))

    # Autotuned launch blocks for this pool shape (what ops.greedy_round /
    # warm_start_min_dist use when n_block / r_block are left unset).
    ch = ops.autotuned_blocks(MICRO_N, MICRO_D, jnp.float32)
    out.append(row("fig4b_micro/autotune", 0.0,
                   f"n_block={ch.n_block}|r_block={ch.r_block}"
                   f"|round_hbm_mb={ch.hbm_bytes / 1e6:.2f}"))

    # Core-Set warm start: M centers fold into ceil(M / r_block) pool reads
    M, RB = 512, ch.r_block
    cen = jnp.asarray(rng.normal(size=(M, MICRO_D)), jnp.float32)
    ops.warm_start_min_dist(x, cen, r_block=RB)   # warm up
    with ops.track_ops() as stats:
        t0 = time.perf_counter()
        ops.warm_start_min_dist(x, cen, r_block=RB).block_until_ready()
        dt = time.perf_counter() - t0
        st = dict(stats)
    out.append(row("fig4b_micro/warm_start", dt * 1e6,
                   f"emb_reads={st['embedding_reads']}"
                   f"|centers={M}|r_block={RB}"))
    return out


def run() -> list:
    X, Y, EX, EY = make_pool()
    srv, key2y = make_server(X, Y, EX, EY)
    out = []
    for strategy in STRATEGIES:
        srv.query(budget=100, strategy=strategy)          # warm up jits
        t0 = time.perf_counter()
        reps = 3
        for r in range(reps):
            srv.query(budget=100, strategy=strategy, rng_seed=r)
        dt = (time.perf_counter() - t0) / reps
        thr = len(X) / dt
        out.append(row(f"fig4b/{strategy}", dt * 1e6,
                       f"throughput_img_s={thr:.0f}"))
    return out
