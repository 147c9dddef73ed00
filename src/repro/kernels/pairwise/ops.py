"""Wrappers for pairwise distance reductions and fused greedy-selection
rounds (Pallas kernel on TPU, jnp ref elsewhere).

Besides impl dispatch ("auto" / "ref" / "interpret" / "pallas"), this layer
does HBM-pass accounting: inside ``track_ops()`` every wrapper records how
many full (N, d) embedding-pool reads and full (N,) vector streams it
issues, so benchmarks can verify the fused greedy round really costs one
pool read per selected center (see kernel.py for the per-round ledger).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.device import on_tpu
from repro.kernels.pairwise import autotune, ref
from repro.kernels.pairwise.kernel import (BIG, greedy_layout,
                                           greedy_round_pallas,
                                           pairwise_min_argmin_pallas)


# ------------------------------------------------------- op accounting ----
# ``pool_rows`` counts POOL ROWS TOUCHED: rows whose feature vector (or
# probs row) a selection pass actually read/scored. The centroid prefilter's
# ≥10x claim is stated in these units — a gated pass records only the rows
# of blocks whose centroid survived the bound check.
_STATS = {"embedding_reads": 0, "vector_streams": 0, "hbm_bytes": 0,
          "pool_rows": 0}
_TRACKING = [False]


def reset_op_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def op_stats() -> dict:
    return dict(_STATS)


@contextlib.contextmanager
def track_ops():
    """Count embedding-pool reads / vector streams issued while active.

    Only Python-level calls are counted (ops invoked from inside a traced
    ``fori_loop`` body trace once) — drive rounds from a Python loop when
    accounting, as the microbenchmark does.
    """
    reset_op_stats()
    _TRACKING[0] = True
    try:
        yield _STATS
    finally:
        _TRACKING[0] = False


def _record(x, emb_reads: int = 0, vec_streams: int = 0) -> None:
    if not _TRACKING[0]:
        return
    n, d = x.shape
    _STATS["embedding_reads"] += emb_reads
    _STATS["vector_streams"] += vec_streams
    _STATS["hbm_bytes"] += 4 * (emb_reads * n * d + vec_streams * n)
    _STATS["pool_rows"] += emb_reads * n


def record_pool_rows(n: int) -> None:
    """Explicit pool-rows-touched tally for passes that do not flow through
    an (N, d) wrapper here (uncertainty scoring over probs rows, gated
    cluster scans)."""
    if _TRACKING[0]:
        _STATS["pool_rows"] += int(n)


def record_greedy_rounds(x, rounds: int) -> None:
    """Account ``rounds`` fused rounds over the pool ``x`` that a device
    loop ran (its trace calls ``greedy_round_padded`` once)."""
    _record(x, emb_reads=rounds, vec_streams=2 * rounds)


# ------------------------------------------------- pairwise reductions ----
@functools.partial(jax.jit, static_argnames=("impl",))
def _pairwise_min_and_argmin(x, c, impl: str):
    if impl == "auto":
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return ref.pairwise_min_and_argmin_ref(x, c)
    return pairwise_min_argmin_pallas(x, c, interpret=(impl == "interpret"))


def pairwise_min_and_argmin(x, c, impl: str = "auto"):
    """Both (min_d (N,), argmin (N,)) from ONE kernel launch — call-sites
    needing the pair must not pay two pool passes."""
    _record(x, emb_reads=1, vec_streams=2)
    return _pairwise_min_and_argmin(x, c, impl)


def pairwise_min_dist(x, c, impl: str = "auto"):
    return pairwise_min_and_argmin(x, c, impl)[0]


def pairwise_argmin(x, c, impl: str = "auto"):
    return pairwise_min_and_argmin(x, c, impl)[1]


@jax.jit
def _pairwise_sq_dists(x, c):
    return ref.pairwise_sq_dists_ref(x, c)


def pairwise_sq_dists(x, c):
    """Full (N, M) matrix — only for small M (DBAL centroid matching)."""
    _record(x, emb_reads=1)
    return _pairwise_sq_dists(x, c)


@jax.jit
def _sq_dist_to_center(x, center):
    with jax.named_scope("alaas.sq_dist_to_center"):
        diff = x.astype(jnp.float32) - center.astype(jnp.float32)[None, :]
        return jnp.sum(diff * diff, axis=-1)


def sq_dist_to_center(x, center):
    _record(x, emb_reads=1, vec_streams=1)
    return _sq_dist_to_center(x, center)


# ---------------------------------------------- fused greedy selection ----
@functools.partial(jax.jit, static_argnames=("impl", "n_block"))
def _greedy_round(x, mind, centers, sel_idx, weights, impl: str,
                  n_block: int):
    # the scope names the kernel's op in a device trace
    # (``%alaas._greedy_round.1 = ... custom-call(...)``)
    with jax.named_scope("alaas._greedy_round"):
        if impl == "auto":
            impl = "pallas" if on_tpu() else "ref"
        if impl == "ref":
            return ref.greedy_round_ref(x, mind, centers, sel_idx, weights)
        return greedy_round_pallas(x, mind, centers, sel_idx, weights,
                                   n_block=n_block,
                                   interpret=(impl == "interpret"))


def autotuned_blocks(n: int, d: int, dtype=jnp.float32):
    """The autotuner's cached (n_block, r_block) winner for this shape."""
    return autotune.autotune_blocks(n, d, dtype)


def masked_weighted_score(mind, weights=None):
    """Host-side mirror of the fused round's argmax score rule: selected
    rows (mind < 0) pin to -BIG BEFORE the weight multiply. Every pre-loop
    argmax must use this, never re-derive it — drifting from the kernel's
    in-round rule is how masked rows leak back into selections."""
    score = mind if weights is None else mind * weights
    return jnp.where(mind < 0.0, -BIG, score)


def greedy_round(x, mind, centers, sel_idx, weights=None, impl: str = "auto",
                 n_block: int | None = None):
    """One fused greedy round: one (N, d) pool read folds the (R, d) queued
    ``centers`` into ``mind``, masks ``sel_idx``, and returns the next
    (weighted) farthest point. -> (new_mind, next_idx, next_score).
    ``n_block=None`` uses the autotuned block for (N, d, dtype)."""
    if sel_idx.shape[0] != centers.shape[0]:
        # enforce the contract on EVERY dispatch path (the ref oracle would
        # otherwise silently leave queued centers unmasked on CPU)
        raise ValueError(
            f"sel_idx must mask exactly the queued centers: got "
            f"{sel_idx.shape[0]} indices for {centers.shape[0]} centers")
    if n_block is None:
        n_block = autotune.autotune_blocks(x.shape[0], x.shape[1],
                                           x.dtype).n_block
    _record(x, emb_reads=1, vec_streams=2)
    return _greedy_round(x, mind, centers, sel_idx, weights, impl, n_block)


def greedy_round_padded(xp, mind, centers, sel_idx, weights, *, n: int,
                        n_block: int, impl: str = "auto"):
    """``greedy_round`` on operands padded once at ``greedy_layout(n,
    n_block)`` — xp (Np, d), mind and weights (1, Np) rows — for loops
    that trace many rounds over one pool. Returns ``(new_mind (1, Np),
    next_idx, next_score)``; rows ``n:`` never win and their min-dist is
    not read. Shards of different lengths share one shape by carrying -1
    min-dists past their own length (selected rows never win either).
    Call it inside a trace: it is not jitted and records no op
    accounting."""
    with jax.named_scope("alaas._greedy_round"):
        if impl == "auto":
            impl = "pallas" if on_tpu() else "ref"
        if impl != "ref":
            return greedy_round_pallas(xp, mind, centers, sel_idx, weights,
                                       n_block=n_block, n=n,
                                       interpret=(impl == "interpret"))
        nm, nxt, score = ref.greedy_round_ref(
            xp[:n], mind[0, :n], centers, sel_idx,
            None if weights is None else weights[0, :n])
        return jnp.pad(nm, (0, xp.shape[0] - n))[None, :], nxt, score


@jax.jit
def _greedy_round_unfused(x, mind, center, sel_idx):
    d = _sq_dist_to_center(x, center)
    nm = jnp.minimum(mind, d)
    nm = nm.at[sel_idx].set(-1.0)
    nxt = jnp.argmax(nm).astype(jnp.int32)
    return nm, nxt, nm[nxt]


def greedy_round_unfused(x, mind, center, sel_idx):
    """The pre-fusion round (distance pass, minimum pass, scatter, argmax
    pass as separate XLA ops) — kept as the microbenchmark baseline."""
    _record(x, emb_reads=1, vec_streams=6)
    return _greedy_round_unfused(x, mind, center, sel_idx)


@functools.partial(jax.jit, static_argnames=("impl", "n_block"))
def _gated_greedy_round(x, mind, centers, block_live, block_pending,
                        weights, impl: str, n_block: int):
    if impl == "auto":
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return ref.gated_greedy_round_ref(x, mind, centers, block_live,
                                          block_pending, weights,
                                          n_block=n_block)
    from repro.kernels.pairwise.kernel import gated_greedy_round_pallas
    return gated_greedy_round_pallas(x, mind, centers, block_live,
                                     block_pending, weights, n_block=n_block,
                                     interpret=(impl == "interpret"))


def gated_greedy_round(x, mind, centers, block_live, block_pending,
                       weights=None, impl: str = "auto", n_block: int = 256):
    """The BLOCK-MASKED round variant behind the centroid prefilter.

    Folds queued ``centers`` (R, d) into ``mind`` for LIVE row blocks only:
    block ``b`` (rows ``[b*n_block, (b+1)*n_block)``) is touched iff
    ``block_live[b]``, and folds only centers ``[block_pending[b]:R)`` —
    blocks skipped in earlier rounds catch up on the centers they missed
    when their centroid bound finally fails. Dead blocks pass ``mind``
    through untouched and emit -BIG partials, so the returned argmax ranges
    over live rows only. Winner masking stays host-side (set the winner's
    ``mind`` slot to -1.0): the caller owns per-block center bookkeeping,
    so it owns row masking too.

    Returns ``(new_mind, next_idx, next_score)`` like ``greedy_round``.
    Accounting: only live-block rows count as pool rows touched.
    """
    nb = int(n_block)
    N = x.shape[0]
    nn = -(-N // min(nb, max(N, 1)))
    live = np.asarray(block_live)
    if live.shape[0] != nn:
        raise ValueError(f"block_live has {live.shape[0]} entries for "
                         f"{nn} blocks of {nb} rows over {N}")
    if _TRACKING[0]:
        rows = int(sum(min(nb, N - b * nb) for b in np.nonzero(live)[0]))
        _STATS["pool_rows"] += rows
        _STATS["embedding_reads"] += 1 if rows else 0
        _STATS["vector_streams"] += 2
        _STATS["hbm_bytes"] += 4 * (rows * x.shape[1] + 2 * N)
    return _gated_greedy_round(x, mind, centers,
                               jnp.asarray(live, jnp.int32),
                               jnp.asarray(block_pending, jnp.int32),
                               weights, impl, nb)


def warm_start_min_dist(x, centers, impl: str = "auto",
                        r_block: int | None = None):
    """Min sq-dist from every pool row to ANY of (M, d) ``centers`` —
    the Core-Set warm start. Folds up to ``r_block`` centers per fused
    pass: ceil(M / r_block) pool reads instead of one per center.
    ``r_block=None`` uses the autotuned block for (N, d, dtype)."""
    if r_block is None:
        r_block = autotune.autotune_blocks(x.shape[0], x.shape[1],
                                           x.dtype).r_block
    N = x.shape[0]
    M = centers.shape[0]
    mind = jnp.full((N,), BIG, jnp.float32)
    for s in range(0, M, r_block):
        chunk = centers[s:s + r_block]
        mind = greedy_round(x, mind, chunk,
                            jnp.full((chunk.shape[0],), -1, jnp.int32),
                            impl=impl)[0]
    return mind
