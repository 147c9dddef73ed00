"""Hybrid strategies (uncertainty x diversity) — beyond the paper's zoo.

All three hybrids ride the SAME fused Pallas substrate as pure k-center
(repro/kernels/pairwise.greedy_round): one (N, d) pool read per selected
center, with per-row weights folded into the round's argmax.

BADGE-lite: k-means++ sampling over uncertainty-scaled embeddings — the
gradient-embedding magnitude of BADGE [2] collapses to (1 - p_max) * h for
the last-layer bias-free case, which keeps the embedding dimension at d
instead of V*d (V up to 256k here). The D^2 sampling step is a weighted
fused round via the Gumbel-max trick (see ``kmeans_pp_sample``).

margin_density: weighted k-center greedy where the weight is margin
uncertainty x local density — uncertain points in dense regions win the
per-round argmax, min-dist keeps the batch spread out.

weighted_kcenter: k-center greedy with least-confidence weights (and the
Core-Set warm start when labeled embeddings are attached).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.strategies.base import (Strategy, unit_weights,
                                        unit_weights_parts)
from repro.core.strategies.uncertainty import lc_scores, mc_scores


def kmeans_pp_sample(rng, x, k: int, impl: str = "auto"):
    """k-means++ seeding AS the selection (BADGE's sampler). x: (N,d).

    D^2 sampling rides the fused greedy round: drawing
    ``idx ~ Categorical(p ∝ min_dist)`` equals
    ``argmax(min_dist * exp(gumbel))`` (Gumbel-max trick, exp is monotone),
    which is exactly the kernel's weighted argmax. Each round is therefore
    ONE (N, d) pool pass — min-dist fold, selected-row masking, and the
    next *sample* all in the same read — instead of the separate
    distance / minimum / scatter / categorical passes of the naive loop.
    """
    N, _ = x.shape
    x = x.astype(jnp.float32)
    from repro.kernels.pairwise import ops
    keys = jax.random.split(rng, k + 1)
    first = jax.random.randint(keys[0], (), 0, N).astype(jnp.int32)
    sel0 = jnp.zeros((k,), jnp.int32).at[0].set(first)
    mind0 = ops.sq_dist_to_center(x, x[first]).at[first].set(-1.0)
    # sampling weights for pick i are drawn from keys[i]; the round that
    # folds center i-1 already computes pick i's weighted argmax
    w1 = jnp.exp(jax.random.gumbel(keys[1], (N,), jnp.float32))
    nxt0 = jnp.argmax(ops.masked_weighted_score(mind0, w1)).astype(jnp.int32)

    def body(i, carry):
        mind, sel, nxt = carry
        sel = sel.at[i].set(nxt)
        w = jnp.exp(jax.random.gumbel(keys[i + 1], (N,), jnp.float32))
        mind, nxt, _ = ops.greedy_round(x, mind, x[nxt][None, :], nxt[None],
                                        weights=w, impl=impl)
        return mind, sel, nxt

    _, sel, _ = jax.lax.fori_loop(1, k, body, (mind0, sel0, nxt0))
    return sel


def _badge_select(rng, budget, *, probs, embeddings, labeled_embeddings=None):
    g = (lc_scores(probs)[:, None].astype(jnp.float32)
         * embeddings.astype(jnp.float32))
    return kmeans_pp_sample(rng, g, budget)


def density_scores(rng, embeddings, n_ref: int = 256):
    """Local density in [0, 1] (higher = denser): negated mean sq-dist to a
    *random* reference subset, min-max normalized. The subset is drawn with
    ``rng`` — NOT the first rows, which would make density depend on pool
    order — so the estimate is permutation-invariant in expectation."""
    from repro.kernels.pairwise import ops
    emb = embeddings.astype(jnp.float32)
    N = emb.shape[0]
    n_ref = min(n_ref, N)
    ridx = jax.random.choice(rng, N, (n_ref,), replace=False)
    d = ops.pairwise_sq_dists(emb, emb[ridx]).mean(-1)
    return 1.0 - (d - d.min()) / jnp.maximum(d.max() - d.min(), 1e-9)


def _margin_density_select(rng, budget, *, probs, embeddings,
                           labeled_embeddings=None):
    """Margin x local-density: prefer uncertain points in dense regions.

    Runs as a *weighted fused* k-center greedy: weight = margin x density,
    so every selection round is one pool pass and the returned batch is
    diverse instead of the top-k clump of a pure score sort."""
    from repro.core.strategies.diversity import k_center_greedy
    k_ref, k_sel = jax.random.split(rng)
    m = unit_weights(mc_scores(probs))
    dens = density_scores(k_ref, embeddings)
    w = unit_weights(m * dens)
    return k_center_greedy(k_sel, budget, embeddings, weights=w)


def _weighted_kcenter_select(rng, budget, *, probs, embeddings,
                             labeled_embeddings=None):
    """K-center greedy with least-confidence weights — the canonical
    uncertainty-weighted diversity strategy on the fused substrate."""
    from repro.core.strategies.diversity import k_center_greedy
    w = unit_weights(lc_scores(probs))
    return k_center_greedy(rng, budget, embeddings,
                           init_centers=labeled_embeddings, weights=w)


# ------------------------------------------------- replica-sharded paths --
def sharded_kmeans_pp(rng, x_list, shards, k: int, impl: str = "auto"):
    """Replica-sharded ``kmeans_pp_sample``: the per-slot Gumbel weights are
    drawn over the FULL (N,) pool from the same key schedule as the single
    path and sliced per shard by global position, so each D² draw is the
    identical categorical sample."""
    from repro.core import selection
    N = selection.replica_total(shards)
    keys = jax.random.split(rng, k + 1)
    first = int(jax.random.randint(keys[0], (), 0, N))
    mind = selection.replica_seed_min_dist(shards, x_list, first)
    sel = np.zeros((k,), np.int64)
    sel[0] = first
    # one key row per pool row: the loop's program depends on N, not on k
    keys = jnp.zeros((max(N, k) + 1,) + keys.shape[1:],
                     keys.dtype).at[:k + 1].set(keys)
    return selection.replica_greedy_select(
        shards, x_list, k, mind_list=mind, sel=sel, start=1,
        weight_for_slot=jax.tree_util.Partial(_gumbel_weights, keys),
        impl=impl)


def _gumbel_weights(keys, slot, gidxs, total):
    """Slot ``slot``'s D² draw, ``exp(gumbel(keys[slot], (total,)))``,
    gathered at the global rows ``gidxs``."""
    w = jnp.exp(jax.random.gumbel(keys[slot], (total,), jnp.float32))
    return tuple(w[g] for g in gidxs)


def _badge_sharded(rng, budget, shards, *, labeled_embeddings=None,
                   executor=None, prefilter=None, state=None):
    # prefilter accepted-and-ignored: D² sampling draws fresh Gumbel
    # weights per slot, which no distance-only centroid bound can cap.
    # state likewise: BADGE's geometry is the uncertainty-scaled gradient
    # embedding, not the raw feats the persisted min-dists were folded over
    from repro.core import selection
    g_list = selection.replica_map(
        lambda s: (lc_scores(jnp.asarray(s.probs))[:, None]
                   .astype(jnp.float32)
                   * jnp.asarray(s.feats, jnp.float32)),
        shards, executor)
    return sharded_kmeans_pp(rng, g_list, shards, budget)


def density_scores_sharded(rng, shards, executor=None, n_ref: int = 256):
    """Sharded ``density_scores``: one global reference draw + gather, then
    per-shard mean-sq-dist rows and a global min/max normalize."""
    from repro.core import selection
    from repro.core.strategies.base import global_min_max
    from repro.kernels.pairwise import ops
    N = selection.replica_total(shards)
    n_ref = min(n_ref, N)
    ridx = np.asarray(jax.random.choice(rng, N, (n_ref,), replace=False))
    ref = jnp.asarray(selection.gather_rows(shards, ridx), jnp.float32)
    d_list = selection.replica_map(
        lambda s: ops.pairwise_sq_dists(
            jnp.asarray(s.feats, jnp.float32), ref).mean(-1)
        if s.n else jnp.zeros((0,), jnp.float32),
        shards, executor)
    lo, hi = global_min_max(d_list)
    return [1.0 - (d - lo) / jnp.maximum(hi - lo, 1e-9) for d in d_list]


def _margin_density_sharded(rng, budget, shards, *, labeled_embeddings=None,
                            executor=None, prefilter=None, state=None):
    # prefilter accepted-and-ignored: weighted rounds (see sharded_k_center).
    # state accepted-and-ignored: margin_density never warm-starts
    from repro.core import selection
    from repro.core.strategies.diversity import sharded_k_center
    k_ref, k_sel = jax.random.split(rng)
    mc_list = selection.replica_map(
        lambda s: mc_scores(jnp.asarray(s.probs)), shards, executor)
    m_list = unit_weights_parts(mc_list)
    dens_list = density_scores_sharded(k_ref, shards, executor)
    w_list = unit_weights_parts([m * d for m, d in zip(m_list, dens_list)])
    return sharded_k_center(k_sel, budget, shards, weights_list=w_list,
                            executor=executor)


def _weighted_kcenter_sharded(rng, budget, shards, *,
                              labeled_embeddings=None, executor=None,
                              prefilter=None, state=None):
    # prefilter accepted-and-ignored: weighted rounds (see sharded_k_center).
    # state IS forwarded: the warm-start min-dist fold is unweighted (weights
    # only rank the per-slot argmax), so the persisted vectors are the exact
    # floats this strategy's warm fold would recompute
    from repro.core import selection
    from repro.core.strategies.diversity import sharded_k_center
    lc_list = selection.replica_map(
        lambda s: lc_scores(jnp.asarray(s.probs)), shards, executor)
    w_list = unit_weights_parts(lc_list)
    return sharded_k_center(rng, budget, shards,
                            init_centers=labeled_embeddings,
                            weights_list=w_list, executor=executor,
                            state=state)


badge = Strategy("badge", ("probs", "embeddings"), _badge_select,
                 _badge_sharded)
margin_density = Strategy("margin_density", ("probs", "embeddings"),
                          _margin_density_select, _margin_density_sharded)
weighted_kcenter = Strategy("weighted_kcenter", ("probs", "embeddings"),
                            _weighted_kcenter_select,
                            _weighted_kcenter_sharded)
