"""Diversity-based strategies: KCG, Core-Set, DBAL (+ Random baseline).

K-center greedy is the paper's heaviest strategy (Fig. 4b: lowest
throughput); every greedy round is ONE fused Pallas pass
(repro/kernels/pairwise.greedy_round_pallas): the pool is read once per
selected center, with the min-dist update, selected-index masking, and the
next argmax folded into that read. The Core-Set warm start folds labeled
centers in chunks via the same kernel (ops.warm_start_min_dist).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import telemetry
from repro.core.strategies.base import Strategy, unit_weights
from repro.core.strategies.uncertainty import lc_scores


def k_center_greedy(rng, budget: int, embeddings, init_centers=None,
                    impl: str = "auto", weights=None):
    """2-approx k-center: repeatedly take the point farthest from all
    centers. init_centers: (M,d) existing (labeled) centers or None.

    ``weights`` (optional (N,) non-negative f32) turns each round into the
    *weighted* fused pass: the next center maximizes ``min_dist * weight``
    while the min-dist fold itself stays unweighted — uncertainty decides
    among the far points, distance still defines "far". ``weights=None``
    takes the identical unweighted path as before (regression anchor)."""
    from repro.kernels.pairwise import ops
    N, _ = embeddings.shape
    emb = embeddings.astype(jnp.float32)
    w = None if weights is None else weights.astype(jnp.float32)
    selected = jnp.zeros((budget,), jnp.int32)
    start = 0
    if init_centers is not None and init_centers.shape[0] > 0:
        mindist = ops.warm_start_min_dist(emb,
                                          init_centers.astype(jnp.float32),
                                          impl=impl)
    else:
        # the seed IS the first returned center (otherwise its cluster can
        # be silently dropped from the returned set)
        first = jax.random.randint(rng, (), 0, N).astype(jnp.int32)
        selected = selected.at[0].set(first)
        mindist = ops.sq_dist_to_center(emb, emb[first]).at[first].set(-1.0)
        start = 1
    if w is None:
        nxt = jnp.argmax(mindist).astype(jnp.int32)
    else:
        # same masked-score rule as the kernel: selected rows never win
        nxt = jnp.argmax(ops.masked_weighted_score(mindist, w)).astype(
            jnp.int32)

    def body(i, carry):
        mindist, selected, nxt = carry
        selected = selected.at[i].set(nxt)
        # one fused pool pass: fold the new center in, mask it, get the
        # following round's (weighted) argmax
        mindist, nxt, _ = ops.greedy_round(emb, mindist, emb[nxt][None, :],
                                           nxt[None], weights=w, impl=impl)
        return mindist, selected, nxt

    _, selected, _ = jax.lax.fori_loop(start, budget, body,
                                       (mindist, selected, nxt))
    return selected


def _kcg_select(rng, budget, *, embeddings, labeled_embeddings=None):
    return k_center_greedy(rng, budget, embeddings, init_centers=None)


def _coreset_select(rng, budget, *, embeddings, labeled_embeddings=None):
    return k_center_greedy(rng, budget, embeddings,
                           init_centers=labeled_embeddings)


def _kmeans(rng, x, k: int, iters: int = 10, weights=None):
    """Weighted Lloyd's with kmeans++-style seeding. x: (N,d) f32."""
    from repro.kernels.pairwise import ops
    N, d = x.shape
    w = jnp.ones((N,), jnp.float32) if weights is None else weights
    keys = jax.random.split(rng, 2)
    # seeding: weighted random first, then farthest-point (cheap ++ variant).
    # The running min-dist only ever sees FILLED centroid rows — recomputing
    # against the whole (k, d) buffer would let zero-initialized rows act as
    # phantom centers at the origin.
    first = jax.random.categorical(keys[0], jnp.log(w + 1e-9))
    cent0 = jnp.zeros((k, d), jnp.float32).at[0].set(x[first])
    mind0 = ops.sq_dist_to_center(x, x[first])
    no_mask = jnp.full((1,), -1, jnp.int32)
    nxt0 = jnp.argmax(mind0 * w).astype(jnp.int32)

    def seed_body(i, carry):
        cents, mind, nxt = carry
        cents = cents.at[i].set(x[nxt])
        mind, nxt, _ = ops.greedy_round(x, mind, x[nxt][None, :], no_mask,
                                        weights=w)
        return cents, mind, nxt

    cents, _, _ = jax.lax.fori_loop(1, k, seed_body, (cent0, mind0, nxt0))

    def lloyd(_, cents):
        assign = ops.pairwise_argmin(x, cents)           # (N,)
        one = jax.nn.one_hot(assign, k, dtype=jnp.float32) * w[:, None]
        num = one.T @ x                                   # (k,d)
        den = jnp.maximum(one.sum(0)[:, None], 1e-9)
        return num / den

    cents = jax.lax.fori_loop(0, iters, lloyd, cents)
    return cents


def _dbal_match(rng, budget: int, x, top_scores, top_idx, match_weights=None):
    """DBAL's tail shared by the single-pool and sharded paths: weighted
    k-means over the prefiltered subset ``x``, then match each centroid to
    a unique pool point. With ``match_weights`` (per-row of ``x``,
    non-negative) the matching cost is ``d2 / weight`` — the min-problem
    mirror of the fused round's ``min_dist * weight`` argmax, so uncertain
    points win centroid ties instead of being coin-flipped away."""
    from repro.kernels.pairwise import ops
    m = x.shape[0]
    cents = _kmeans(rng, x, budget, weights=jnp.maximum(top_scores, 1e-6))
    d2 = ops.pairwise_sq_dists(cents, x)                  # (k, m)
    cost = (d2 if match_weights is None
            else d2 / jnp.maximum(match_weights, 1e-6)[None, :])

    def body(i, carry):
        taken_mask, sel = carry
        row = jnp.where(taken_mask, jnp.inf, cost[i])
        j = jnp.argmin(row)
        return taken_mask.at[j].set(True), sel.at[i].set(top_idx[j])

    sel = jnp.zeros((budget,), jnp.int32)
    _, sel = jax.lax.fori_loop(0, budget, body,
                               (jnp.zeros((m,), bool), sel))
    return sel


def diverse_mini_batch(rng, budget: int, probs, embeddings, beta: int = 10,
                       weights=None):
    """DBAL [55]: prefilter beta*budget by LC, weighted k-means, then pick
    the nearest pool point to each centroid (unique via masking).

    ``weights`` (optional (N,) over the pool) threads into the
    centroid-matching step (``weights=None`` keeps the unweighted match)."""
    scores = lc_scores(probs)
    m = min(beta * budget, scores.shape[0])
    top_scores, top_idx = jax.lax.top_k(scores, m)
    x = embeddings[top_idx].astype(jnp.float32)
    mw = None if weights is None else weights[top_idx]
    return _dbal_match(rng, budget, x, top_scores, top_idx, match_weights=mw)


def _dbal_select(rng, budget, *, probs, embeddings, labeled_embeddings=None):
    # centroid matching rides the same LC weighting as the fused hybrids
    # (ROADMAP PR-2 open item): among near-equidistant candidates the more
    # uncertain point is matched first
    return diverse_mini_batch(rng, budget, probs, embeddings,
                              weights=unit_weights(lc_scores(probs)))


def _random_select(rng, budget, *, probs=None):
    n = probs.shape[0]
    return jax.random.permutation(rng, n)[:budget].astype(jnp.int32)


# ------------------------------------------------- replica-sharded paths --
def sharded_k_center(rng, budget: int, shards, *, init_centers=None,
                     weights_list=None, executor=None, impl: str = "auto",
                     prefilter=None, state=None):
    """Replica-sharded ``k_center_greedy``: per-shard fused rounds +
    cross-shard (value, global index) merges — selections bit-identical to
    the single-pool path for every shard count (see core.selection).

    ``prefilter`` routes the UNWEIGHTED geometry (kcg/coreset) through the
    centroid-gated engine (core.prefilter) when any shard carries a
    summary; weighted rounds rank by ``min_dist * weight``, which the
    distance-only triangle bound cannot cap, so they always take the full
    path.

    ``state`` (a ``core.selection.KCenterState`` prepared by the session's
    ``KCenterStateCache``) replaces the warm-start fold on the warm path:
    the persisted pool-level min-dists are gathered down to the view rows
    instead of streaming every row against every labeled center. Same
    floats (slice-invariant distances + exact min fold), O(delta) cost.
    Ignored on the seeded path — there is no warm fold to save."""
    from repro.core import selection
    from repro.kernels.pairwise import ops
    warm = init_centers is not None and init_centers.shape[0] > 0
    if prefilter is not None and weights_list is None \
            and any(s.summary is not None for s in shards):
        from repro.core import prefilter as pf
        return pf.gated_greedy_select(
            rng, budget, shards, init_centers=init_centers,
            slack=prefilter.slack, executor=executor, impl=impl,
            state=state if warm else None)
    N = selection.replica_total(shards)
    emb_list = [telemetry.h2d(s.feats, jnp.float32) for s in shards]
    sel = np.zeros((budget,), np.int64)
    if weights_list is None:
        def weight_for_slot(slot, i):
            return None
    else:
        def weight_for_slot(slot, i):
            return weights_list[i]
    capture = None
    if warm:
        if state is not None:
            mind = state.view_minds(shards)
            capture = state.capture
        else:
            init = telemetry.h2d(init_centers, jnp.float32)
            mind = [ops.warm_start_min_dist(emb_list[i], init, impl=impl)
                    if s.n else None for i, s in enumerate(shards)]
        start = 0
    else:
        # the random seed IS the first returned center, as in the single
        # path (same rng call, same N -> same draw)
        first = int(jax.random.randint(rng, (), 0, N))
        mind = selection.replica_seed_min_dist(shards, emb_list, first)
        sel[0] = first
        start = 1
    return selection.replica_greedy_select(
        shards, emb_list, budget, mind_list=mind, sel=sel, start=start,
        weight_for_slot=weight_for_slot, executor=executor, impl=impl,
        capture=capture)


def _kcg_sharded(rng, budget, shards, *, labeled_embeddings=None,
                 executor=None, prefilter=None, state=None):
    # kcg never warm-starts (no init centers), so the persisted min-dist
    # state has nothing to save it; accepted and ignored
    return sharded_k_center(rng, budget, shards, executor=executor,
                            prefilter=prefilter)


def _coreset_sharded(rng, budget, shards, *, labeled_embeddings=None,
                     executor=None, prefilter=None, state=None):
    return sharded_k_center(rng, budget, shards,
                            init_centers=labeled_embeddings,
                            executor=executor, prefilter=prefilter,
                            state=state)


def _dbal_sharded(rng, budget, shards, *, labeled_embeddings=None,
                  executor=None, beta: int = 10, prefilter=None, state=None):
    """Sharded DBAL: shards propose their local LC top-(beta*budget), the
    merged prefilter subset is gathered to the coordinator, and the k-means
    + weighted matching tail is the exact single-pool code over it."""
    from repro.core import selection
    from repro.core.strategies.base import unit_weights_parts
    scores = selection.replica_map(
        lambda s: lc_scores(jnp.asarray(s.probs)), shards, executor)
    N = selection.replica_total(shards)
    m = min(beta * budget, N)
    top_idx, top_scores = selection.replica_top_k(shards, scores, m,
                                                  executor)
    x = jnp.asarray(selection.gather_rows(shards, top_idx), jnp.float32)
    mw = jnp.asarray(selection.gather_rows(
        shards, top_idx, arrays=unit_weights_parts(scores)), jnp.float32)
    return np.asarray(_dbal_match(rng, budget, x, jnp.asarray(top_scores),
                                  jnp.asarray(top_idx), match_weights=mw))


def _random_sharded(rng, budget, shards, *, labeled_embeddings=None,
                    executor=None, prefilter=None, state=None):
    from repro.core import selection
    n = selection.replica_total(shards)
    return np.asarray(jax.random.permutation(rng, n)[:budget])


k_center = Strategy("kcg", ("embeddings",), _kcg_select, _kcg_sharded)
core_set = Strategy("coreset", ("embeddings",), _coreset_select,
                    _coreset_sharded)
dbal = Strategy("dbal", ("probs", "embeddings"), _dbal_select, _dbal_sharded)
random_sampling = Strategy("random", ("probs",), _random_select,
                           _random_sharded)
