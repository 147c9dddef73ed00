"""Uncertainty-based strategies: LC, MC, RC, ES (paper Fig. 4 set).

Score conventions follow Settles' survey [46] / the paper's references:
  LC  least confidence      1 - max_c p(c)            (higher = pick)
  MC  margin confidence     -(p(1) - p(2))            (small margin = pick)
  RC  ratio confidence      p(2) / p(1)               (ratio near 1 = pick)
  ES  entropy sampling      -sum p log p

Every score runs through the fused Pallas kernel on TPU
(repro/kernels/uncertainty): one streaming pass over the class/vocab axis,
no materialized softmax — the serving hot-spot when the scorer is an LLM
with a 100k-256k vocab. Served pools carry softmax probs, which the kernel
scores as the logits ``log p``; ``scores_from_logits`` takes raw logits.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core.strategies.base import Strategy, top_k_select
from repro.kernels.uncertainty import ops as unc_ops


# per-row scores of softmax probs: the fused Pallas kernel on TPU, the
# closed forms (kernels/uncertainty/ref.py) elsewhere
lc_scores = functools.partial(unc_ops.probs_scores, kind="lc")
mc_scores = functools.partial(unc_ops.probs_scores, kind="mc")
rc_scores = functools.partial(unc_ops.probs_scores, kind="rc")
es_scores = functools.partial(unc_ops.probs_scores, kind="es")

SCORE_FNS = {"lc": lc_scores, "mc": mc_scores, "rc": rc_scores,
             "es": es_scores}


def scores_from_logits(logits, kind: str, impl: str = "auto"):
    """Fused logits->score (kernel or reference; see kernels/uncertainty)."""
    return unc_ops.uncertainty_scores(logits, kind, impl=impl)


def _make(kind: str) -> Strategy:
    def select_fn(rng, budget, *, probs):
        from repro.kernels.pairwise import ops
        ops.record_pool_rows(int(probs.shape[0]))
        return top_k_select(SCORE_FNS[kind](probs), budget)

    def sharded_fn(rng, budget, shards, *, labeled_embeddings=None,
                   executor=None, prefilter=None, state=None):
        # ``state`` (persisted k-center min-dists) accepted and ignored:
        # uncertainty scoring is stateless per row
        from repro.core import selection
        if prefilter is not None:
            # cap-gated cluster scan: bit-identical to the full scan by
            # the strictly-below stopping rule (core.prefilter)
            from repro.core import prefilter as pf
            idx, _ = pf.gated_top_k(shards, kind, budget, executor)
            return idx
        # per-shard scoring (scores are per-row, so shard slices produce the
        # exact floats of the full matrix) + partial top-k merge
        from repro.kernels.pairwise import ops

        def score(s):
            ops.record_pool_rows(s.n)
            return SCORE_FNS[kind](jnp.asarray(s.probs))

        scores = selection.replica_map(score, shards, executor)
        idx, _ = selection.replica_top_k(shards, scores, budget, executor)
        return idx

    return Strategy(kind, ("probs",), select_fn, sharded_fn)


least_confidence = _make("lc")
margin_confidence = _make("mc")
ratio_confidence = _make("rc")
entropy_sampling = _make("es")
