"""Pure-jnp oracles for fused uncertainty scoring: over logits, and the
closed forms over softmax probabilities the served strategies score."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def uncertainty_stats_ref(logits):
    """logits: (N, V) -> dict of per-row scores (fp32).

    lc = 1 - p_max; mc = -(p1 - p2); rc = p2/p1; es = entropy(softmax).
    """
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    top2 = jax.lax.top_k(lg, 2)[0]
    p1 = jnp.exp(top2[:, 0] - lse)
    p2 = jnp.exp(top2[:, 1] - lse)
    p = jax.nn.softmax(lg, axis=-1)
    es = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)), 0.0),
                  axis=-1)
    return {
        "lc": 1.0 - p1,
        "mc": -(p1 - p2),
        "rc": p2 / jnp.maximum(p1, 1e-12),
        "es": es,
    }


def uncertainty_scores_ref(logits, kind: str):
    return uncertainty_stats_ref(logits)[kind]


# floor under probabilities before a log: keeps log(0) finite, and far
# below any score difference f32 can show
TINY = 1e-30


def probs_scores_ref(probs, kind: str):
    """probs: (N, C) softmax rows -> (N,) scores (Settles' conventions):
    lc = 1 - p1; mc = -(p1 - p2); rc = p2 / p1; es = -sum p log p."""
    if kind == "lc":
        return 1.0 - jnp.max(probs, axis=-1)
    if kind == "es":
        p = jnp.clip(probs, 1e-12, 1.0)
        return -jnp.sum(p * jnp.log(p), axis=-1)
    top2 = jax.lax.top_k(probs, 2)[0]
    if kind == "mc":
        return -(top2[..., 0] - top2[..., 1])
    if kind == "rc":
        return top2[..., 1] / jnp.maximum(top2[..., 0], 1e-12)
    raise KeyError(f"unknown uncertainty score {kind!r}")
