"""The plain reference of the selections, written from the semantics the
service states and importing nothing of the program: k-center greedy from
the labeled centers, each pick the unlabeled row farthest (squared L2,
float32 at ``Precision.HIGHEST``) from every labeled row and every
earlier pick. A scorer's feature reference is ``bench/references/<scorer>.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def _min_dist(x, centers):
    x2 = jnp.sum(x * x, axis=1)
    c2 = jnp.sum(centers * centers, axis=1)
    d = x2[:, None] + c2[None, :] - 2.0 * jnp.dot(x, centers.T,
                                                   precision=HIGHEST)
    return jnp.min(jnp.maximum(d, 0.0), axis=1)


def min_dist(x, centers, block: int = 2048):
    """(N,) squared distance of each row of ``x`` to its nearest center,
    ``block`` centers at a time (the last block padded with a copy of the
    first center, which leaves the minimum unchanged)."""
    x = jnp.asarray(x)
    out = None
    m = centers.shape[0]
    for s in range(0, m, block):
        c = centers[s:s + block]
        if c.shape[0] < block:
            c = np.concatenate([c, np.repeat(centers[:1], block - c.shape[0],
                                             axis=0)])
        part = _min_dist(x, jnp.asarray(c))
        out = part if out is None else jnp.minimum(out, part)
    return out


@jax.jit
def _greedy_gaps(x, mind, picks):
    """Teacher-forced greedy: for each pick in order, the share by which
    its distance to the chosen set lies below the farthest row's, then the
    pick joins the set. Rows already chosen carry ``mind = -inf``."""
    def step(mind, p):
        best = jnp.max(mind)
        gap = (best - mind[p]) / jnp.maximum(best, 1e-30)
        d = jnp.sum(jnp.square(x - x[p][None, :]), axis=1)
        mind = jnp.minimum(mind, d).at[p].set(-jnp.inf)
        return mind, gap

    _, gaps = jax.lax.scan(step, mind, picks)
    return gaps


def greedy_gaps(feats: np.ndarray, labeled: np.ndarray,
                picks: np.ndarray) -> np.ndarray:
    """Per-pick relative gaps of a k-center selection over the whole pool
    ``feats``: ``labeled`` are the pool rows labeled before the query,
    ``picks`` the pool rows the query returned, in order. A pick that
    repeats a row or hits a labeled row reads a gap of at least 1."""
    x = jnp.asarray(feats)
    mind = min_dist(x, feats[labeled])
    mind = mind.at[jnp.asarray(labeled)].set(-jnp.inf)
    gaps = np.asarray(_greedy_gaps(x, mind, jnp.asarray(picks, jnp.int32)))
    return np.where(np.isfinite(gaps), gaps, np.inf)
