"""Jit'd wrappers for fused uncertainty scoring.

impl="auto" uses the Pallas kernel on TPU and the jnp reference elsewhere
(interpret-mode Pallas is for validation, not speed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.device import on_tpu
from repro.kernels.uncertainty import ref
from repro.kernels.uncertainty.kernel import uncertainty_stats_pallas

KINDS = ("lc", "mc", "rc", "es")


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if on_tpu() else "ref"
    return impl


@functools.partial(jax.jit, static_argnames=("kind", "impl"))
def uncertainty_scores(logits, kind: str = "lc", impl: str = "auto"):
    """logits: (N, V) -> (N,) fp32 scores (higher = more informative)."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.uncertainty_scores_ref(logits, kind)
    stats = uncertainty_stats_pallas(logits, interpret=(impl == "interpret"))
    return stats[KINDS.index(kind)]


@functools.partial(jax.jit, static_argnames=("impl",))
def uncertainty_stats(logits, impl: str = "auto"):
    """All four scores in one pass: dict of (N,) fp32."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.uncertainty_stats_ref(logits)
    stats = uncertainty_stats_pallas(logits, interpret=(impl == "interpret"))
    return {k: stats[i] for i, k in enumerate(KINDS)}


@functools.partial(jax.jit, static_argnames=("kind", "impl"))
def probs_scores(probs, kind: str = "lc", impl: str = "auto"):
    """probs: (N, C) softmax rows -> (N,) fp32 scores. ``log p`` are logits
    whose softmax is ``p``, so the kernel scores the served probs columns
    directly; the reference is the closed form on ``p``."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.probs_scores_ref(probs, kind)
    logits = jnp.log(jnp.maximum(probs.astype(jnp.float32), ref.TINY))
    return uncertainty_scores(logits, kind, impl)
