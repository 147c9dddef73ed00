"""Pallas TPU kernel: fused uncertainty scores over the vocab axis.

One streaming pass over (R_b, V_b) VMEM tiles of the logits, carrying
per-row online statistics as (R_b, 1) columns in VMEM scratch across the
sequential vocab grid axis: running max m1, runner-up m2, shifted sum-exp,
and shifted sum(l * exp(l)) — everything LC/MC/RC/ES need, with no (N, V)
softmax ever materialized in HBM. This is the AL serving hot-spot when the
scorer is an LLM (V up to 256k): arithmetic intensity is O(1) per logit, so
the kernel's job is to keep the pass memory-bound at exactly one HBM read of
the logits. The four scores leave as one (R_b, 4) block per row block.

Grid: (row_blocks, vocab_blocks); rows parallel, vocab sequential
(dimension_semantics = ("parallel", "arbitrary")).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(logits_ref, out_ref, m1, m2, se, sl, *, nv: int, v: int,
            v_block: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m1[...] = jnp.full_like(m1, NEG)
        m2[...] = jnp.full_like(m2, NEG)
        se[...] = jnp.zeros_like(se)
        sl[...] = jnp.zeros_like(sl)

    lg = logits_ref[...].astype(jnp.float32)            # (R, Vb)
    # mask the vocab-padding tail
    col = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1) + j * v_block
    lg = jnp.where(col < v, lg, NEG)

    bm1 = jnp.max(lg, axis=-1, keepdims=True)           # block max (R, 1)
    # block runner-up: max over the block with exactly one occurrence (the
    # leftmost) of the max knocked out
    first = jnp.min(jnp.where(lg == bm1, col, jnp.iinfo(jnp.int32).max),
                    axis=-1, keepdims=True)
    bm2 = jnp.max(jnp.where(col == first, NEG, lg), axis=-1, keepdims=True)

    om1, om2 = m1[...], m2[...]
    nm1 = jnp.maximum(om1, bm1)
    # new runner-up = max of remaining candidates
    nm2 = jnp.maximum(jnp.maximum(jnp.minimum(om1, bm1), om2), bm2)

    scale = jnp.exp(om1 - nm1)                          # rescale old sums
    e = jnp.exp(lg - nm1)
    e = jnp.where(col < v, e, 0.0)
    se[...] = se[...] * scale + jnp.sum(e, axis=-1, keepdims=True)
    sl[...] = sl[...] * scale + jnp.sum(e * lg, axis=-1, keepdims=True)
    m1[...] = nm1
    m2[...] = nm2

    @pl.when(j == nv - 1)
    def _fin():
        lse = m1[...] + jnp.log(jnp.maximum(se[...], 1e-30))
        p1 = jnp.exp(m1[...] - lse)
        p2 = jnp.exp(m2[...] - lse)
        ent = lse - sl[...] / jnp.maximum(se[...], 1e-30)
        # (R, 4) = [lc, mc, rc, es] columns, assembled by lane select
        k = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(
            k == 0, 1.0 - p1, jnp.where(
                k == 1, -(p1 - p2), jnp.where(
                    k == 2, p2 / jnp.maximum(p1, 1e-12), ent)))


def uncertainty_stats_pallas(logits, *, row_block: int = 256,
                             v_block: int = 2048, interpret: bool = False):
    """logits: (N, V) -> (4, N) fp32 rows = [lc, mc, rc, es]."""
    N, V = logits.shape
    if N == 0:                  # an empty shard: no rows, no grid
        return jnp.zeros((4, 0), jnp.float32)
    rb = min(row_block, N)
    vb = min(v_block, V)
    nr = -(-N // rb)
    nv = -(-V // vb)
    Np, Vp = nr * rb, nv * vb
    if (Np, Vp) != (N, V):
        logits = jnp.pad(logits, ((0, Np - N), (0, Vp - V)),
                         constant_values=NEG)
    out = pl.pallas_call(
        functools.partial(_kernel, nv=nv, v=V, v_block=vb),
        grid=(nr, nv),
        in_specs=[pl.BlockSpec((rb, vb), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((rb, 4), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, 4), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rb, 1), jnp.float32) for _ in range(4)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits)
    return out[:N].T
