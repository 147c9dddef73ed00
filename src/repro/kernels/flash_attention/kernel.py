"""Pallas TPU flash-attention (forward) with GQA, causal and local-window
masking.

Grid: (B, H, q_blocks, kv_blocks) — first three parallel, kv sequential.
The wrapper moves heads ahead of the sequence, (B, S, H, D) -> (B, H, S, D),
so every block is a (seq, head_dim) tile: the TPU tiling wants the last two
block dims to be (multiple of 8, whole head_dim). Online-softmax carry (m,
l, acc) lives in VMEM scratch as columns; K/V blocks are indexed at h // G
so grouped query heads share one KV stream (GQA without materializing
repeated KV). Block shapes default to (128, head_dim) tiles — MXU-aligned
for head_dim in {64, 128, 256}.

Serving-path kernel: forward only (training uses the chunked jnp attention,
which XLA differentiates; see DESIGN.md §kernels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            nk: int, qb: int, kb: int, skv: int, scale: float,
            causal: bool, window: Optional[int]):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)                  # (qb, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (kb, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qi = pl.program_id(2)
    q_pos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    k_pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = k_pos < skv
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_s[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    corr = jnp.exp(m_prev - m_cur)                       # (qb, 1)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_cur

    @pl.when(j == nk - 1)
    def _fin():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           q_block: int = 128, kv_block: int = 128,
                           interpret: bool = False):
    """q: (B,Sq,H,D); k,v: (B,Skv,KH,D) -> (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5
    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    nq = -(-Sq // qb)
    nk = -(-Skv // kb)
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))  # (B, H, S, D)
    if nq * qb != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * qb - Sq), (0, 0)))
    if nk * kb != Skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * kb - Skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * kb - Skv), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, qb=qb, kb=kb, skv=Skv, scale=scale,
                          causal=causal, window=window),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kb, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * qb, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)
