"""Pallas TPU decode attention (flash-decode style): one query token against
a long KV cache, KV-block sequential with online softmax, valid-length
masking via scalar prefetch.

Grid: (B, KH, kv_blocks). The G grouped query heads of each KV head are
processed together as the (G, D) left operand of the MXU dots — this turns
GQA decode into dense (G x D) @ (D x kb) matmuls instead of G vector-matrix
products, the standard v5e trick for batch-1-friendly decode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            nk: int, kb: int, scale: float, window: Optional[int]):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    cur_len = len_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                  # (G, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)            # (kb, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = k_pos < cur_len
    if window is not None:
        ok &= k_pos > cur_len - 1 - window
    s = jnp.where(ok, s, NEG)

    m_prev = m_s[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_cur[:, None])
    corr = jnp.exp(m_prev - m_cur)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1)
    acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_cur

    @pl.when(j == nk - 1)
    def _fin():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, cur_len, *,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            kv_block: int = 256, interpret: bool = False):
    """q: (B,1,H,D); caches (B,S,KH,D); cur_len: int32 scalar/array.

    Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5
    kb = min(kv_block, S)
    nk = -(-S // kb)
    if nk * kb != S:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, nk * kb - S), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, nk * kb - S), (0, 0), (0, 0)))
    qg = q.reshape(B, KH, G, D)
    cur = jnp.asarray(cur_len, jnp.int32).reshape((1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, kb, 1, D), lambda b, h, j, *_: (b, j, h, 0)),
            pl.BlockSpec((1, kb, 1, D), lambda b, h, j, *_: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, kb=kb, scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cur, qg, k_cache, v_cache)
    return out.reshape(B, 1, H, D)
