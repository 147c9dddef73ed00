"""Wrapper: pallas flash attention on TPU, chunked-jnp fallback elsewhere."""
from __future__ import annotations

from typing import Optional

from repro.common.device import on_tpu
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def flash_attention_auto(q, k, v, *, causal: bool = True,
                         window: Optional[int] = None,
                         scale: Optional[float] = None, **chunk_kw):
    if on_tpu():
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale)
    from repro.models.layers.attention import chunked_attention
    return chunked_attention(q, k, v, causal=causal, window=window,
                             scale=scale,
                             q_chunk=chunk_kw.get("q_chunk", 512),
                             kv_chunk=chunk_kw.get("kv_chunk", 1024))
