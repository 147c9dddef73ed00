"""The plain reference of the ``resnet18`` scorer's served features.

Written from the semantics the service states, importing nothing of the
program and taking none of its arrays: pixels whose per-image maximum
exceeds 1.5 are divided by 255; a 3x3 stride-1 stem with ReLU; basic
blocks (two 3x3 convolutions, the first strided 2 at the start of stages
2-4, a 1x1 projection on the shortcut where the width changes); after
each convolution of a block a per-image norm over the spatial axes
(biased variance, eps 1e-5, a learned scale, no shift), ReLU after the
first and after the residual add; global average pooling.

Precision is the configuration's: ``conv_precision: "default"`` is XLA's
default for float32 convolutions, which on a TPU rounds both operands to
bfloat16 and sums their exact products in float32, and on a CPU keeps
float32 operands; ``activation_dtype`` is the type every activation is
held in. The reference computes exactly that, at
``Precision.HIGHEST`` on the rounded operands. ``control=True`` is the
nearest precision below: activations held in bfloat16 as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def conv_operand_dtype(cfg: dict, platform: str):
    """The type XLA's stated precision rounds convolution operands to."""
    if cfg["conv_precision"] == "default":
        return jnp.bfloat16 if platform == "tpu" else jnp.float32
    return jnp.dtype(cfg["conv_precision"]).type


def _conv(x, w, stride, operands):
    """Exact products of operands rounded to ``operands``, float32 sums."""
    x = x.astype(operands).astype(jnp.float32)
    w = w.astype(operands).astype(jnp.float32)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _norm(x, scale):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=(1, 2), keepdims=True)
    return ((xf - mu) / jnp.sqrt(var + 1e-5) * scale).astype(x.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stage_sizes", "operands", "acts"))
def _features(params, pixels, *, stage_sizes, operands, acts):
    x = pixels.astype(jnp.float32)
    mx = jnp.max(x, axis=(1, 2, 3), keepdims=True)
    x = jnp.where(mx > 1.5, x / 255.0, x)
    h = jax.nn.relu(_conv(x, params["stem"], 1, operands)).astype(acts)
    bi = 0
    for si, n in enumerate(stage_sizes):
        for k in range(n):
            b = params["blocks"][bi]
            stride = 2 if (k == 0 and si > 0) else 1
            y = _conv(h, b["conv1"], stride, operands).astype(acts)
            y = jax.nn.relu(_norm(y, b["scale1"].astype(acts)))
            y = _norm(_conv(y, b["conv2"], 1, operands).astype(acts),
                      b["scale2"].astype(acts))
            sc = h if "proj" not in b else \
                _conv(h, b["proj"], stride, operands).astype(acts)
            h = jax.nn.relu(y + sc)
            bi += 1
    return jnp.mean(h.astype(jnp.float32), axis=(1, 2))


def features(params, pixels, cfg: dict, control: bool = False):
    """(B, H, W, 3) pixels (uint8 or float) -> (B, width) float32, on the
    device, at the configuration's precision (``control``: one below)."""
    acts = jnp.bfloat16 if control else jnp.dtype(cfg["activation_dtype"])
    return _features(params, jnp.asarray(pixels),
                     stage_sizes=tuple(cfg["stage_sizes"]),
                     operands=conv_operand_dtype(
                         cfg, jax.devices()[0].platform),
                     acts=jnp.dtype(acts).type)


def pool_features(params, pixels: np.ndarray, cfg: dict, block: int = 1000,
                  control: bool = False) -> np.ndarray:
    """Reference features of every row, ``block`` rows per call (the last
    block zero-padded so one program serves them all)."""
    n = pixels.shape[0]
    out = np.empty((n, int(cfg["widths"][-1])), np.float32)
    for s in range(0, n, block):
        part = pixels[s:s + block]
        m = part.shape[0]
        if m < block:
            part = np.concatenate(
                [part, np.zeros((block - m,) + part.shape[1:], part.dtype)])
        out[s:s + m] = np.asarray(features(params, part, cfg, control))[:m]
    return out
