"""Inputs made from ``--seed``, on the device, in one call each.

The pool generator is the arithmetic of ``repro.data.synthetic.image_pool``
(Gaussian noise plus two class-dependent bright pixels), copied here so a
change to the program cannot move the benchmark's inputs, and drawn with
``jax.random`` at the configuration's image size. Pixels are stored as
uint8, as CIFAR-10 stores them: ``pixel = clip(round(128 + 48 x))``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NOISE = 0.15
PIXEL_MID = 128.0
PIXEL_GAIN = 48.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**64 (seeds need not fit
    32 signed bits)."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def np_rng(seed: int, stream: int) -> np.random.Generator:
    """Host-side generator for schedules and samples, one stream per use."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


@functools.partial(jax.jit, static_argnames=("n", "hw", "classes"))
def _pool(key, *, n: int, hw: int, classes: int):
    ky, kx = jax.random.split(key)
    y = jax.random.randint(ky, (n,), 0, classes)
    x = jax.random.normal(kx, (n, hw, hw, 3), jnp.float32) * NOISE
    for c in range(classes):
        m = (y == c).astype(jnp.float32)
        x = x.at[:, c % hw, (c * 3) % hw, c % 3].add(2.5 * m)
        x = x.at[:, (c * 2) % hw, c % hw, (c + 1) % 3].add(1.5 * m)
    px = jnp.clip(jnp.round(PIXEL_MID + PIXEL_GAIN * x), 0, 255)
    return px.astype(jnp.uint8), y.astype(jnp.int32)


def image_pool(seed: int, n: int, hw: int, classes: int, stream: int = 0):
    """(pixels (n, hw, hw, 3) uint8, classes (n,) int32) on the host."""
    key = jax.random.fold_in(seed_key(seed), stream)
    x, y = _pool(key, n=n, hw=hw, classes=classes)
    return np.asarray(x), np.asarray(y)


def stamp_rows(bank: np.ndarray, start: int, n: int) -> np.ndarray:
    """Rows ``start .. start+n`` of an endless distinct stream: bank row
    ``i % len(bank)`` with the global index ``i`` written into the first
    four pixel bytes of its top row, so no two rows share content."""
    idx = np.arange(start, start + n, dtype=np.int64)
    rows = bank[idx % len(bank)].copy()
    flat = rows.reshape(n, -1)
    for b in range(4):
        flat[:, b] = ((idx >> (8 * b)) & 0xFF).astype(np.uint8)
    return rows
