"""The ``resnet18`` scorer: the seeded weights and rows the benchmark
makes for it, and the program's server backend built around them.

``params`` follows the program's published layout (stem, four stages of
basic blocks, 1x1 projections where the width changes, per-block norm
scales, a linear head); the server and the reference are handed the same
arrays and neither makes its own. Rows are uint8 images
(``harness.data.image_pool``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import data

MODEL_NAME = "resnet18"


def resnet_layout(stage_sizes, widths, in_ch: int, classes: int):
    """[(path, shape, fan_in or None for ones)] in the program's order."""
    out = [(("stem",), (3, 3, in_ch, widths[0]), 9 * in_ch)]
    cin, bi = widths[0], 0
    for n, w in zip(stage_sizes, widths):
        for _ in range(n):
            out += [(("blocks", bi, "conv1"), (3, 3, cin, w), 9 * cin),
                    (("blocks", bi, "conv2"), (3, 3, w, w), 9 * w),
                    (("blocks", bi, "scale1"), (w,), None),
                    (("blocks", bi, "scale2"), (w,), None)]
            if cin != w:
                out.append((("blocks", bi, "proj"), (1, 1, cin, w), cin))
            cin, bi = w, bi + 1
    out.append((("head",), (cin, classes), cin))
    return out


def resnet_params(seed: int, stage_sizes, widths, in_ch: int, classes: int):
    """Scaled-normal conv weights (std 1/sqrt(fan_in)), unit norm scales:
    the program's pytree, made on the device in one jitted call."""
    layout = resnet_layout(tuple(stage_sizes), tuple(widths), in_ch, classes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(layout))
        leaves = []
        for (_, shape, fan), k in zip(layout, keys):
            if fan is None:
                leaves.append(jnp.ones(shape, jnp.float32))
            else:
                leaves.append(jax.random.normal(k, shape, jnp.float32)
                              / np.sqrt(fan))
        return leaves

    leaves = make(jax.random.fold_in(data.seed_key(seed), 1 << 20))
    params = {"blocks": []}
    for (path, _, _), leaf in zip(layout, leaves):
        if path[0] == "blocks":
            while len(params["blocks"]) <= path[1]:
                params["blocks"].append({})
            params["blocks"][path[1]][path[2]] = leaf
        else:
            params[path[0]] = leaf
    return params


def params(seed: int, cfg: dict):
    return resnet_params(seed, cfg["stage_sizes"], cfg["widths"],
                         cfg["channels"], cfg["num_classes"])


def rows(seed: int, n: int, cfg: dict, stream: int = 0):
    """(rows (n, hw, hw, 3) uint8, classes (n,) int32) on the host."""
    return data.image_pool(seed, n, int(cfg["image_hw"]),
                           int(cfg["num_classes"]), stream=stream)


def distinct(bank, start: int, n: int):
    """Rows ``start .. start+n`` of an endless stream of distinct rows."""
    return data.stamp_rows(bank, start, n)


def backend(cfg: dict, weights):
    """The program's ResNet backend, serving ``weights``."""
    from repro.models.resnet import ResNetConfig
    from repro.service.backends import ResNetBackend
    out = ResNetBackend(ResNetConfig(
        stage_sizes=tuple(cfg["stage_sizes"]), widths=tuple(cfg["widths"]),
        in_channels=cfg["channels"], num_classes=cfg["num_classes"]))
    out.params = weights
    return out
