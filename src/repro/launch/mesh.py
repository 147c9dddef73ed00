"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single-pod: (16,16)=(data,model), 256 chips. Multi-pod:
(2,16,16)=(pod,data,model), 512 chips. The dry-run launcher forces 512 host
platform devices via XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices, have {len(devs)} — launch via "
            "repro.launch.dryrun which forces "
            "--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devs[:need])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    need = int(np.prod(shape))
    return jax.make_mesh(shape, axes, devices=jax.devices()[:need])
