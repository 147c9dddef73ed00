"""Pod-scale AL selection: score shards locally, merge globally.

Demonstrates the distributed selection layer (core/selection.py) on an
8-device mesh (forced host devices): every data shard computes fused
uncertainty scores for its slice of the pool, then

  * budget-B uncertainty selection = local top-B + all_gather merge,
  * diversity selection = distributed greedy k-center,

with per-round communication independent of pool size — the same program
runs on the (pod, data, model) production mesh.

Run: PYTHONPATH=src python examples/distributed_selection.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.selection import (distributed_k_center,  # noqa: E402
                                  distributed_top_k, sharded_scores)
from repro.launch.mesh import make_debug_mesh  # noqa: E402


def main():
    mesh = make_debug_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    N, C, D, BUDGET = 65536, 512, 64, 128

    # a pool of logits + embeddings, sharded over the data axis
    logits = jnp.asarray(rng.normal(size=(N, C)) * 2, jnp.float32)
    emb = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        scores = sharded_scores(logits, "lc", mesh)        # stays sharded
        idx_u = distributed_top_k(scores, BUDGET, mesh)    # replicated result
        jax.block_until_ready(idx_u)
        t_unc = time.perf_counter() - t0

        t0 = time.perf_counter()
        idx_d = distributed_k_center(emb, BUDGET, mesh)
        jax.block_until_ready(idx_d)
        t_div = time.perf_counter() - t0

    # verify against the single-device reference
    ref = np.argsort(-np.asarray(scores))[:BUDGET]
    match = len(set(np.asarray(idx_u).tolist()) & set(ref.tolist()))
    print(f"pool={N} budget={BUDGET} devices={mesh.devices.size}")
    print(f"uncertainty top-k: {t_unc*1e3:.0f} ms, "
          f"{match}/{BUDGET} agree with the global reference")
    print(f"k-center greedy:   {t_div*1e3:.0f} ms, "
          f"{len(set(np.asarray(idx_d).tolist()))} unique centers")


if __name__ == "__main__":
    main()
