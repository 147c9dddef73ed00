"""Block-size autotuner for the fused greedy-selection kernels.

``greedy_round_pallas`` has two launch parameters that trade HBM traffic
against VMEM pressure:

``n_block``
    Rows per grid step. The (Rp, d) center tile is re-fetched once per row
    block (its BlockSpec index map is constant), so small ``n_block`` means
    ceil(N / n_block) redundant center reads; large ``n_block`` grows the
    per-step VMEM footprint (row tile + (Rp, n_block) distance matrix) and
    eventually spills.

``r_block``
    Centers folded per fused pass in ``ops.warm_start_min_dist``. M centers
    cost ceil(M / r_block) full pool reads, so bytes-per-center shrinks
    monotonically with ``r_block`` until the center tile + distance matrix
    no longer fit the VMEM budget.

The tuner sweeps both over the same op-accounted HBM model the benchmarks
use (bytes actually moved per fused round) and rejects candidates whose
per-step VMEM model exceeds the budget. Every candidate is a multiple of
128 rows, the lane tiling of the kernels' (1, n_block) vector blocks.
A pick is a pure function of (N, d, dtype, variant); winners are cached in
memory per key, and ``report()`` exposes the cache so benchmarks can print
the chosen blocks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax.numpy as jnp

N_BLOCK_CANDIDATES = (128, 256, 512, 1024)
R_BLOCK_CANDIDATES = (8, 32, 64, 128, 256, 512)

# The v5e compiler's default scoped VMEM. Every (n_block, r_block) pair
# ``tile_vmem_bytes`` admits against it compiles for a described v5e, for
# both round variants, f32 and bf16 pools and d from 32 to 8192
# (tests/test_tpu_compile.py sweeps them).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class BlockChoice:
    n_block: int
    r_block: int
    hbm_bytes: float          # modeled bytes per fused round at (n, 1)


# keyed (n, d, dtype name, round variant): the plain fused round and the
# gated (block-masked) round have different per-step footprints — a VMEM
# budget that holds scalar-prefetch vectors and a winner that amortizes
# dead-block skips do NOT transfer between variants, so sharing one entry
# would serve one of them a wrong (possibly infeasible) block
_CACHE: Dict[Tuple[int, int, str, str], BlockChoice] = {}

VARIANTS = ("round", "gated")


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def round_hbm_bytes(n: int, d: int, dtype_bytes: float, n_block: int,
                    r_block: int) -> float:
    """Modeled HBM bytes of ONE fused greedy round (see kernel.py ledger):
    pool read + min-dist read/write + weight read + per-block center
    re-fetch + (max, argmax) block partials, each one 128-lane group of
    f32/i32 per row block."""
    nb = min(n_block, n)
    nn = -(-n // nb)
    np_ = nn * nb
    rp = _pad_to(max(r_block, 1), 8)
    pool = np_ * d * dtype_bytes
    vectors = 3 * 4 * np_                 # mind in, mind out, weights in
    centers = nn * rp * (d * 4 + 4)       # (Rp, d) tile + sel idx per block
    partials = nn * 2 * 128 * 4
    return pool + vectors + centers + partials


def tile_vmem_bytes(d: int, dtype_bytes: float, n_block: int,
                    r_block: int) -> float:
    """Per-grid-step VMEM: the row tile in its dtype, its f32 upcast
    (none for f32 input) and f32 square, the f32 center tile
    double-buffered plus its square, four (Rp, n_block) f32 distance
    temporaries, and eight (1, n_block) f32 vector rows padded to 8
    sublanes."""
    rp = _pad_to(max(r_block, 1), 8)
    upcast = 4 if dtype_bytes < 4 else 0
    row = n_block * d * (dtype_bytes + upcast + 4)
    cen = 3 * rp * d * 4
    dist = 4 * rp * n_block * 4
    vecs = 8 * 8 * n_block * 4
    return row + cen + dist + vecs


def _feasible(n: int, d: int, dtype_bytes: float, n_block: int,
              r_block: int) -> bool:
    return tile_vmem_bytes(d, dtype_bytes, n_block, r_block) \
        <= VMEM_BUDGET_BYTES


def autotune_blocks(n: int, d: int, dtype=jnp.float32,
                    variant: str = "round") -> BlockChoice:
    """Best (n_block, r_block) for an (N, d) pool of ``dtype`` under the
    HBM and VMEM models; cached per round ``variant`` ("round" = plain
    fused, "gated" = block-masked)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, "
                         f"got {variant!r}")
    dt = jnp.dtype(dtype)
    key = (int(n), int(d), dt.name, variant)
    if key in _CACHE:
        return _CACHE[key]
    dtype_bytes = float(dt.itemsize)
    # n_block is scored on the single-center round (R = 1, the greedy-loop
    # hot path); ties in modeled bytes break to the LARGER block (fewer
    # grid steps and partials to reduce host-side).
    n_cands = [nb for nb in N_BLOCK_CANDIDATES
               if _feasible(n, d, dtype_bytes, nb, 8)] or \
        [N_BLOCK_CANDIDATES[0]]
    best_nb = min(n_cands,
                  key=lambda nb: (round_hbm_bytes(n, d, dtype_bytes, nb, 1),
                                  -nb))
    # r_block amortizes a warm-start pass over r centers: rank by modeled
    # bytes per folded center at the chosen n_block.
    r_cands = [rb for rb in R_BLOCK_CANDIDATES
               if _feasible(n, d, dtype_bytes, best_nb, rb)] or \
        [R_BLOCK_CANDIDATES[0]]
    best_rb = min(r_cands,
                  key=lambda rb: (round_hbm_bytes(n, d, dtype_bytes, best_nb,
                                                  rb) / rb, -rb))
    choice = BlockChoice(best_nb, best_rb,
                         round_hbm_bytes(n, d, dtype_bytes, best_nb, 1))
    _CACHE[key] = choice
    return choice


def report() -> Dict[Tuple[int, int, str, str], BlockChoice]:
    """Cached winners keyed by (N, d, dtype name, variant) — for benchmark
    output."""
    return dict(_CACHE)


def clear_cache() -> None:
    """Forget every cached pick."""
    _CACHE.clear()
