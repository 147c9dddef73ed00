"""Pallas TPU kernels for pairwise-distance reductions and fused k-center
greedy selection rounds.

Two kernels live here:

``pairwise_min_argmin_pallas``
    min_j ||x_i - c_j||^2 (and its argmin) over a large center set without
    materializing the (N, M) distance matrix in HBM. Tiles (N_b, d) x
    (M_b, d) hit the MXU via the -2*x@c^T term; the ||.||^2 terms and the
    running (min, argmin) fold into the same pass through VMEM scratch.
    Grid: (n_blocks, m_blocks); rows parallel, centers sequential.

``greedy_round_pallas``
    One *fused* k-center greedy round. The unfused round re-streams the
    pool repeatedly:

        HBM traffic per round, unfused (N rows, d features, fp32):
          1. sq_dist_to_center      read (N, d) + write (N,)
          2. jnp.minimum            read 2x (N,) + write (N,)
          3. scatter winner mask    read/write (N,)
          4. jnp.argmax             read (N,)
        => one (N, d) pool read plus ~6 full (N,) vector streams, each a
        separate XLA op with its own HBM round trip.

        HBM traffic per round, fused (this kernel):
          1. one grid pass: read (N, d) + read (N,) min-dist + write (N,)
             min-dist + write 2 x (N / N_b) block partials
        => exactly ONE (N, d) pool read per selected center; everything
        else rides along in the same pass.

    Per (N_b, d) embedding tile the kernel (a) computes squared distances
    to the R queued centers held in VMEM, (b) folds them into the running
    min-dist in place, (c) masks already-selected indices to -1, and (d)
    emits per-block (max, argmax) partials of the (optionally weighted)
    min-dist. A tiny O(N / N_b) host-side reduction over the partials
    yields the next center — no second pass over the pool.

    The R-center ("multi-center") form is what makes the Core-Set
    warm-start cheap: M labeled centers fold into ceil(M / R) pool passes
    instead of one pass per center (see ``ops.warm_start_min_dist``).

Layout. Distances are computed transposed, as an (R, N_b) tile with pool
rows on the 128 lanes, so every per-row quantity (min-dist, weights, the
argmin) is a lane-major (1, N_b) row and the (N,) vectors travel as
(1, N) arrays in blocks of (1, N_b). On the chip N_b is therefore a
multiple of 128 whenever it is smaller than N. Per-block (max, argmax)
partials are written broadcast over one 128-lane group of a (1, 128 *
n_blocks) array, the smallest block the TPU tiling allows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.4e38
LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _sq_dists_t(x, c):
    """(Mc, Nb) squared distances from the (Mc, d) centers to the (Nb, d)
    pool rows, pool rows on lanes. The row norms come out of the MXU as a
    (1, Nb) row (a ones-vector matmul), so nothing is relaid out."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    xc = jax.lax.dot_general(c, x, nt, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
    ones = jnp.ones((8, x.shape[1]), jnp.float32)
    x2 = jax.lax.dot_general(ones, x * x, nt, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)[0:1]
    c2 = jnp.sum(c * c, axis=1, keepdims=True)
    return jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)


def _block_max(mval, offset, bmax_ref, barg_ref):
    """Write the (1, Nb) row's max and the lowest lane index holding it
    (plus ``offset``) broadcast over the block's partial lanes."""
    m = jnp.max(mval, axis=1, keepdims=True)                      # (1, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, mval.shape, 1)
    first = jnp.min(jnp.where(mval == m, lane, mval.shape[1]), axis=1,
                    keepdims=True)
    bmax_ref[...] = jnp.broadcast_to(m, bmax_ref.shape)
    barg_ref[...] = jnp.broadcast_to(first + offset, barg_ref.shape)


def _partials(bmax, barg):
    """Reduce the per-block partials: the first block holding the max."""
    bmax = bmax.reshape(-1, LANES)[:, 0]
    barg = barg.reshape(-1, LANES)[:, 0]
    win = jnp.argmax(bmax)
    return barg[win], bmax[win]


def _row_vec(v, n_pad):
    """(N,) -> (1, N + n_pad) f32 lane-major row."""
    return jnp.pad(v.astype(jnp.float32), (0, n_pad)).reshape(1, -1)


def _kernel(x_ref, c_ref, mind_ref, argm_ref, acc_d, acc_i, *, nm: int,
            m: int, m_block: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_d[...] = jnp.full_like(acc_d, BIG)
        acc_i[...] = jnp.zeros_like(acc_i)

    d = _sq_dists_t(x_ref[...], c_ref[...])              # (Mb, Nb)
    row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0) + j * m_block
    d = jnp.where(row < m, d, BIG)
    bmin = jnp.min(d, axis=0, keepdims=True)             # (1, Nb)
    # first index of the min: argmin's tie rule, across center blocks too
    # (a later block must be strictly better to replace it)
    barg = jnp.min(jnp.where(d == bmin, row, jnp.iinfo(jnp.int32).max),
                   axis=0, keepdims=True)
    better = bmin < acc_d[...]
    acc_i[...] = jnp.where(better, barg, acc_i[...])
    acc_d[...] = jnp.where(better, bmin, acc_d[...])

    @pl.when(j == nm - 1)
    def _fin():
        mind_ref[...] = acc_d[...]
        argm_ref[...] = acc_i[...]


def pairwise_min_argmin_pallas(x, c, *, n_block: int = 256,
                               m_block: int = 256, interpret: bool = False):
    """x: (N,d), c: (M,d) -> (min_d (N,), argmin (N,)) fp32/int32."""
    N, d = x.shape
    M, _ = c.shape
    nb = min(n_block, N)
    mb = min(m_block, -(-M // 8) * 8)
    nn = -(-N // nb)
    nm = -(-M // mb)
    Np, Mp = nn * nb, nm * mb
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
    if Mp != M:
        c = jnp.pad(c, ((0, Mp - M), (0, 0)))
    mind, argm = pl.pallas_call(
        functools.partial(_kernel, nm=nm, m=M, m_block=mb),
        grid=(nn, nm),
        in_specs=[
            pl.BlockSpec((nb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((mb, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nb), lambda i, j: (0, i)),
            pl.BlockSpec((1, nb), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, nb), jnp.float32),
            pltpu.VMEM((1, nb), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, c)
    return mind[0, :N], argm[0, :N]


def _greedy_kernel(x_ref, mind_ref, c_ref, sel_ref, w_ref,
                   nmind_ref, bmax_ref, barg_ref, *, n: int, r: int,
                   n_block: int):
    i = pl.program_id(0)
    d = _sq_dists_t(x_ref[...], c_ref[...])              # (Rp, Nb)
    row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    d = jnp.where(row < r, d, BIG)

    nm = jnp.minimum(mind_ref[...], jnp.min(d, axis=0, keepdims=True))
    gid = jax.lax.broadcasted_iota(jnp.int32, nm.shape, 1) + i * n_block
    hit = jnp.max(jnp.where(gid == sel_ref[...], 1, 0), axis=0,
                  keepdims=True) > 0                     # (1, Nb)
    nm = jnp.where(hit, -1.0, nm)
    nmind_ref[...] = nm

    # Selected (nm < 0) and padded rows are pinned to -BIG *before* the
    # weight multiply: with -1 * w a zero-weight masked row scores -0.0 and
    # ties (first-index wins) against legitimate zero-score rows, so a
    # masked row could win the argmax. -BIG can never tie a real score.
    score = nm * w_ref[...]
    valid = (gid < n) & jnp.logical_not(nm < 0.0)
    _block_max(jnp.where(valid, score, -BIG), i * n_block, bmax_ref,
               barg_ref)


def greedy_layout(n: int, n_block: int):
    """``(rows per block, padded rows)`` of the fused round over an
    ``n``-row pool at ``n_block``: the layout a caller that pads once
    hands to ``greedy_round_pallas(..., n=n)``."""
    nb = min(n_block, n)
    return nb, -(-n // nb) * nb


def greedy_round_pallas(x, mind, centers, sel_idx, weights=None, *,
                        n_block: int = 256, interpret: bool = False,
                        n: int | None = None):
    """One fused greedy round: fold ``centers`` into the running min-dist,
    mask ``sel_idx``, and return the next (weighted) farthest point.

    x: (N, d) pool; mind: (N,) running min sq-dist (selected rows already
    -1); centers: (R, d) newly queued centers; sel_idx: (R,) int32 pool
    indices to mask this round (-1 = no mask); weights: optional (N,)
    non-negative weights applied to the argmax score only — the returned
    min-dist is never weighted. Selected rows (new or carried-in -1) and
    padded rows score -BIG, so they cannot win the argmax even against
    zero-weight or zero-distance rows; exact score ties break to the
    lowest pool index independent of ``n_block`` (per-block argmax takes
    the first max in the block, the host reduction the first max block).

    Returns ``(new_mind (N,) f32, next_idx () i32, next_score () f32)``.

    With ``n`` the operands come padded already, at ``greedy_layout(n,
    n_block)``: x (Np, d), mind and weights (1, Np) rows, rows ``n:``
    ignored. Nothing is padded or sliced, and ``new_mind`` stays a (1, Np)
    row: a loop folding many rounds over one pool pads it once.
    """
    R = centers.shape[0]
    if sel_idx.shape[0] != R:
        raise ValueError(
            f"sel_idx must mask exactly the queued centers: got "
            f"{sel_idx.shape[0]} indices for {R} centers")
    padded = n is not None
    N, d = (n, x.shape[1]) if padded else x.shape
    nb, Np = greedy_layout(N, n_block)
    nn = Np // nb
    Rp = -(-R // 8) * 8
    if padded:
        if x.shape[0] != Np or mind.shape != (1, Np):
            raise ValueError(
                f"padded operands must hold {Np} rows for n={N} at "
                f"n_block={n_block}: got {x.shape[0]} and {mind.shape}")
        w = jnp.ones((1, Np), jnp.float32) if weights is None else weights
    else:
        if Np != N:
            x = jnp.pad(x, ((0, Np - N), (0, 0)))
        mind = _row_vec(mind, Np - N)
        w = (jnp.ones((1, Np), jnp.float32) if weights is None
             else _row_vec(weights, Np - N))
    if Rp != R:
        centers = jnp.pad(centers, ((0, Rp - R), (0, 0)))
        sel_idx = jnp.pad(sel_idx, (0, Rp - R), constant_values=-1)
    nmind, bmax, barg = pl.pallas_call(
        functools.partial(_greedy_kernel, n=N, r=R, n_block=nb),
        grid=(nn,),
        in_specs=[
            pl.BlockSpec((nb, d), lambda i: (i, 0)),
            pl.BlockSpec((1, nb), lambda i: (0, i)),
            pl.BlockSpec((Rp, d), lambda i: (0, 0)),
            pl.BlockSpec((Rp, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, nb), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, nb), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
            jax.ShapeDtypeStruct((1, nn * LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, nn * LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, mind.astype(jnp.float32), centers.astype(jnp.float32),
      sel_idx.astype(jnp.int32).reshape(Rp, 1), w.astype(jnp.float32))
    # O(N / N_b) reduction over block partials picks the next center.
    nxt, score = _partials(bmax, barg)
    return (nmind if padded else nmind[0, :N]), nxt, score


def _gated_kernel(live_ref, pend_ref, x_ref, mind_ref, c_ref, w_ref,
                  nmind_ref, bmax_ref, barg_ref, *, n: int, r: int,
                  n_block: int):
    i = pl.program_id(0)
    mind = mind_ref[...]
    live = live_ref[i] > 0

    @pl.when(live)
    def _eval():
        d = _sq_dists_t(x_ref[...], c_ref[...])          # (Rp, Nb)
        row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        # catch-up masking: this block already folded centers
        # [0, pend[i]) in earlier rounds; fold only the queue's tail
        d = jnp.where((row >= pend_ref[i]) & (row < r), d, BIG)
        nm = jnp.minimum(mind, jnp.min(d, axis=0, keepdims=True))
        nmind_ref[...] = nm
        gid = jax.lax.broadcasted_iota(jnp.int32, nm.shape, 1) + i * n_block
        score = nm * w_ref[...]
        valid = (gid < n) & jnp.logical_not(nm < 0.0)
        _block_max(jnp.where(valid, score, -BIG), i * n_block, bmax_ref,
                   barg_ref)

    @pl.when(jnp.logical_not(live))
    def _skip():
        # dead block: min-dists pass through, partials can never win
        nmind_ref[...] = mind
        bmax_ref[...] = jnp.full(bmax_ref.shape, -BIG, jnp.float32)
        barg_ref[...] = jnp.full(barg_ref.shape, i * n_block, jnp.int32)


def gated_greedy_round_pallas(x, mind, centers, block_live, block_pending,
                              weights=None, *, n_block: int = 256,
                              interpret: bool = False):
    """Block-masked greedy round: the centroid prefilter's TPU path.

    Same per-row math as ``greedy_round_pallas``, but two scalar-prefetch
    vectors steer the grid: ``block_live[b]`` gates whether block ``b`` is
    evaluated at all (a dead block's x-tile index map redirects to block 0,
    so its pool rows are never fetched from HBM), and ``block_pending[b]``
    is the first queued-center column the block has NOT folded yet — a
    block that skipped earlier rounds folds the centers it missed when its
    bound finally fails. Winner masking is host-side (mind[i] = -1.0).

    Returns ``(new_mind (N,), next_idx () i32, next_score () f32)`` where
    the argmax ranges over live, unmasked, unpadded rows only.
    """
    N, d = x.shape
    R = centers.shape[0]
    nb = min(n_block, N)
    nn = -(-N // nb)
    Np = nn * nb
    Rp = -(-R // 8) * 8
    if block_live.shape[0] != nn or block_pending.shape[0] != nn:
        raise ValueError(
            f"block vectors must have one entry per row block: got "
            f"{block_live.shape[0]}/{block_pending.shape[0]} for {nn}")
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
    if Rp != R:
        centers = jnp.pad(centers, ((0, Rp - R), (0, 0)))
    w = (jnp.ones((1, Np), jnp.float32) if weights is None
         else _row_vec(weights, Np - N))
    live = block_live.astype(jnp.int32)
    pend = block_pending.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nn,),
        in_specs=[
            # dead blocks re-point their x tile at block 0: no HBM fetch
            # for the pool rows the gate pruned
            pl.BlockSpec((nb, d),
                         lambda i, lv, pd: (jnp.where(lv[i] > 0, i, 0), 0)),
            pl.BlockSpec((1, nb), lambda i, lv, pd: (0, i)),
            pl.BlockSpec((Rp, d), lambda i, lv, pd: (0, 0)),
            pl.BlockSpec((1, nb), lambda i, lv, pd: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, nb), lambda i, lv, pd: (0, i)),
            pl.BlockSpec((1, LANES), lambda i, lv, pd: (0, i)),
            pl.BlockSpec((1, LANES), lambda i, lv, pd: (0, i)),
        ],
    )
    nmind, bmax, barg = pl.pallas_call(
        functools.partial(_gated_kernel, n=N, r=R, n_block=nb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
            jax.ShapeDtypeStruct((1, nn * LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, nn * LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(live, pend, x, _row_vec(mind, Np - N),
      centers.astype(jnp.float32), w)
    nxt, score = _partials(bmax, barg)
    return nmind[0, :N], nxt, score
