"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracle (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the whole module is the kernel lane: run it alone with `pytest -m interpret`
pytestmark = pytest.mark.interpret

rng = np.random.default_rng(0)


def _arr(shape, dtype, scale=1.0):
    x = rng.normal(size=shape) * scale
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------- uncertainty ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(16, 128), (5, 300), (64, 1024), (1, 37)])
def test_uncertainty_kernel(shape, dtype):
    from repro.kernels.uncertainty import ref
    from repro.kernels.uncertainty.kernel import uncertainty_stats_pallas

    lg = _arr(shape, dtype, scale=3.0)
    out = uncertainty_stats_pallas(lg, row_block=8, v_block=128,
                                   interpret=True)
    rr = ref.uncertainty_stats_ref(lg)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    for i, k in enumerate(("lc", "mc", "rc", "es")):
        np.testing.assert_allclose(out[i], rr[k], rtol=tol, atol=tol,
                                   err_msg=f"{k} {shape} {dtype}")


def test_uncertainty_extreme_logits():
    """Online stats must survive large logit magnitudes (no overflow)."""
    from repro.kernels.uncertainty import ref
    from repro.kernels.uncertainty.kernel import uncertainty_stats_pallas

    lg = _arr((8, 512), jnp.float32, scale=80.0)
    out = uncertainty_stats_pallas(lg, interpret=True)
    rr = ref.uncertainty_stats_ref(lg)
    for i, k in enumerate(("lc", "mc", "rc", "es")):
        np.testing.assert_allclose(out[i], rr[k], rtol=1e-4, atol=1e-4)


def test_uncertainty_ops_dispatch():
    from repro.kernels.uncertainty import ops

    lg = _arr((32, 256), jnp.float32, scale=2.0)
    for kind in ("lc", "mc", "rc", "es"):
        a = ops.uncertainty_scores(lg, kind, impl="ref")
        b = ops.uncertainty_scores(lg, kind, impl="interpret")
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- pairwise ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nmd", [(64, 32, 16), (100, 70, 64), (33, 257, 128)])
def test_pairwise_kernel(nmd, dtype):
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import pairwise_min_argmin_pallas

    N, M, d = nmd
    x = _arr((N, d), dtype)
    c = _arr((M, d), dtype)
    mind, argm = pairwise_min_argmin_pallas(x, c, n_block=16, m_block=64,
                                            interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(mind, ref.pairwise_min_dist_ref(x, c),
                               rtol=tol, atol=tol)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(argm),
                                      np.asarray(ref.pairwise_argmin_ref(x, c)))


def test_pairwise_min_and_argmin_single_launch():
    from repro.kernels.pairwise import ops, ref

    x, c = _arr((70, 24), jnp.float32), _arr((33, 24), jnp.float32)
    mind, argm = ops.pairwise_min_and_argmin(x, c, impl="interpret")
    np.testing.assert_allclose(mind, ref.pairwise_min_dist_ref(x, c),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(argm),
                                  np.asarray(ref.pairwise_argmin_ref(x, c)))
    with ops.track_ops() as stats:
        ops.pairwise_min_and_argmin(x, c, impl="ref")
    assert stats["embedding_reads"] == 1       # the pair costs ONE pool pass


# --------------------------------------------------- fused greedy round ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nrd", [(64, 1, 16), (100, 3, 64), (33, 8, 100),
                                 (257, 5, 130)])
def test_greedy_round_kernel(nrd, dtype):
    """Interpret-mode parity vs the jnp oracle on non-block-multiple N / R
    and d not a multiple of 128."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    N, R, d = nrd
    x = _arr((N, d), dtype)
    c = _arr((R, d), dtype)
    mind = jnp.asarray(np.abs(rng.normal(size=(N,))) * 10, jnp.float32)
    sel = jnp.asarray(rng.choice(N, R, replace=False), jnp.int32)
    nm_k, ni_k, nv_k = greedy_round_pallas(x, mind, c, sel, n_block=16,
                                           interpret=True)
    nm_r, ni_r, nv_r = ref.greedy_round_ref(x, mind, c, sel)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(nm_k, nm_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(nv_k, nv_r, rtol=tol, atol=tol)
    if dtype == jnp.float32:
        assert int(ni_k) == int(ni_r)
    # masked rows must be pinned to -1 and never win the argmax
    np.testing.assert_array_equal(np.asarray(nm_k)[np.asarray(sel)], -1.0)
    assert int(ni_k) not in set(np.asarray(sel).tolist())


def test_greedy_round_weighted_argmax():
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    N, R, d = 90, 2, 48
    x = _arr((N, d), jnp.float32)
    c = _arr((R, d), jnp.float32)
    mind = jnp.asarray(np.abs(rng.normal(size=(N,))) * 10, jnp.float32)
    sel = jnp.asarray([3, 77], jnp.int32)
    w = jnp.asarray(np.abs(rng.normal(size=(N,))) + 0.1, jnp.float32)
    nm_k, ni_k, nv_k = greedy_round_pallas(x, mind, c, sel, w, n_block=32,
                                           interpret=True)
    nm_r, ni_r, nv_r = ref.greedy_round_ref(x, mind, c, sel, w)
    np.testing.assert_allclose(nm_k, nm_r, rtol=1e-4, atol=1e-4)
    assert int(ni_k) == int(ni_r)
    np.testing.assert_allclose(nv_k, nv_r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_greedy_round_on_padded_operands(weighted):
    """Operands padded once to the kernel's layout, handed over with their
    real row count, give the unpadded call's (mind[:n], idx, score)
    bitwise, at a ragged N (not a multiple of n_block)."""
    from repro.kernels.pairwise.kernel import (greedy_layout,
                                               greedy_round_pallas)

    N, d, nb = 77, 48, 32
    x = _arr((N, d), jnp.float32)
    c = _arr((1, d), jnp.float32)
    mind = jnp.asarray(np.abs(rng.normal(size=(N,))) * 10, jnp.float32)
    mind = mind.at[5].set(-1.0)                 # a row picked earlier
    sel = jnp.asarray([40], jnp.int32)
    w = (jnp.asarray(np.abs(rng.normal(size=(N,))) + 0.1, jnp.float32)
         if weighted else None)
    _, Np = greedy_layout(N, nb)
    assert Np % nb == 0 and Np > N

    def row(v):
        return jnp.pad(v, (0, Np - N))[None, :]

    nm_p, ni_p, nv_p = greedy_round_pallas(
        jnp.pad(x, ((0, Np - N), (0, 0))), row(mind), c, sel,
        None if w is None else row(w), n_block=nb, n=N, interpret=True)
    nm_u, ni_u, nv_u = greedy_round_pallas(x, mind, c, sel, w, n_block=nb,
                                           interpret=True)
    assert nm_p.shape == (1, Np)
    np.testing.assert_array_equal(np.asarray(nm_p)[0, :N], np.asarray(nm_u))
    assert int(ni_p) == int(ni_u) < N
    assert np.asarray(nv_p).tobytes() == np.asarray(nv_u).tobytes()


def test_greedy_round_no_mask_sentinel():
    """sel_idx = -1 must mask nothing."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    x = _arr((40, 32), jnp.float32)
    c = _arr((1, 32), jnp.float32)
    mind = jnp.full((40,), 1e9, jnp.float32)
    no_mask = jnp.full((1,), -1, jnp.int32)
    nm_k, _, _ = greedy_round_pallas(x, mind, c, no_mask, n_block=16,
                                     interpret=True)
    np.testing.assert_allclose(nm_k, ref.pairwise_min_dist_ref(x, c),
                               rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(nm_k) >= 0.0)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_warm_start_chunked_matches_oneshot(impl):
    """Core-Set warm start: chunked multi-center passes == one-shot min."""
    from repro.kernels.pairwise import ops, ref

    x = _arr((123, 130), jnp.float32)        # d not a multiple of 128
    cen = _arr((37, 130), jnp.float32)       # M not a multiple of r_block
    got = ops.warm_start_min_dist(x, cen, impl=impl, r_block=10)
    np.testing.assert_allclose(got, ref.pairwise_min_dist_ref(x, cen),
                               rtol=1e-4, atol=1e-4)
    with ops.track_ops() as stats:
        ops.warm_start_min_dist(x, cen, impl=impl, r_block=10)
    assert stats["embedding_reads"] == 4     # ceil(37 / 10) pool passes


def test_greedy_round_op_accounting():
    from repro.kernels.pairwise import ops

    x = _arr((64, 16), jnp.float32)
    mind = jnp.full((64,), 1e9, jnp.float32)
    with ops.track_ops() as stats:
        for i in range(5):
            mind, nxt, _ = ops.greedy_round(
                x, mind, x[i][None, :], jnp.asarray([i], jnp.int32),
                impl="ref")
    assert stats["embedding_reads"] == 5     # exactly one pool read / round


# ------------------------------------------ fused round edge cases (PR 2) ----
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("nblock", [16, 64])
def test_greedy_round_weighted_random_parity(seed, nblock):
    """Random weights, N not divisible by n_block: kernel == oracle, with a
    bit-identical argmax."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    r = np.random.default_rng(seed)
    N, R, d = 50, 3, 24
    x = jnp.asarray(r.normal(size=(N, d)), jnp.float32)
    c = jnp.asarray(r.normal(size=(R, d)), jnp.float32)
    mind = jnp.asarray(np.abs(r.normal(size=(N,))) * 5, jnp.float32)
    sel = jnp.asarray(r.choice(N, R, replace=False), jnp.int32)
    w = jnp.asarray(r.uniform(0.0, 2.0, size=(N,)), jnp.float32)
    nm_k, ni_k, nv_k = greedy_round_pallas(x, mind, c, sel, w,
                                           n_block=nblock, interpret=True)
    nm_r, ni_r, nv_r = ref.greedy_round_ref(x, mind, c, sel, w)
    np.testing.assert_allclose(nm_k, nm_r, rtol=1e-4, atol=1e-4)
    assert int(ni_k) == int(ni_r)
    np.testing.assert_allclose(nv_k, nv_r, rtol=1e-4, atol=1e-4)


def test_greedy_round_fully_masked_block():
    """An ENTIRE n_block of rows is selected this round: the winner must
    come from the other blocks, never the all-masked one."""
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    N, d, nb = 48, 32, 16
    x = _arr((N, d), jnp.float32)
    sel = jnp.arange(16, 32, dtype=jnp.int32)          # all of block 1
    c = x[16:32]                                       # fold those 16 centers
    mind = jnp.full((N,), 1e6, jnp.float32)
    nm, ni, _ = greedy_round_pallas(x, mind, c, sel, n_block=nb,
                                    interpret=True)
    assert not (16 <= int(ni) < 32)
    np.testing.assert_array_equal(np.asarray(nm)[16:32], -1.0)
    # centers/sel length mismatch must be a loud error, not silent
    # mispadding — on the kernel AND on every ops dispatch path (the ref
    # oracle would otherwise quietly leave queued centers unmasked)
    from repro.kernels.pairwise import ops
    with pytest.raises(ValueError):
        greedy_round_pallas(x, mind, x[:1], sel, n_block=nb, interpret=True)
    with pytest.raises(ValueError):
        ops.greedy_round(x, mind, x[:1], sel, impl="ref")


@pytest.mark.parametrize("impl_interpret", [False, True])
def test_greedy_round_all_but_one_selected(impl_interpret):
    """Every row but one carries the selected -1 marker (or is masked this
    round): the argmax must return the single live row — even when its
    weight is ZERO, where the old ``-1 * w`` masking tied at -0.0 and could
    leak a masked row."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    N, d, live = 40, 16, 23
    x = _arr((N, d), jnp.float32)
    c = _arr((1, d), jnp.float32)
    mind = jnp.full((N,), -1.0, jnp.float32).at[live].set(50.0)
    sel = jnp.full((1,), -1, jnp.int32)
    w = jnp.zeros((N,), jnp.float32)                   # zero weights
    if impl_interpret:
        _, ni, _ = greedy_round_pallas(x, mind, c, sel, w, n_block=16,
                                       interpret=True)
    else:
        _, ni, _ = ref.greedy_round_ref(x, mind, c, sel, w)
    assert int(ni) == live


def test_greedy_round_zero_weight_masked_row_never_wins():
    """Masked row 0 with weight 0 scored -0.0 under ``-1 * w`` masking and
    argmax-tied (first index wins) against legitimate zero-score rows; it
    must lose now that masked rows pin to -BIG."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    N, d = 24, 16
    x = _arr((N, d), jnp.float32)
    x = x.at[5].set(x[0])                              # row 5 duplicates row 0
    c = x[0][None, :]
    mind = jnp.full((N,), 1e6, jnp.float32)
    sel = jnp.zeros((1,), jnp.int32)                   # mask row 0
    w = jnp.zeros((N,), jnp.float32)                   # all scores 0 or -BIG
    for got in (greedy_round_pallas(x, mind, c, sel, w, n_block=8,
                                    interpret=True)[1],
                ref.greedy_round_ref(x, mind, c, sel, w)[1]):
        assert int(got) != 0                           # never the masked row
        assert int(got) == 1                           # first live row ties win


@pytest.mark.parametrize("nblock", [8, 16, 32, 64])
def test_greedy_round_tiebreak_stable_across_n_block(nblock):
    """Exact score ties must break to the LOWEST pool index for every
    n_block (per-block argmax takes the first max, the host reduction the
    first max block) — selections must not depend on the launch tiling."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import greedy_round_pallas

    N, d = 64, 16
    base = _arr((N, d), jnp.float32)
    # rows 9, 27, 58 identical -> identical distance and weight -> 3-way tie
    x = base.at[27].set(base[9]).at[58].set(base[9])
    far = base[9] + 100.0                              # make them the winners
    x = x * 0.01 + 0.0
    x = x.at[9].set(far).at[27].set(far).at[58].set(far)
    c = jnp.zeros((1, d), jnp.float32)
    mind = jnp.full((N,), 1e9, jnp.float32)
    sel = jnp.full((1,), -1, jnp.int32)
    w = jnp.ones((N,), jnp.float32)
    _, ni, _ = greedy_round_pallas(x, mind, c, sel, w, n_block=nblock,
                                   interpret=True)
    _, ni_r, _ = ref.greedy_round_ref(x, mind, c, sel, w)
    assert int(ni) == int(ni_r) == 9


# ------------------------------------------------------------- autotuner ----
def test_autotune_blocks_cached_and_feasible():
    from repro.kernels.pairwise import autotune

    autotune.clear_cache()
    ch = autotune.autotune_blocks(4096, 64, jnp.float32)
    assert ch.n_block in autotune.N_BLOCK_CANDIDATES
    assert ch.r_block in autotune.R_BLOCK_CANDIDATES
    assert autotune.tile_vmem_bytes(64, 4, ch.n_block, ch.r_block) \
        <= autotune.VMEM_BUDGET_BYTES
    assert autotune.autotune_blocks(4096, 64, jnp.float32) is ch  # cached
    assert (4096, 64, "float32", "round") in autotune.report()
    # the gated (block-masked) round is a SEPARATE cache entry: its winner
    # must never alias the plain round's (the PR-6 collision bug)
    ch_gated = autotune.autotune_blocks(4096, 64, jnp.float32,
                                        variant="gated")
    assert (4096, 64, "float32", "gated") in autotune.report()
    assert autotune.autotune_blocks(
        4096, 64, jnp.float32, variant="gated") is ch_gated
    assert autotune.autotune_blocks(4096, 64, jnp.float32) is ch
    with pytest.raises(ValueError, match="variant"):
        autotune.autotune_blocks(4096, 64, jnp.float32, variant="bogus")
    # a huge feature dim must force smaller tiles, not blow the budget
    ch_wide = autotune.autotune_blocks(4096, 8192, jnp.float32)
    assert autotune.tile_vmem_bytes(8192, 4, ch_wide.n_block,
                                    ch_wide.r_block) \
        <= autotune.VMEM_BUDGET_BYTES
    assert ch_wide.n_block <= ch.n_block


def test_autotune_pick_is_pure():
    """A pick depends on (N, d, dtype) alone: clearing the cache and
    re-picking gives an equal choice, and no file is written."""
    from repro.kernels.pairwise import autotune

    autotune.clear_cache()
    ch = autotune.autotune_blocks(2048, 32, jnp.float32)
    autotune.clear_cache()
    assert (2048, 32, "float32", "round") not in autotune.report()
    again = autotune.autotune_blocks(2048, 32, jnp.float32)
    assert again == ch and again is not ch


def test_autotune_model_amortizes_r_block():
    """Bytes-per-folded-center must be non-increasing in r_block (that is
    the whole point of the multi-center warm start)."""
    from repro.kernels.pairwise import autotune

    per_center = [
        autotune.round_hbm_bytes(4096, 64, 4, 256, rb) / rb
        for rb in autotune.R_BLOCK_CANDIDATES
    ]
    assert all(a >= b for a, b in zip(per_center, per_center[1:]))


def test_greedy_round_autotuned_default_matches_ref():
    """ops.greedy_round with n_block unset (autotuned) stays bit-identical
    to the oracle on the interpret path."""
    from repro.kernels.pairwise import ops, ref

    x = _arr((100, 24), jnp.float32)
    c = _arr((2, 24), jnp.float32)
    mind = jnp.asarray(np.abs(rng.normal(size=(100,))) * 5, jnp.float32)
    sel = jnp.asarray([7, 42], jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(100,)), jnp.float32)
    nm_k, ni_k, _ = ops.greedy_round(x, mind, c, sel, weights=w,
                                     impl="interpret")
    nm_r, ni_r, _ = ref.greedy_round_ref(x, mind, c, sel, w)
    np.testing.assert_allclose(nm_k, nm_r, rtol=1e-4, atol=1e-4)
    assert int(ni_k) == int(ni_r)


# ------------------------------------------------- gated (masked) round ----
@pytest.mark.parametrize("nrd", [(64, 3, 16), (100, 5, 64), (33, 2, 100),
                                 (257, 9, 40)])
def test_gated_greedy_round_kernel(nrd):
    """Interpret-mode parity vs the oracle on ragged N with a random
    live/pending pattern: dead blocks pass mind through untouched, live
    blocks catch up only on the centers they have not folded."""
    from repro.kernels.pairwise import ref
    from repro.kernels.pairwise.kernel import gated_greedy_round_pallas

    N, R, d = nrd
    nb = 16
    nn = -(-N // nb)
    x = _arr((N, d), jnp.float32)
    c = _arr((R, d), jnp.float32)
    mind = jnp.asarray(np.abs(rng.normal(size=(N,))) * 10, jnp.float32)
    live = jnp.asarray(rng.integers(0, 2, size=nn), jnp.int32)
    pend = jnp.asarray(rng.integers(0, R + 1, size=nn), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(N,)), jnp.float32)
    for weights in (None, w):
        nm_k, ni_k, nv_k = gated_greedy_round_pallas(
            x, mind, c, live, pend, weights=weights, n_block=nb,
            interpret=True)
        nm_r, ni_r, nv_r = ref.gated_greedy_round_ref(
            x, mind, c, live, pend, weights=weights, n_block=nb)
        np.testing.assert_allclose(nm_k, nm_r, rtol=1e-4, atol=1e-4)
        assert int(ni_k) == int(ni_r)
        np.testing.assert_allclose(nv_k, nv_r, rtol=1e-4, atol=1e-4)
    # dead blocks: mind passes through bitwise
    dead_rows = np.concatenate(
        [np.arange(b * nb, min((b + 1) * nb, N))
         for b in np.nonzero(np.asarray(live) == 0)[0]]) \
        if (np.asarray(live) == 0).any() else np.zeros(0, np.int64)
    np.testing.assert_array_equal(np.asarray(nm_k)[dead_rows],
                                  np.asarray(mind)[dead_rows])


def test_gated_round_all_live_matches_plain_round():
    """Every block live with nothing pending-masked == the plain fused
    round (same floats), the degenerate-gate sanity check."""
    from repro.kernels.pairwise import ops

    x = _arr((90, 32), jnp.float32)
    c = _arr((4, 32), jnp.float32)
    mind = jnp.asarray(np.abs(rng.normal(size=(90,))) * 10, jnp.float32)
    nn = -(-90 // 16)
    nm_g, ni_g, _ = ops.gated_greedy_round(
        x, mind, c, np.ones(nn, np.int64), np.zeros(nn, np.int64),
        impl="interpret", n_block=16)
    sel = jnp.full((4,), -1, jnp.int32)
    nm_p, ni_p, _ = ops.greedy_round(x, mind, c, sel, impl="interpret")
    np.testing.assert_array_equal(np.asarray(nm_g), np.asarray(nm_p))
    assert int(ni_g) == int(ni_p)


def test_gated_round_accounting_counts_live_rows_only():
    from repro.kernels.pairwise import ops

    x = _arr((100, 8), jnp.float32)
    c = _arr((1, 8), jnp.float32)
    mind = jnp.full((100,), 1e9, jnp.float32)
    live = np.array([1, 0, 0, 1], np.int64)      # blocks of 32: 32+4 rows
    with ops.track_ops() as stats:
        ops.gated_greedy_round(x, mind, c, live, np.zeros(4, np.int64),
                               impl="ref", n_block=32)
    assert stats["pool_rows"] == 32 + 4          # last block is ragged
    with pytest.raises(ValueError, match="block_live"):
        ops.gated_greedy_round(x, mind, c, np.ones(3, np.int64),
                               np.zeros(3, np.int64), n_block=32)


# -------------------------------------------------------- flash attention ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "cfg", [
        dict(B=2, Sq=64, Skv=64, H=4, KH=2, D=32, causal=True, win=None),
        dict(B=1, Sq=48, Skv=80, H=4, KH=4, D=16, causal=True, win=16),
        dict(B=2, Sq=33, Skv=100, H=8, KH=2, D=64, causal=False, win=None),
        dict(B=1, Sq=128, Skv=128, H=8, KH=1, D=64, causal=True, win=None),
    ])
def test_flash_attention_kernel(cfg, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref

    q = _arr((cfg["B"], cfg["Sq"], cfg["H"], cfg["D"]), dtype)
    k = _arr((cfg["B"], cfg["Skv"], cfg["KH"], cfg["D"]), dtype)
    v = _arr((cfg["B"], cfg["Skv"], cfg["KH"], cfg["D"]), dtype)
    out = flash_attention_pallas(q, k, v, causal=cfg["causal"],
                                 window=cfg["win"], q_block=16, kv_block=32,
                                 interpret=True)
    rf = flash_attention_ref(q, k, v, causal=cfg["causal"], window=cfg["win"])
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rf, np.float32), rtol=tol, atol=tol)


# -------------------------------------------------------- decode attention ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "cfg", [
        dict(B=2, H=4, KH=2, D=32, S=128, cur=77, win=None),
        dict(B=1, H=8, KH=1, D=64, S=96, cur=96, win=None),
        dict(B=2, H=4, KH=4, D=16, S=64, cur=13, win=8),
        dict(B=3, H=16, KH=2, D=64, S=200, cur=1, win=None),
    ])
def test_decode_attention_kernel(cfg, dtype):
    from repro.kernels.decode_attention.kernel import decode_attention_pallas
    from repro.kernels.decode_attention.ref import decode_attention_ref

    q = _arr((cfg["B"], 1, cfg["H"], cfg["D"]), dtype)
    k = _arr((cfg["B"], cfg["S"], cfg["KH"], cfg["D"]), dtype)
    v = _arr((cfg["B"], cfg["S"], cfg["KH"], cfg["D"]), dtype)
    out = decode_attention_pallas(q, k, v, cfg["cur"], window=cfg["win"],
                                  kv_block=32, interpret=True)
    rf = decode_attention_ref(q, k, v, cfg["cur"], window=cfg["win"])
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rf, np.float32), rtol=tol, atol=tol)


# ------------------------------------------------- blockwise encoder lane ----
def test_blockwise_encoder_interpret_matches_chunked():
    """The serving blockwise attention path through the Pallas flash kernel
    (interpret mode) vs the chunked-jnp production fallback: same encoder,
    same params, same blocks — features agree to fp32 kernel tolerance.
    Covers the intra/inter-block (causal, GQA, block-padded) shapes the
    TransformerBackend feeds the kernel on TPU."""
    from repro.data.synthetic import text_pool
    from repro.models import blockwise
    from repro.service.backends import TransformerBackend

    toks, _ = text_pool(6, num_classes=3, seq_len=40, vocab=512, seed=11)
    kw = dict(seq_len=40, block_size=16, kv_chunk=16)
    chunked = TransformerBackend(attention_impl="chunked", **kw)
    interp = TransformerBackend(attention_impl="interpret", **kw)
    x = chunked.preprocess(toks)
    fc = chunked.features(x)
    fi = interp.features(x)
    np.testing.assert_allclose(fi, fc, rtol=2e-4, atol=2e-4)
    # and directly at the encode level with a non-dividing block
    params = chunked.params
    cfg = chunked.cfg
    emb = blockwise.embed_tokens(cfg, params, jnp.asarray(x))
    hc = blockwise.blockwise_encode(cfg, params, emb, block=7, kv_chunk=16,
                                    impl="chunked")
    hi = blockwise.blockwise_encode(cfg, params, emb, block=7, kv_chunk=16,
                                    impl="interpret")
    np.testing.assert_allclose(np.asarray(hi), np.asarray(hc),
                               rtol=2e-4, atol=2e-4)
