"""The served path's Pallas kernels compile for a TPU v5e.

Interpret mode checks what the kernels compute, not whether the chip's
compiler accepts their block layouts and VMEM use. These tests compile
each kernel for a described (not attached) v5e at the chip smoke's widths
— a 10,000-row pool of 512-wide ResNet-18 features with 10 classes, and
the flash kernel at ``configs/text_al.yml``'s encoder shapes — and assert
the compiled program holds the Mosaic kernel (``tpu_custom_call``). A
sweep compiles both greedy rounds at every (n_block, r_block) pair the
autotuner's VMEM model admits, over a range of feature widths and both
pool dtypes, so no pick the model can make is refused on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.pairwise import autotune
from repro.kernels.pairwise.kernel import (gated_greedy_round_pallas,
                                           greedy_round_pallas,
                                           pairwise_min_argmin_pallas)
from repro.kernels.uncertainty.kernel import uncertainty_stats_pallas
from repro.models.blockwise import tiny_encoder_config

N, D, CLASSES = 10_000, 512, 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the whole program fits one v5e's 16 GB of HBM with room to spare
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 << 30
    return compiled


@pytest.mark.parametrize("centers", ["one", "r_block"])
def test_greedy_round_compiles(one_chip, centers):
    """The k-center round (R=1) and the warm-start fold (R=r_block) at the
    autotuner's pick for the pool."""
    pick = autotune.autotune_blocks(N, D, jnp.float32)
    r = 1 if centers == "one" else pick.r_block
    _compile(one_chip,
             lambda x, m, c, s: greedy_round_pallas(x, m, c, s,
                                                    n_block=pick.n_block),
             ((N, D), jnp.float32), ((N,), jnp.float32),
             ((r, D), jnp.float32), ((r,), jnp.int32))


def test_device_pick_loop_compiles(one_chip):
    """The sharded k-center pick loop as one device program at the
    benchmark cell's widths: a 49,000 x 512 pool on one shard, unweighted,
    with the budget a traced bound. The kernel keeps its trace name inside
    the loop body."""
    from repro.core.selection import _greedy_loop
    rows = 49_000
    blocks = (autotune.autotune_blocks(rows, D, jnp.float32).n_block,)
    compiled = _compile(
        one_chip,
        lambda x, m, g, b: _greedy_loop((x,), (m,), (g,), None, b,
                                        blocks=blocks, impl="pallas"),
        ((rows, D), jnp.float32), ((rows,), jnp.float32),
        ((rows,), jnp.int32), ((2,), jnp.int32))
    text = compiled.as_text()
    assert "while" in text
    assert any("_greedy_round" in line and "custom-call(" in line
               for line in text.splitlines())


def test_gated_greedy_round_compiles(one_chip):
    nb = 256
    nn = -(-N // nb)
    _compile(one_chip,
             lambda live, pend, x, m, c: gated_greedy_round_pallas(
                 x, m, c, live, pend, n_block=nb),
             ((nn,), jnp.int32), ((nn,), jnp.int32), ((N, D), jnp.float32),
             ((N,), jnp.float32), ((5, D), jnp.float32))


def test_pairwise_min_argmin_compiles(one_chip):
    # k-means seeding / prefilter assignment: the pool against 64 centroids
    _compile(one_chip, pairwise_min_argmin_pallas,
             ((N, D), jnp.float32), ((64, D), jnp.float32))


def test_uncertainty_stats_compiles(one_chip):
    _compile(one_chip, uncertainty_stats_pallas,
             ((N, CLASSES), jnp.float32))


def test_flash_attention_compiles(one_chip):
    """configs/text_al.yml: batch 32, seq_len 128, the tiny GQA encoder."""
    cfg = tiny_encoder_config()
    B, S = 32, 128
    _compile(one_chip,
             lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             ((B, S, cfg.n_heads, cfg.hd), jnp.float32),
             ((B, S, cfg.n_kv_heads, cfg.hd), jnp.float32),
             ((B, S, cfg.n_kv_heads, cfg.hd), jnp.float32))


SWEEP_N = 4096                   # four row blocks at the largest n_block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [32, 512, 2048, 8192])
@pytest.mark.parametrize("variant", autotune.VARIANTS)
def test_autotune_candidates_compile(one_chip, variant, d, dtype):
    """Every candidate pair the VMEM model admits compiles."""
    nbytes = jnp.dtype(dtype).itemsize
    pairs = [(nb, rb) for nb in autotune.N_BLOCK_CANDIDATES
             for rb in autotune.R_BLOCK_CANDIDATES
             if autotune._feasible(SWEEP_N, d, nbytes, nb, rb)]
    assert pairs, "the model must admit at least one pair"
    for nb, rb in pairs:
        if variant == "round":
            _compile(one_chip,
                     lambda x, m, c, s: greedy_round_pallas(x, m, c, s,
                                                            n_block=nb),
                     ((SWEEP_N, d), dtype), ((SWEEP_N,), jnp.float32),
                     ((rb, d), jnp.float32), ((rb,), jnp.int32))
        else:
            nn = -(-SWEEP_N // nb)
            _compile(one_chip,
                     lambda live, pend, x, m, c: gated_greedy_round_pallas(
                         x, m, c, live, pend, n_block=nb),
                     ((nn,), jnp.int32), ((nn,), jnp.int32),
                     ((SWEEP_N, d), dtype), ((SWEEP_N,), jnp.float32),
                     ((rb, d), jnp.float32))


def test_four_chip_pick_loop_compiles(topo):
    """The pick loop across chips as one SPMD program for the described
    v5e:2x2: four shards of 12,500 x 512, one per chip, unweighted, with
    the budget a traced bound. Per pick the kernel runs on every chip and
    the proposals meet in one all-gather inside the loop."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.selection import _lane_loop
    from repro.kernels.pairwise.kernel import greedy_layout
    rows = 12_500
    mesh = Mesh(np.asarray(topo.devices[:4]), ("lanes",))
    lane, whole = NamedSharding(mesh, P("lanes")), NamedSharding(mesh, P())
    n_block = autotune.autotune_blocks(rows, D, jnp.float32).n_block
    padded = greedy_layout(rows, n_block)[1]
    sds = jax.ShapeDtypeStruct
    compiled = _lane_loop.lower(
        sds((4 * padded, D), jnp.float32, sharding=lane),
        sds((4 * padded,), jnp.float32, sharding=lane),
        sds((4 * padded,), jnp.int32, sharding=lane), None, None,
        sds((2,), jnp.int32, sharding=whole), mesh=mesh, n=rows,
        n_block=n_block, total=None, impl="pallas").compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text
    # one collective for the first pick, one in the loop's body (the
    # compiler may lower the all-gather to an all-reduce)
    collective = re.compile(r"\s(all-gather|all-reduce)(-start)?\(")
    assert sum(bool(collective.search(line))
               for line in text.splitlines()) == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 << 30
