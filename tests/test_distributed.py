"""Distributed machinery (8 forced host devices, subprocess): partition
rules, distributed top-k / k-center selection, compressed psum, small-mesh
lower+compile of build_cell."""
import os
import subprocess
import sys
import textwrap

import pytest

import jax
from repro.common.param import ParamDecl
from repro.distributed import partition


# ------------------------------------------------------- partition rules ----
class FakeMesh:
    def __init__(self, axis_names, shape):
        self.axis_names = axis_names
        import numpy as np
        self.devices = np.zeros(shape)


def _rules(axes=("data", "model"), shape=(16, 16)):
    return partition.make_rules(FakeMesh(axes, shape))


def test_pspec_basic():
    r = _rules()
    assert r.pspec(("embed", "ff"), (256, 1024)) == \
        jax.sharding.PartitionSpec("data", "model")


def test_pspec_divisibility_relaxation():
    r = _rules()
    # 40 heads do not divide 16 -> replicate that dim
    assert r.pspec(("heads", None), (40, 128)) == \
        jax.sharding.PartitionSpec()
    # flat fused dim divides -> sharded
    assert r.pspec(("batch", None, "qkv"), (256, 4, 5120)) == \
        jax.sharding.PartitionSpec("data", None, "model")


def test_pspec_no_axis_reuse():
    r = _rules()
    # expert takes "model" first; ff must not reuse it
    spec = r.pspec(("expert", "embed", "ff"), (64, 2048, 1408))
    assert spec == jax.sharding.PartitionSpec("model", "data")


def test_pspec_multipod_batch():
    r = _rules(("pod", "data", "model"), (2, 16, 16))
    assert r.pspec(("batch", None), (256, 4096)) == \
        jax.sharding.PartitionSpec(("pod", "data"))
    # batch=1 cannot shard
    assert r.pspec(("batch", None), (1, 4096)) == \
        jax.sharding.PartitionSpec()


def test_tree_pspecs():
    r = _rules()
    decls = {"w": ParamDecl((512, 1024), ("embed", "ff"))}
    specs = partition.tree_pspecs(decls, r)
    assert specs["w"] == jax.sharding.PartitionSpec("data", "model")


# --------------------------------------------------- subprocess helpers ----
def _run_sub(code: str, devices: int = 8) -> str:
    prog = (f'import os\n'
            f'os.environ["XLA_FLAGS"] = '
            f'"--xla_force_host_platform_device_count={devices}"\n'
            f'import sys\nsys.path.insert(0, "src")\n') + textwrap.dedent(code)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return r.stdout


@pytest.mark.slow
def test_distributed_topk_matches_global():
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.selection import distributed_top_k
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh((8,), ("data",))
        scores = jnp.asarray(np.random.default_rng(0).normal(size=(512,)),
                             jnp.float32)
        with jax.set_mesh(mesh):
            idx = distributed_top_k(scores, 16, mesh)
        ref = np.argsort(-np.asarray(scores))[:16]
        assert set(np.asarray(idx).tolist()) == set(ref.tolist())
        print("TOPK_OK")
    """)
    assert "TOPK_OK" in out


@pytest.mark.slow
def test_distributed_kcenter_covers_clusters():
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.selection import distributed_k_center
        from repro.launch.mesh import make_debug_mesh
        rng = np.random.default_rng(0)
        centers = rng.normal(size=(8, 16)) * 20
        pts = np.concatenate([c + rng.normal(size=(32, 16)) * 0.1
                              for c in centers]).astype(np.float32)
        perm = rng.permutation(256)
        lab = np.repeat(np.arange(8), 32)[perm]
        mesh = make_debug_mesh((8,), ("data",))
        with jax.set_mesh(mesh):
            idx = distributed_k_center(jnp.asarray(pts[perm]), 8, mesh)
        got = set(lab[np.asarray(idx)].tolist())
        assert len(got) == 8, got
        print("KC_OK")
    """)
    assert "KC_OK" in out


@pytest.mark.slow
def test_distributed_kcenter_weighted():
    """Weighted distributed k-center: ones-weights reproduce the unweighted
    selections exactly, and random weights still give unique in-range
    indices that favor the heavily-weighted region."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.selection import distributed_k_center
        from repro.launch.mesh import make_debug_mesh
        rng = np.random.default_rng(0)
        pts = jnp.asarray(rng.normal(size=(256, 16)), jnp.float32)
        mesh = make_debug_mesh((8,), ("data",))
        with jax.set_mesh(mesh):
            base = distributed_k_center(pts, 12, mesh)
            ones = distributed_k_center(pts, 12, mesh,
                                        weights=jnp.ones((256,), jnp.float32))
            w = jnp.asarray(rng.uniform(0.001, 1.0, size=(256,)), jnp.float32)
            w = w.at[128:].set(w[128:] * 1000.0)   # favor the upper half
            wsel = distributed_k_center(pts, 12, mesh, weights=w)
        assert np.array_equal(np.asarray(base), np.asarray(ones)), \\
            (base, ones)
        wi = np.asarray(wsel)
        assert len(set(wi.tolist())) == 12 and wi.min() >= 0 and wi.max() < 256
        assert np.mean(wi[1:] >= 128) >= 0.7, wi   # seed (idx 0) is unweighted
        print("KCW_OK")
    """)
    assert "KCW_OK" in out


@pytest.mark.slow
def test_compressed_psum_close_to_exact():
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh((8,), ("data",))
        g = jnp.asarray(np.random.default_rng(1).normal(size=(8, 64)),
                        jnp.float32)
        def f(x):
            return compressed_psum(x[0], "data", quantize=True)
        fn = shard_map(f, mesh=mesh, in_specs=P("data", None), out_specs=P())
        with jax.set_mesh(mesh):
            approx = np.asarray(fn(g))
        exact = np.asarray(jnp.sum(g, 0))
        err = np.abs(approx - exact).max() / (np.abs(exact).max() + 1e-9)
        assert err < 0.05, err
        print("PSUM_OK", err)
    """)
    assert "PSUM_OK" in out


@pytest.mark.slow
def test_build_cell_small_mesh_compiles():
    """build_cell lower+compile on a small mesh for one arch x two shapes;
    validates the full dry-run path end to end in-process."""
    out = _run_sub("""
        import jax
        from repro.configs import get_smoke_config, SHAPES
        import dataclasses
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import build_cell
        from repro.roofline import analysis
        cfg = get_smoke_config("qwen3-8b")
        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        for shape_name in ("train_4k", "decode_32k"):
            shape = dataclasses.replace(SHAPES[shape_name], seq_len=64,
                                        global_batch=8)
            cell = build_cell(cfg, shape, mesh)
            compiled = cell.lower().compile()
            roof = analysis.analyze(compiled, cfg, shape, 8)
            assert roof.flops_per_chip > 0
            assert roof.step_time > 0
        print("CELL_OK")
    """)
    assert "CELL_OK" in out
