"""Backend determinism contract: per-sample preprocessing, explicit PRNG
keys, clear input validation, and the cross-backend parity suite (chunk
invariance, eviction-recompute bit-identity, deterministic head fits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import telemetry
from repro.data.synthetic import audio_pool, image_pool, text_pool
from repro.service.backends import (MLPBackend, ResNetBackend,
                                    TransformerBackend, make_backend)
from repro.service.config import ALServiceConfig
from repro.service.server import ALServer


# ------------------------------------------------- per-sample preprocess --
def test_resnet_preprocess_is_per_sample():
    """Regression: the uint8-range check used to be a whole-batch
    ``x.max() > 1.5`` — a [0,1] sample batched next to a 255-range sample
    got divided by 255, so the same bytes produced different features
    depending on batchmates (content-addressed cache poison)."""
    be = ResNetBackend()
    lo = np.random.default_rng(0).random((1, 8, 8, 3)).astype(np.float32)
    hi = np.full((1, 8, 8, 3), 200.0, np.float32)
    alone = be.preprocess(lo)
    batched = be.preprocess(np.concatenate([lo, hi]))
    assert np.array_equal(alone[0], batched[0])       # lo untouched
    assert np.allclose(batched[1], hi[0] / 255.0)     # hi rescaled
    f_alone = be.features(alone)
    f_batched = be.features(be.preprocess(np.concatenate([lo, hi])))
    assert np.array_equal(f_alone[0], f_batched[0])


def test_resnet_preprocess_keeps_unit_range_batches():
    be = ResNetBackend()
    x = np.random.default_rng(1).random((4, 8, 8, 3)).astype(np.float32)
    assert np.array_equal(be.preprocess(x), x)


# ------------------------------------------------------- explicit PRNG keys --
def test_explicit_old_style_keys_accepted():
    """Regression: ``rng or PRNGKey(0)`` raised "truth value of an array
    is ambiguous" for explicit uint32[2] keys in init_head and every
    backend constructor."""
    key = jax.random.PRNGKey(123)
    be = MLPBackend(in_dim=12, rng=key)
    h1 = be.init_head(jax.random.PRNGKey(7))
    h2 = be.init_head(jax.random.PRNGKey(7))
    assert np.array_equal(h1.w, h2.w)
    ResNetBackend(rng=key)
    TransformerBackend(rng=key, seq_len=8, block_size=4)
    # defaults still work
    assert be.init_head().w.shape == (be.feat_dim, be.num_classes)


# --------------------------------------------------------- MLP validation --
def test_mlp_preprocess_validates_ndim():
    be = MLPBackend(in_dim=12)
    with pytest.raises(ValueError, match="batch"):
        be.preprocess(np.zeros((7,), np.float32))      # 1-D payload
    with pytest.raises(ValueError, match="in_dim=12"):
        be.preprocess(np.zeros((3, 5), np.float32))    # wrong feature width
    flat = be.preprocess(np.zeros((3, 12), np.float32))
    nested = be.preprocess(np.zeros((3, 4, 3), np.float32))
    assert flat.shape == nested.shape == (3, 12)


# ------------------------------------------------------------ parity suite --
def _cases():
    return {
        "synthetic_cnn": (
            lambda: make_backend("synthetic_cnn"),
            lambda: image_pool(24, num_classes=4, hw=8, seed=3)[0]),
        "mlp": (
            lambda: MLPBackend(in_dim=48, feat_dim=16),
            lambda: np.random.default_rng(4).normal(
                size=(24, 48)).astype(np.float32)),
        "transformer_text": (
            lambda: make_backend("transformer", seq_len=24, block_size=8,
                                 kv_chunk=8),
            lambda: text_pool(24, num_classes=4, seq_len=24, vocab=512,
                              seed=5)[0]),
        "transformer_audio": (
            lambda: make_backend("transformer", seq_len=24, block_size=8,
                                 kv_chunk=8, modality="audio", input_dim=6),
            lambda: audio_pool(24, num_classes=4, n_frames=24, n_mels=6,
                               seed=6)[0]),
    }


@pytest.mark.parametrize("case", sorted(_cases()))
def test_backend_chunk_invariance(case):
    """Features are identical whether the pool is embedded all at once or
    one sample at a time in the canonical padded batch shape."""
    make, data = _cases()[case]
    be, raw = make(), data()
    x = be.preprocess(raw)
    bs = 8
    full = be.features(x)
    for i in range(0, len(x), 3):           # spot-check rows
        padded = np.concatenate(
            [x[i:i + 1], np.zeros((bs - 1,) + x.shape[1:], x.dtype)])
        assert np.array_equal(be.features(padded)[0], full[i]), \
            f"{case}: row {i} depends on batch composition"


@pytest.mark.parametrize("case", sorted(_cases()))
def test_backend_eviction_recompute_bitwise(case):
    """A feature recomputed after cache eviction reproduces the
    ingest-time bytes exactly (the `_feats_for` canonical-shape path)."""
    make, data = _cases()[case]
    raw = list(data())
    ingest = ALServer(ALServiceConfig(batch_size=8), backend=make())
    keys = ingest.push_data(raw)
    want = np.stack([ingest.cache.get(k) for k in keys])
    feat_bytes = want[0].nbytes
    tiny = ALServer(ALServiceConfig(batch_size=8,
                                    cache_bytes=5 * feat_bytes),
                    backend=make())
    keys2 = tiny.push_data(raw)
    assert keys2 == keys
    assert tiny.cache.stats()["entries"] < len(keys)   # eviction happened
    got = tiny.session()._feats_for(keys)
    assert np.array_equal(got, want), f"{case}: recompute changed bytes"


@pytest.mark.parametrize("case", sorted(_cases()))
def test_backend_head_fit_deterministic(case):
    make, data = _cases()[case]
    be, raw = make(), data()
    feats = be.features(be.preprocess(raw))
    labels = np.arange(len(feats)) % be.num_classes
    key = jax.random.PRNGKey(9)
    h1 = be.fit_head(feats, labels, head=be.init_head(key))
    h2 = be.fit_head(feats, labels, head=be.init_head(key))
    assert np.array_equal(h1.w, h2.w) and np.array_equal(h1.b, h2.b)
    assert np.array_equal(be.probs(feats, h1), be.probs(feats, h2))


# ------------------------------------------------------ bucketed head fit --
def _reference_fit(feats, labels, head, steps=200, lr=0.5):
    """The head fit on the rows as they are, with no padding: the mean
    negative log-likelihood plus weight decay, one ``lax.scan``."""
    x = jnp.asarray(feats, jnp.float32)
    y = jnp.asarray(labels, jnp.int32)

    def loss_fn(p):
        lp = jax.nn.log_softmax(x @ p["w"] + p["b"])
        nll = -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))
        return nll + 1e-4 * jnp.sum(p["w"] ** 2)

    def step(p, _):
        g = jax.grad(loss_fn)(p)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), None

    p, _ = jax.jit(lambda p: jax.lax.scan(step, p, None, length=steps))(
        {"w": head.w, "b": head.b})
    return p["w"], p["b"]


_HEAD_BACKENDS = {
    "mlp": lambda k=10: MLPBackend(in_dim=48, feat_dim=16, num_classes=k),
    "synthetic_cnn": lambda k=10: make_backend("synthetic_cnn",
                                               num_classes=k),
}


def _labeled(be, n, seed):
    rng = np.random.default_rng(seed)
    feats = np.tanh(rng.normal(size=(n, be.feat_dim))).astype(np.float32)
    labels = rng.integers(0, be.num_classes, size=n)
    return feats, labels


@pytest.mark.parametrize("n", [1, 255, 256, 257, 1000, 4096])
@pytest.mark.parametrize("case", sorted(_HEAD_BACKENDS))
def test_bucketed_head_fit_matches_unpadded_reference(case, n):
    """Padding the labeled rows to their bucket changes nothing: the
    weights match an unpadded fit and the accuracy is the same."""
    be = _HEAD_BACKENDS[case]()
    feats, labels = _labeled(be, n, seed=n)
    head = be.fit_head(feats, labels)
    w, b = _reference_fit(feats, labels, be.init_head())
    np.testing.assert_allclose(head.w, w, atol=1e-5)
    np.testing.assert_allclose(head.b, b, atol=1e-5)
    want = float(np.mean(
        np.argmax(feats @ np.asarray(w) + np.asarray(b), -1) == labels))
    assert be.evaluate(feats, labels, head) == pytest.approx(want)


@pytest.mark.parametrize("case", sorted(_HEAD_BACKENDS))
def test_head_fit_compiles_once_per_bucket(case):
    """Two labeled counts in one bucket run the same compiled fit and
    evaluation: the second count compiles nothing."""
    # 7 classes: a head shape no other test compiles, so the first count
    # of the bucket compiles here whatever ran before
    be = _HEAD_BACKENDS[case](7)
    feats, labels = _labeled(be, 700, seed=1)

    def compiles():
        return telemetry.snapshot()["counters"].get("compile.count", 0)

    with telemetry.span("test.head_fit"):
        c0 = compiles()
        be.evaluate(feats[:600], labels[:600],
                    be.fit_head(feats[:600], labels[:600]))
        c1 = compiles()
        be.evaluate(feats, labels, be.fit_head(feats, labels))
        c2 = compiles()
    assert c1 > c0          # the first count of the bucket compiles
    assert c2 == c1
