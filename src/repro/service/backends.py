"""Scorer backends for the AL service.

A backend = frozen feature extractor + trainable linear head (the paper's
'fine-tune ResNet-18's last layer' protocol), exposing exactly the artifacts
the strategy zoo needs: probs + embeddings.

Every backend obeys the batch-insensitivity contract the content-addressed
EmbeddingCache depends on: ``preprocess`` makes per-sample decisions only
(never whole-batch statistics) and ``features`` is row-local, so a sample's
feature bytes are identical no matter which neighbours shared its batch or
how the pool was chunked at push time. TransformerBackend extends the same
contract to the sequence axis: its blockwise-chunked forward
(models/blockwise.py) produces bit-identical features at any block size.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import blockwise as blockwise_lib
from repro.models import resnet as resnet_lib


@dataclasses.dataclass
class HeadState:
    w: jax.Array
    b: jax.Array


class FeatureBackend:
    """Shared logic: fit/eval a softmax head on frozen features."""

    num_classes: int
    feat_dim: int

    def preprocess(self, raw: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def features(self, batch: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    # -- head -------------------------------------------------------------
    def init_head(self, rng=None) -> HeadState:
        # `rng or PRNGKey(0)` would bool() an explicit uint32[2] key and
        # raise "truth value of an array is ambiguous"
        if rng is None:
            rng = jax.random.PRNGKey(0)
        w = jax.random.normal(rng, (self.feat_dim, self.num_classes),
                              jnp.float32) * 0.01
        return HeadState(w=w, b=jnp.zeros((self.num_classes,), jnp.float32))

    def fit_head(self, feats: np.ndarray, labels: np.ndarray,
                 steps: int = 200, lr: float = 0.5,
                 head: Optional[HeadState] = None) -> HeadState:
        if head is None:
            head = self.init_head()
        x, y, mask = _padded(feats, labels)
        w, b = _fit_head(head.w, head.b, x, y, mask, lr, steps=steps)
        return HeadState(w=w, b=b)

    def probs(self, feats: np.ndarray, head: HeadState) -> np.ndarray:
        return np.asarray(_head_probs(jnp.asarray(feats, jnp.float32),
                                      head.w, head.b))

    def evaluate(self, feats: np.ndarray, labels: np.ndarray,
                 head: HeadState) -> float:
        x, y, mask = _padded(feats, labels)
        return float(_head_correct(x, y, mask, head.w, head.b)) / len(feats)


# The labeled rows are padded to a power-of-two bucket (at least
# MIN_BUCKET), so one compiled fit and one evaluation serve every labeled
# count in a bucket; the features are arguments, never constants baked
# into a program per call.
MIN_BUCKET = 256


def _padded(feats, labels):
    """Rows padded with zero features, label 0 and mask 0 to their
    bucket: (x, y, mask) as float32, int32, float32."""
    n = len(feats)
    rows = max(MIN_BUCKET, 1 << (n - 1).bit_length())
    x = np.zeros((rows,) + np.shape(feats)[1:], np.float32)
    x[:n] = feats
    y = np.zeros((rows,), np.int32)
    y[:n] = labels
    mask = np.zeros((rows,), np.float32)
    mask[:n] = 1.0
    return x, y, mask


@functools.partial(jax.jit, static_argnames=("steps",))
def _fit_head(w, b, x, y, mask, lr, *, steps):
    """``steps`` of gradient descent on the softmax head: the mean
    negative log-likelihood over the rows with mask 1, plus weight decay.
    A padded row (zero features, mask 0) adds nothing to the gradient."""
    def loss_fn(p):
        lp = jax.nn.log_softmax(x @ p["w"] + p["b"])
        nll = -jnp.sum(mask * jnp.take_along_axis(lp, y[:, None], 1)[:, 0])
        return nll / jnp.sum(mask) + 1e-4 * jnp.sum(p["w"] ** 2)

    def fit_head_step(p, _):
        with jax.named_scope("alaas.fit_head_step"):
            g = jax.grad(loss_fn)(p)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), None

    p, _ = jax.lax.scan(fit_head_step, {"w": w, "b": b}, None, length=steps)
    return p["w"], p["b"]


@jax.jit
def _head_correct(x, y, mask, w, b):
    """Rows with mask 1 whose logits' argmax is their label."""
    with jax.named_scope("alaas.head_correct"):
        hit = jnp.argmax(x @ w + b, axis=-1) == y
        return jnp.sum(jnp.where(mask > 0, hit, False))


@jax.jit
def _head_probs(x, w, b):
    with jax.named_scope("alaas.probs"):
        return jax.nn.softmax(x @ w + b, axis=-1)


class ResNetBackend(FeatureBackend):
    """Paper-faithful image scorer (resnet-18 or the tiny CPU variant)."""

    def __init__(self, cfg: Optional[resnet_lib.ResNetConfig] = None,
                 rng=None, num_classes: int = 10):
        self.cfg = cfg or resnet_lib.tiny_config(num_classes)
        self.num_classes = self.cfg.num_classes
        self.feat_dim = self.cfg.widths[-1]
        if rng is None:
            rng = jax.random.PRNGKey(42)
        self.params = resnet_lib.init_resnet(self.cfg, rng)
        # params are arguments, not closed-over constants: baked in, the
        # ResNet-18 weights double the compiled program and its cache entry
        def resnet_features(p, x):
            with jax.named_scope("alaas.resnet_features"):
                return resnet_lib.resnet_features(p, self.cfg, x)

        self._feat = jax.jit(resnet_features)

    def preprocess(self, raw: np.ndarray) -> np.ndarray:
        x = np.asarray(raw, np.float32)
        # uint8-range detection is PER SAMPLE: a whole-batch x.max() would
        # rescale a [0,1] sample differently depending on its batchmates,
        # breaking the content-addressed cache (same bytes, different
        # features). Each sample's scale depends on that sample alone.
        axes = tuple(range(1, x.ndim))
        mx = x.max(axis=axes, keepdims=True) if axes else x
        return np.where(mx > 1.5, x / 255.0, x)

    def features(self, batch: np.ndarray) -> np.ndarray:
        return np.asarray(self._feat(self.params, jnp.asarray(batch)))


class MLPBackend(FeatureBackend):
    """Cheap random-projection feature backend for tests/property checks."""

    def __init__(self, in_dim: int, feat_dim: int = 64, num_classes: int = 10,
                 rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(7)
        k1, k2 = jax.random.split(rng)
        self.in_dim = in_dim
        self.params = (jax.random.normal(k1, (in_dim, 128)) / np.sqrt(in_dim),
                       jax.random.normal(k2, (128, feat_dim)) / np.sqrt(128))
        self.num_classes = num_classes
        self.feat_dim = feat_dim
        self._feat = jax.jit(
            lambda p, x: jnp.tanh(jnp.tanh(x @ p[0]) @ p[1]))

    def preprocess(self, raw: np.ndarray) -> np.ndarray:
        x = np.asarray(raw, np.float32)
        if x.ndim < 2:
            raise ValueError(
                f"MLPBackend.preprocess expects a batch of samples "
                f"(N, features...); got shape {x.shape} — a 1-D payload "
                f"has no batch axis to flatten over")
        x = x.reshape(x.shape[0], -1)
        if x.shape[1] != self.in_dim:
            raise ValueError(
                f"MLPBackend.preprocess: sample flattens to {x.shape[1]} "
                f"features, backend was built with in_dim={self.in_dim}")
        return x

    def features(self, batch: np.ndarray) -> np.ndarray:
        return np.asarray(self._feat(self.params,
                                     jnp.asarray(batch, jnp.float32)))


class TransformerBackend(FeatureBackend):
    """Text/audio scorer: frozen blockwise-chunked transformer encoder.

    The forward (models/blockwise.py) processes the sequence in fixed-size
    blocks through the standard transformer layers — flash-attention Pallas
    kernel on TPU, chunked online-softmax elsewhere, remat per block — so
    peak activation memory is flat in sequence length, and the block size
    is bitwise-invisible in the feature bytes (chunked == unchunked at any
    ``block_size``).

    ``modality="text"``: raw items are int token rows, -1 = right-padding;
    ``modality="audio"``: raw items are (frames, input_dim) float frames.
    ``preprocess`` pads/truncates every sample to ``seq_len`` per-sample
    (no cross-sample statistics), giving the DynamicBatcher one canonical
    item shape. ``kv_chunk`` is clamped to ``seq_len`` so the online-softmax
    KV grid never varies with block padding (the bitwise contract).
    """

    def __init__(self, cfg: Optional[ArchConfig] = None, rng=None,
                 num_classes: int = 10, block_size: int = 64,
                 seq_len: int = 128, pooling: str = "mean",
                 modality: str = "text", input_dim: int = 0,
                 kv_chunk: int = 128, attention_impl: Optional[str] = None):
        if modality not in ("text", "audio"):
            raise ValueError(f"unknown modality {modality!r}")
        if pooling not in ("mean", "last"):
            raise ValueError(f"unknown pooling {pooling!r}")
        if modality == "audio" and not input_dim:
            raise ValueError("audio modality needs input_dim (frame features)")
        self.cfg = cfg or blockwise_lib.tiny_encoder_config()
        self.num_classes = num_classes
        self.feat_dim = self.cfg.d_model
        self.block_size = max(1, int(block_size))
        self.seq_len = max(1, int(seq_len))
        self.pooling = pooling
        self.modality = modality
        self.input_dim = int(input_dim)
        self.kv_chunk = max(1, min(int(kv_chunk), self.seq_len))
        self.impl = attention_impl or self.cfg.attention_impl
        if rng is None:
            rng = jax.random.PRNGKey(11)
        self.params = blockwise_lib.init_encoder(
            self.cfg, rng, self.input_dim if modality == "audio" else None)

        def forward(params, batch):
            if self.modality == "text":
                x = blockwise_lib.embed_tokens(self.cfg, params, batch)
                mask = batch >= 0
            else:
                x = blockwise_lib.embed_frames(params, batch)
                mask = jnp.ones(batch.shape[:2], bool)
            h = blockwise_lib.blockwise_encode(
                self.cfg, params, x, block=self.block_size,
                kv_chunk=self.kv_chunk, impl=self.impl)
            return blockwise_lib.pool_hidden(h, mask, self.pooling)

        self._feat = jax.jit(forward)

    def preprocess(self, raw: np.ndarray) -> np.ndarray:
        x = np.asarray(raw)
        if self.modality == "text":
            if x.ndim != 2:
                raise ValueError(
                    f"text preprocess expects (N, tokens) int rows; got "
                    f"shape {x.shape}")
            if not np.issubdtype(x.dtype, np.integer):
                raise ValueError(
                    f"text preprocess expects integer tokens; got {x.dtype}")
            if x.size and int(x.max()) >= self.cfg.vocab:
                raise ValueError(
                    f"token id {int(x.max())} out of range for vocab "
                    f"{self.cfg.vocab}")
            out = np.full((x.shape[0], self.seq_len), -1, np.int32)
            L = min(x.shape[1], self.seq_len)
            out[:, :L] = x[:, :L]
            return out
        if x.ndim != 3 or x.shape[-1] != self.input_dim:
            raise ValueError(
                f"audio preprocess expects (N, frames, {self.input_dim}) "
                f"float frames; got shape {x.shape}")
        out = np.zeros((x.shape[0], self.seq_len, self.input_dim), np.float32)
        L = min(x.shape[1], self.seq_len)
        out[:, :L] = x[:, :L]
        return out

    def features(self, batch: np.ndarray) -> np.ndarray:
        return np.asarray(self._feat(self.params, jnp.asarray(batch)))

    def activation_accounting(self, batch: int,
                              seq_len: Optional[int] = None) -> dict:
        return blockwise_lib.activation_accounting(
            self.cfg, batch, seq_len or self.seq_len, self.block_size,
            self.kv_chunk)


BACKENDS = {
    "resnet18": lambda **kw: ResNetBackend(resnet_lib.resnet18_config(), **kw),
    "synthetic_cnn": lambda **kw: ResNetBackend(**kw),
    "transformer": lambda **kw: TransformerBackend(**kw),
}


def make_backend(name: str, config=None, **kw) -> FeatureBackend:
    """Build a registered backend; ``config`` (ALServiceConfig) supplies
    the transformer knobs (block/seq-len/pooling/modality) when given."""
    if name not in BACKENDS:
        raise KeyError(f"unknown backend {name!r}")
    if config is not None and name == "transformer":
        kw.setdefault("block_size", config.model_block_size)
        kw.setdefault("seq_len", config.model_seq_len)
        kw.setdefault("pooling", config.model_pooling)
        kw.setdefault("modality", config.model_modality)
        kw.setdefault("input_dim", config.model_input_dim)
    return BACKENDS[name](**kw)
