"""Inception-v3 — clean-room JAX/flax implementation of the feature extractor
the reference loads as a frozen 2015 GraphDef (``retrain1/retrain.py:26-36,
66-74``: ``classify_image_graph_def.pb``, bottleneck tensor
``pool_3/_reshape:0`` of width 2048, input 299×299×3, 1008 output classes).

Architecture per Szegedy et al., "Rethinking the Inception Architecture for
Computer Vision" (the network in that .pb):

    stem:   conv3x3/2 32 → conv3x3 32 → conv3x3 64(SAME) → maxpool3x3/2
            → conv1x1 80 → conv3x3 192 → maxpool3x3/2
    mixed 35×35 (Inception-A) ×3   (pool-branch widths 32, 64, 64)
    reduction (mixed_3)
    mixed 17×17 (Inception-B) ×4   (7×1/1×7 factorized, widths 128/160/160/192)
    reduction (mixed_8)
    mixed 8×8 (Inception-C) ×2
    global average pool → 2048-d bottleneck → dense → num_classes logits

TPU-first notes: NHWC layout, bfloat16 compute with float32 params/BN stats,
static shapes, global-average-pool instead of the pb's fixed 8×8 AvgPool (so
smaller test inputs still produce a 2048-d bottleneck). BatchNorm runs in
inference mode (frozen trunk — exactly how the reference uses it: features
only, ``retrain1/retrain.py:300-314``); the reference's DecodeJpeg/ResizeBilinear
preprocessing nodes become :func:`preprocess` on the host + ``jax.image.resize``.

No pretrained weights ship in this zero-egress environment;
:func:`load_pretrained` restores a converted ``.npz``/msgpack bundle when one
is available, and random-init weights are used otherwise (transfer-learning
mechanics — bottleneck caching, head training, export — are identical either
way).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

BOTTLENECK_SIZE = 2048  # pool_3/_reshape width, retrain1/retrain.py:30
INPUT_SIZE = 299  # MODEL_INPUT_{WIDTH,HEIGHT}, retrain1/retrain.py:33-34
INPUT_DEPTH = 3
NUM_CLASSES_2015 = 1008  # the 2015 pb's ImageNet head


def preprocess(images_u8: jnp.ndarray) -> jnp.ndarray:
    """uint8/float [0,255] HWC images → model input in [-1, 1].

    Parity with the pb's ``Sub(128) → Mul(2/255)`` input nodes."""
    x = jnp.asarray(images_u8, jnp.float32)
    return (x - 128.0) * (2.0 / 255.0)


class ConvBN(nn.Module):
    """conv → frozen BatchNorm → ReLU, the v3 building block."""

    features: int
    kernel: tuple[int, int]
    strides: tuple[int, int] = (1, 1)
    padding: str = "SAME"
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(
            self.features,
            self.kernel,
            strides=self.strides,
            padding=self.padding,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="conv",
        )(x)
        x = nn.BatchNorm(
            use_running_average=True,
            epsilon=1e-3,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="bn",
        )(x)
        return nn.relu(x)


def _avg_pool_same(x):
    return nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")


class InceptionA(nn.Module):
    pool_features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = self.dtype
        b1 = ConvBN(64, (1, 1), dtype=d, name="branch1x1")(x)
        b5 = ConvBN(48, (1, 1), dtype=d, name="branch5x5_1")(x)
        b5 = ConvBN(64, (5, 5), dtype=d, name="branch5x5_2")(b5)
        b3 = ConvBN(64, (1, 1), dtype=d, name="branch3x3dbl_1")(x)
        b3 = ConvBN(96, (3, 3), dtype=d, name="branch3x3dbl_2")(b3)
        b3 = ConvBN(96, (3, 3), dtype=d, name="branch3x3dbl_3")(b3)
        bp = ConvBN(self.pool_features, (1, 1), dtype=d, name="branch_pool")(_avg_pool_same(x))
        return jnp.concatenate([b1, b5, b3, bp], axis=-1)


class ReductionA(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = self.dtype
        b3 = ConvBN(384, (3, 3), strides=(2, 2), padding="VALID", dtype=d, name="branch3x3")(x)
        bd = ConvBN(64, (1, 1), dtype=d, name="branch3x3dbl_1")(x)
        bd = ConvBN(96, (3, 3), dtype=d, name="branch3x3dbl_2")(bd)
        bd = ConvBN(96, (3, 3), strides=(2, 2), padding="VALID", dtype=d, name="branch3x3dbl_3")(bd)
        bp = nn.max_pool(x, (3, 3), strides=(2, 2), padding="VALID")
        return jnp.concatenate([b3, bd, bp], axis=-1)


class InceptionB(nn.Module):
    channels_7x7: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d, c = self.dtype, self.channels_7x7
        b1 = ConvBN(192, (1, 1), dtype=d, name="branch1x1")(x)
        b7 = ConvBN(c, (1, 1), dtype=d, name="branch7x7_1")(x)
        b7 = ConvBN(c, (1, 7), dtype=d, name="branch7x7_2")(b7)
        b7 = ConvBN(192, (7, 1), dtype=d, name="branch7x7_3")(b7)
        bd = ConvBN(c, (1, 1), dtype=d, name="branch7x7dbl_1")(x)
        bd = ConvBN(c, (7, 1), dtype=d, name="branch7x7dbl_2")(bd)
        bd = ConvBN(c, (1, 7), dtype=d, name="branch7x7dbl_3")(bd)
        bd = ConvBN(c, (7, 1), dtype=d, name="branch7x7dbl_4")(bd)
        bd = ConvBN(192, (1, 7), dtype=d, name="branch7x7dbl_5")(bd)
        bp = ConvBN(192, (1, 1), dtype=d, name="branch_pool")(_avg_pool_same(x))
        return jnp.concatenate([b1, b7, bd, bp], axis=-1)


class ReductionB(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = self.dtype
        b3 = ConvBN(192, (1, 1), dtype=d, name="branch3x3_1")(x)
        b3 = ConvBN(320, (3, 3), strides=(2, 2), padding="VALID", dtype=d, name="branch3x3_2")(b3)
        b7 = ConvBN(192, (1, 1), dtype=d, name="branch7x7x3_1")(x)
        b7 = ConvBN(192, (1, 7), dtype=d, name="branch7x7x3_2")(b7)
        b7 = ConvBN(192, (7, 1), dtype=d, name="branch7x7x3_3")(b7)
        b7 = ConvBN(192, (3, 3), strides=(2, 2), padding="VALID", dtype=d, name="branch7x7x3_4")(b7)
        bp = nn.max_pool(x, (3, 3), strides=(2, 2), padding="VALID")
        return jnp.concatenate([b3, b7, bp], axis=-1)


class InceptionC(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = self.dtype
        b1 = ConvBN(320, (1, 1), dtype=d, name="branch1x1")(x)
        b3 = ConvBN(384, (1, 1), dtype=d, name="branch3x3_1")(x)
        b3 = jnp.concatenate(
            [
                ConvBN(384, (1, 3), dtype=d, name="branch3x3_2a")(b3),
                ConvBN(384, (3, 1), dtype=d, name="branch3x3_2b")(b3),
            ],
            axis=-1,
        )
        bd = ConvBN(448, (1, 1), dtype=d, name="branch3x3dbl_1")(x)
        bd = ConvBN(384, (3, 3), dtype=d, name="branch3x3dbl_2")(bd)
        bd = jnp.concatenate(
            [
                ConvBN(384, (1, 3), dtype=d, name="branch3x3dbl_3a")(bd),
                ConvBN(384, (3, 1), dtype=d, name="branch3x3dbl_3b")(bd),
            ],
            axis=-1,
        )
        bp = ConvBN(192, (1, 1), dtype=d, name="branch_pool")(_avg_pool_same(x))
        return jnp.concatenate([b1, b3, bd, bp], axis=-1)


class InceptionV3(nn.Module):
    """Returns logits; use ``method=bottleneck`` (or ``return_bottleneck``)
    for the 2048-d penultimate features the retrain pipeline consumes."""

    num_classes: int = NUM_CLASSES_2015
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, return_bottleneck: bool = False):
        d = self.compute_dtype
        x = jnp.asarray(x, d)
        # Stem.
        x = ConvBN(32, (3, 3), strides=(2, 2), padding="VALID", dtype=d, name="Conv2d_1a_3x3")(x)
        x = ConvBN(32, (3, 3), padding="VALID", dtype=d, name="Conv2d_2a_3x3")(x)
        x = ConvBN(64, (3, 3), dtype=d, name="Conv2d_2b_3x3")(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="VALID")
        x = ConvBN(80, (1, 1), padding="VALID", dtype=d, name="Conv2d_3b_1x1")(x)
        x = ConvBN(192, (3, 3), padding="VALID", dtype=d, name="Conv2d_4a_3x3")(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="VALID")
        # 35x35.
        x = InceptionA(32, dtype=d, name="Mixed_5b")(x)
        x = InceptionA(64, dtype=d, name="Mixed_5c")(x)
        x = InceptionA(64, dtype=d, name="Mixed_5d")(x)
        x = ReductionA(dtype=d, name="Mixed_6a")(x)
        # 17x17.
        x = InceptionB(128, dtype=d, name="Mixed_6b")(x)
        x = InceptionB(160, dtype=d, name="Mixed_6c")(x)
        x = InceptionB(160, dtype=d, name="Mixed_6d")(x)
        x = InceptionB(192, dtype=d, name="Mixed_6e")(x)
        x = ReductionB(dtype=d, name="Mixed_7a")(x)
        # 8x8.
        x = InceptionC(dtype=d, name="Mixed_7b")(x)
        x = InceptionC(dtype=d, name="Mixed_7c")(x)
        # Global average pool → 2048-d bottleneck (pool_3/_reshape parity).
        bottleneck = jnp.mean(x, axis=(1, 2)).astype(jnp.float32)
        if return_bottleneck:
            return bottleneck
        logits = nn.Dense(
            self.num_classes, dtype=d, param_dtype=jnp.float32, name="logits"
        )(bottleneck.astype(d))
        return logits.astype(jnp.float32)

    def bottleneck(self, x):
        return self(x, return_bottleneck=True)


def create_model(num_classes: int = NUM_CLASSES_2015, compute_dtype=jnp.bfloat16) -> InceptionV3:
    return InceptionV3(num_classes=num_classes, compute_dtype=compute_dtype)


def init_params(model: InceptionV3, seed: int = 0, image_size: int = INPUT_SIZE):
    # Jitted: eager flax init dispatches each of the trunk's ~500 primitives
    # individually, each its own compile + launch. One compiled program
    # runs in milliseconds (and hits the persistent compilation cache
    # across processes).
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, image_size, image_size, INPUT_DEPTH), jnp.float32),
    )
    return variables


def load_pretrained(path: str, model: InceptionV3, image_size: int = INPUT_SIZE):
    """Restore converted weights (msgpack bundle written by
    ``train.checkpoint.export_inference_bundle`` or an ``.npz``). Returns the
    full variables dict {'params': ..., 'batch_stats': ...}."""
    import numpy as np

    from distributed_tensorflow_tpu.train.checkpoint import load_inference_bundle
    from flax import serialization

    # eval_shape: only the tree STRUCTURE is needed (every value is about to
    # be overwritten) — no compile, no device compute.
    shapes = jax.eval_shape(
        model.init,
        jax.random.PRNGKey(0),
        jnp.zeros((1, image_size, image_size, INPUT_DEPTH), jnp.float32),
    )
    template = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), shapes
    )
    if path.endswith(".npz"):
        flat = dict(np.load(path))
        state = serialization.to_state_dict(template)
        missing: list[str] = []

        def fill(prefix, node):
            for k, v in node.items():
                key = f"{prefix}/{k}" if prefix else k
                if isinstance(v, dict):
                    fill(key, v)
                elif key in flat:
                    node[k] = flat[key]
                else:
                    missing.append(key)

        fill("", state)
        if missing:
            # Partial archive: the zero template would silently kill the
            # network (a missing BatchNorm scale zeroes its whole layer).
            # Refill over REAL init values (BN scale/var = 1, random
            # kernels) so absent leaves degrade gracefully, and say so.
            import logging

            logging.getLogger(__name__).warning(
                "%s is missing %d tensors (e.g. %s); filling them with "
                "fresh init values",
                path, len(missing), missing[0],
            )
            template = init_params(model, image_size=image_size)
            state = serialization.to_state_dict(template)
            missing.clear()
            fill("", state)
        return serialization.from_state_dict(template, state)
    restored, _ = load_inference_bundle(path, template=template)
    return restored
