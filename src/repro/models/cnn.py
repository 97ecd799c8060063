"""The paper's HFL models.

* ``cnn``  — the HFL task model (Section VI): two 5x5 conv layers with 15
  and 28 output channels, each followed by 2x2 max-pool, then two linear
  layers. Hidden width is chosen so the f32 parameter size matches the
  paper's Table I message sizes (z = 448 KB FashionMNIST / 882 KB CIFAR-10).
* ``mini`` — the IKC mini model ξ: one 2x2 conv (+2x2 max-pool) and one
  linear layer over a 1x10x10 crop; ~10 KB as in Table I.

Everything is NHWC; init is He-normal [41] as in the paper.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import he_normal


def _conv_im2col(x, w):
    """VALID 2D conv, stride 1, as im2col + GEMM: the k*k shifted slices
    are stacked into a (B, oh, ow, k*k*C) patch tensor and multiplied by
    the flattened kernel."""
    kh, kw, ci, co = w.shape
    B, H, W, C = x.shape
    oh, ow = H - kh + 1, W - kw + 1
    patches = jnp.stack([x[:, i:i + oh, j:j + ow, :]
                         for i in range(kh) for j in range(kw)], axis=3)
    return patches.reshape(B, oh, ow, kh * kw * C) @ w.reshape(kh * kw * ci, co)


def _conv_native(x, w):
    """VALID 2D conv, stride 1, as the compiler's own convolution."""
    return jax.lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                        dimension_numbers=("NHWC", "HWIO",
                                                           "NHWC"))


def _conv(x, w):
    """VALID 2D conv, lowered per platform when the program compiles.

    On XLA:CPU the native conv (and its backward) is 6-10x slower than
    im2col + GEMM, so the CPU keeps :func:`_conv_im2col`. Everywhere
    else :func:`_conv_native`: on the TPU the im2col patch tensors have
    minor dimensions of 75 or 375 against 128-wide tiles, and writing
    and reading them back in f32 made local training (a vmapped
    Q*L-deep scan of this conv, forward and backward) memory-bound.

    A kernel with one input channel keeps im2col on every platform:
    vmapped over lanes, the TPU's native conv folds the lanes into one
    dense conv with lanes x out-channels features and lays its output
    out again twice, which on a v5e took a paper-width FMNIST local
    step from 40 to 64 ms. Both lowerings contract at the default
    matmul precision and accumulate in f32.
    """
    if w.shape[2] == 1:
        return _conv_im2col(x, w)
    return jax.lax.platform_dependent(x, w, cpu=_conv_im2col,
                                      default=_conv_native)


def _maxpool2(x):
    """2x2/2 max pool via reshape (dims must be even — they are for both
    dataset geometries); avoids reduce_window's slow CPU backward."""
    B, H, W, C = x.shape
    x = x[:, :H // 2 * 2, :W // 2 * 2, :]   # truncate odd edges (VALID)
    return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))


def cnn_init(key, image_hw: Tuple[int, int], channels: int, n_classes: int = 10,
             hidden: int | None = None) -> Dict:
    """hidden=None picks the paper-size width (226 for 28x28x1, 294 for 32x32x3)."""
    H, W = image_hw
    if hidden is None:
        hidden = 226 if channels == 1 else 294
    h1, w1 = (H - 4) // 2, (W - 4) // 2
    h2, w2 = (h1 - 4) // 2, (w1 - 4) // 2
    flat = h2 * w2 * 28
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "conv1": he_normal(k1, (5, 5, channels, 15), fan_in=5 * 5 * channels),
        "conv2": he_normal(k2, (5, 5, 15, 28), fan_in=5 * 5 * 15),
        "fc1": he_normal(k3, (flat, hidden), fan_in=flat),
        "fc2": he_normal(k4, (hidden, n_classes), fan_in=hidden),
    }


def cnn_apply(params, x) -> jnp.ndarray:
    """x: (B, H, W, C) in [0,1] -> logits (B, n_classes)."""
    x = _maxpool2(jax.nn.relu(_conv(x, params["conv1"])))
    x = _maxpool2(jax.nn.relu(_conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"])
    return x @ params["fc2"]


def mini_init(key, n_classes: int = 10, channels_out: int = 10) -> Dict:
    """Mini model ξ on a 1x10x10 crop: 2x2 conv -> 2x2 pool -> linear."""
    k1, k2 = jax.random.split(key)
    flat = 4 * 4 * channels_out  # (10-1)//2 = 4 after VALID conv + pool
    return {
        "conv": he_normal(k1, (2, 2, 1, channels_out), fan_in=4),
        "fc": he_normal(k2, (flat, n_classes), fan_in=flat),
    }


def mini_apply(params, x) -> jnp.ndarray:
    """x: (B, 10, 10, 1) single-channel random crop."""
    x = _maxpool2(jax.nn.relu(_conv(x, params["conv"])))
    x = x.reshape(x.shape[0], -1)
    return x @ params["fc"]


def mini_preprocess(images: jnp.ndarray, key) -> jnp.ndarray:
    """IKC preprocessing: keep channel 0, random-crop to 10x10."""
    B, H, W, C = images.shape
    kx, ky = jax.random.split(key)
    ox = jax.random.randint(kx, (), 0, H - 10 + 1)
    oy = jax.random.randint(ky, (), 0, W - 10 + 1)
    crop = jax.lax.dynamic_slice(images, (0, ox, oy, 0), (B, 10, 10, 1))
    return crop


def softmax_xent(logits, labels) -> jnp.ndarray:
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (lse - gold).mean()
