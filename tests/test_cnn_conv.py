"""The CNN's two VALID-conv lowerings agree, and the CPU keeps im2col.

``models.cnn._conv`` picks its lowering when the program compiles:
im2col + GEMM on the CPU, the compiler's own convolution elsewhere (the
TPU) unless the kernel has one input channel. Both are called directly
here, as local training calls them: vmapped over lanes, each lane with
its own kernel, forward and both gradients. Which one the TPU program
takes for each conv is checked by ``tests/test_tpu_compile.py``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import cnn

LANES, BATCH = 3, 4

# (input h/w, input channels, kernel size, output channels)
SHAPES = {
    "fmnist_conv1": (28, 1, 5, 15),
    "fmnist_conv2": (12, 15, 5, 28),
    "cifar_conv1": (32, 3, 5, 15),
    "mini_conv": (10, 1, 2, 10),
}


def _lane_inputs(hw, ci, k, co, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(kx, (LANES, BATCH, hw, hw, ci), jnp.float32)
    w = jax.random.normal(kw, (LANES, k, k, ci, co), jnp.float32)
    return x, w / np.sqrt(k * k * ci)


def _forward_and_grads(conv, x, w):
    """Per-lane output, and the gradients of a per-lane scalar of it with
    respect to the input and the kernel, vmapped over lanes."""
    def lane(xx, ww):
        def loss(xx, ww):
            return jnp.sum(jnp.tanh(conv(xx, ww)))
        return conv(xx, ww), jax.grad(loss, argnums=(0, 1))(xx, ww)
    return jax.jit(jax.vmap(lane))(x, w)


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_native_conv_matches_im2col_forward_and_gradients(shape):
    """Both lowerings contract in full f32 here
    (``default_matmul_precision("highest")``), so they differ only in
    the order of the k*k*C-term sums: rtol 1e-5 of each tensor's scale
    covers that order and nothing else."""
    x, w = _lane_inputs(*SHAPES[shape])
    with jax.default_matmul_precision("highest"):
        y_n, (gx_n, gw_n) = _forward_and_grads(cnn._conv_native, x, w)
        y_i, (gx_i, gw_i) = _forward_and_grads(cnn._conv_im2col, x, w)
    hw, _, k, co = SHAPES[shape]
    assert y_n.shape == (LANES, BATCH, hw - k + 1, hw - k + 1, co)
    for got, want in ((y_n, y_i), (gx_n, gx_i), (gw_n, gw_i)):
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5 * scale)


def test_cnn_apply_on_cpu_is_bitwise_im2col(monkeypatch):
    """On the CPU the platform switch lowers to today's im2col code: the
    whole CNN's logits are the same bits as with ``_conv_im2col``
    called directly. Each side is traced afresh (a new lambda), since
    ``jit``'s cache does not see the patched module global."""
    assert jax.default_backend() == "cpu"
    params = cnn.cnn_init(jax.random.PRNGKey(1), (28, 28), 1)
    x = jax.random.uniform(jax.random.PRNGKey(2), (8, 28, 28, 1))
    switched = jax.jit(lambda p, x: cnn.cnn_apply(p, x))(params, x)
    monkeypatch.setattr(cnn, "_conv", cnn._conv_im2col)
    direct = jax.jit(lambda p, x: cnn.cnn_apply(p, x))(params, x)
    np.testing.assert_array_equal(np.asarray(switched), np.asarray(direct))
