"""Pallas TPU kernel: pairwise squared distances for K-means (Algorithm 2).

The clustering hot spot is N devices x P auxiliary-model weights against
K centroids. TPU adaptation: the ||x||^2 - 2 x.c + ||c||^2 expansion turns
the distance matrix into one MXU matmul plus row/col norms; we tile N into
MXU-aligned 128-row blocks held in VMEM, tile the centroid axis into
128-wide panels, and stream 512-wide feature blocks when P is large.

Grid: (N/BN, K/BK, P/BP). The feature axis is the *reduction* axis,
iterated innermost with an f32 VMEM scratch accumulator; each (BN, BK)
output block is finalised (clamped at 0) on its last feature step. The
blocked K axis means clustering at N=1e5 never materialises a monolithic
(N, Kp) panel per grid step — only (BN, BK) tiles live in VMEM.

VMEM budget per step: BN*BP + BK*BP + 2*BN*BK f32 ≈ 0.5 MiB « 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BN = 128     # device rows per block  (MXU lane-aligned)
BP = 512     # feature columns per reduction step
BK = 128     # centroid columns per block

def _kernel(x_ref, c_ref, out_ref, acc_ref, *, n_p_blocks: int):
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)           # (BN, BP)
    c = c_ref[...].astype(jnp.float32)           # (BK, BP)
    xx = jnp.sum(x * x, axis=1, keepdims=True)   # (BN, 1)
    cc = jnp.sum(c * c, axis=1)[None, :]         # (1, BK)
    acc_ref[...] += xx + cc - 2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(pi == n_p_blocks - 1)
    def _done():
        out_ref[...] = jnp.maximum(acc_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pairwise_sq_dists_pallas(x: jnp.ndarray, c: jnp.ndarray,
                             interpret: bool = True) -> jnp.ndarray:
    """x: (N, P), c: (K, P) -> (N, K) f32. Pads to tile multiples."""
    N, P = x.shape
    K = c.shape[0]
    xp = jnp.pad(x, ((0, (-N) % BN), (0, (-P) % BP)))
    cp = jnp.pad(c, ((0, (-K) % BK), (0, (-P) % BP)))
    Np, Pp = xp.shape
    Kp = cp.shape[0]
    n_p_blocks = Pp // BP

    out = pl.pallas_call(
        functools.partial(_kernel, n_p_blocks=n_p_blocks),
        grid=(Np // BN, Kp // BK, n_p_blocks),
        in_specs=[
            pl.BlockSpec((BN, BP), lambda i, j, p: (i, p)),
            pl.BlockSpec((BK, BP), lambda i, j, p: (j, p)),
        ],
        out_specs=pl.BlockSpec((BN, BK), lambda i, j, p: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BN, BK), jnp.float32)],
        interpret=interpret,
    )(xp, cp)
    return out[:N, :K]
