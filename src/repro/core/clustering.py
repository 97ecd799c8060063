"""K-means device clustering (Algorithm 2) + Adjusted Rand Index (eq. 28).

The K-means distance computation routes through the Pallas pairwise-
distance kernel (``repro.kernels.kmeans_dist``) when ``use_kernel=True``
(interpret mode on CPU), with a pure-jnp fallback that is also the
kernel's oracle.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def pairwise_sq_dists(x: jnp.ndarray, c: jnp.ndarray,
                      use_kernel: bool = False) -> jnp.ndarray:
    """x: (N, D), c: (K, D) -> (N, K) squared euclidean distances."""
    if use_kernel:
        from repro.kernels.kmeans_dist.ops import pairwise_sq_dists as pk
        return pk(x, c)
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    cc = jnp.sum(c * c, axis=1)[None, :]
    # full f32 like the kernel: a bf16 pass (the TPU default) would move
    # near-tie argmins and so the cluster labels
    xc = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(xx + cc - 2.0 * xc, 0.0)


def _kmeans_pp_init(key, x: jnp.ndarray, k: int,
                    use_kernel: bool = False) -> jnp.ndarray:
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centers = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    def body(i, carry):
        centers, key = carry
        d = pairwise_sq_dists(x, centers, use_kernel=use_kernel)  # (n, k)
        # only first i centers are valid
        valid = jnp.arange(k) < i
        d = jnp.where(valid[None, :], d, jnp.inf)
        mind = jnp.min(d, axis=1)
        key, ks = jax.random.split(key)
        probs = mind / jnp.maximum(jnp.sum(mind), 1e-12)
        nxt = jax.random.choice(ks, n, p=probs)
        return centers.at[i].set(x[nxt]), key

    centers, _ = jax.lax.fori_loop(1, k, body, (centers, key))
    return centers


@functools.partial(jax.jit, static_argnames=("k", "iters", "use_kernel"))
def kmeans(key, x: jnp.ndarray, k: int, iters: int = 50,
           use_kernel: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Lloyd's algorithm with kmeans++ init. Returns (labels (N,), centers)."""
    x = x.astype(jnp.float32)
    centers = _kmeans_pp_init(key, x, k, use_kernel=use_kernel)

    def step(carry, _):
        centers = carry
        d = pairwise_sq_dists(x, centers, use_kernel=use_kernel)
        lab = jnp.argmin(d, axis=1)
        oh = jax.nn.one_hot(lab, k, dtype=jnp.float32)       # (N, k)
        counts = oh.sum(0)
        sums = jnp.matmul(oh.T, x, precision=jax.lax.Precision.HIGHEST)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None],
                        centers)
        return new, None

    centers, _ = jax.lax.scan(step, centers, None, length=iters)
    lab = jnp.argmin(pairwise_sq_dists(x, centers, use_kernel=use_kernel), axis=1)
    return lab, centers


def kmeans_best_of(key, x, k: int, restarts: int = 8, iters: int = 50,
                   use_kernel: bool = False):
    """Multiple restarts, keep lowest inertia."""
    best = (None, None, np.inf)
    for r, kk in enumerate(jax.random.split(key, restarts)):
        lab, cen = kmeans(kk, x, k, iters, use_kernel)
        d = pairwise_sq_dists(x, cen, use_kernel=False)
        inertia = float(jnp.sum(jnp.min(d, axis=1)))
        if inertia < best[2]:
            best = (lab, cen, inertia)
    return best[0], best[1]


def adjusted_rand_index(pred: np.ndarray, truth: np.ndarray) -> float:
    """Pair-counting ARI (eq. 28 uses the unadjusted Rand pair counts; we
    report the standard adjusted form as in [42]/sklearn)."""
    pred = np.asarray(pred)
    truth = np.asarray(truth)
    n = len(pred)
    # contingency
    pu, pi = np.unique(pred, return_inverse=True)
    tu, ti = np.unique(truth, return_inverse=True)
    cont = np.zeros((len(pu), len(tu)), dtype=np.int64)
    np.add.at(cont, (pi, ti), 1)
    def c2(v):
        return v * (v - 1) // 2
    sum_ij = c2(cont).sum()
    a = c2(cont.sum(axis=1)).sum()
    b = c2(cont.sum(axis=0)).sum()
    total = c2(n)
    # promote before multiplying: a*b in int64 overflows (silently) once
    # pair counts pass ~3e9, i.e. N ~ 1e5
    exp = float(a) * float(b) / float(total) if total else 0.0
    mx = (a + b) / 2.0
    if mx == exp:
        return 1.0
    return float((sum_ij - exp) / (mx - exp))
