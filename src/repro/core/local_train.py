"""Per-device local training (paper eq. (1)) — vmapped full-batch GD.

Device datasets are padded to a common ``Dmax`` with a validity mask so the
whole scheduled cohort trains as one vmapped, jitted computation.

A payload with a frozen base (``ModelSpec.frozen_init_fn``) takes it as
``apply_fn(params, X, frozen)``: the base is one array tree shared by
every lane (unbatched under ``vmap``), and only ``params`` — the
trainable tree — is differentiated and returned.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def apply_model(apply_fn: Callable, params, X, frozen=None):
    """Logits of ``params`` on ``X``; the frozen base, if any, goes in
    as the third argument."""
    if frozen is None:
        return apply_fn(params, X)
    return apply_fn(params, X, frozen)


def masked_loss(apply_fn: Callable, params, X, y, mask,
                frozen=None) -> jnp.ndarray:
    """Mean CE over valid samples only. X: (Dmax, ...), mask: (Dmax,)."""
    logits = apply_model(apply_fn, params, X, frozen)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    per = (lse - gold) * mask
    return jnp.sum(per) / jnp.maximum(jnp.sum(mask), 1.0)


def local_sgd(apply_fn: Callable, params, X, y, mask, L: int, lr: float,
              frozen=None):
    """L full-batch GD steps (eq. (1)) on one device; gradients of the
    trainable ``params`` only."""
    grad_fn = jax.grad(masked_loss, argnums=1)

    def body(p, _):
        g = grad_fn(apply_fn, p, X, y, mask, frozen)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), None

    params, _ = jax.lax.scan(body, params, None, length=L)
    return params


def cohort_local_sgd(apply_fn: Callable, params_per_dev, X, y, mask,
                     L: int, lr: float, frozen=None, lane_chunk: int = 0):
    """vmap of local_sgd over the device axis.

    params_per_dev: pytree with leading device axis; X: (H, Dmax, ...).
    ``frozen``: the payload's base, shared by all lanes. ``lane_chunk``
    > 0 trains the lanes that many at a time, one chunk after another
    (``lax.map``), to bound the activations: H is padded up to whole
    chunks with copies of the last lane, which are dropped.
    Its ops carry the named scope ``local_train`` in every program that
    inlines it (the fused round, the async dispatch, the sweep).
    """
    def fn(p, xx, yy, mm):
        return local_sgd(apply_fn, p, xx, yy, mm, L, lr, frozen)
    with jax.named_scope("local_train"):
        H = X.shape[0]
        if not 0 < lane_chunk < H:
            return jax.vmap(fn)(params_per_dev, X, y, mask)
        n = -(-H // lane_chunk)
        pad = n * lane_chunk - H

        def split(a):
            if pad:
                a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                            mode="edge")
            return a.reshape((n, lane_chunk) + a.shape[1:])

        def join(a):
            a = a.reshape((n * lane_chunk,) + a.shape[2:])
            return a[:H] if pad else a

        chunks = jax.tree.map(split, (params_per_dev, X, y, mask))
        out = jax.lax.map(lambda a: jax.vmap(fn)(*a), chunks)
        return jax.tree.map(join, out)
