"""Per-device local training (paper eq. (1)) — vmapped full-batch GD.

Device datasets are padded to a common ``Dmax`` with a validity mask so the
whole scheduled cohort trains as one vmapped, jitted computation.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp



def masked_loss(apply_fn: Callable, params, X, y, mask) -> jnp.ndarray:
    """Mean CE over valid samples only. X: (Dmax, ...), mask: (Dmax,)."""
    logits = apply_fn(params, X)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    per = (lse - gold) * mask
    return jnp.sum(per) / jnp.maximum(jnp.sum(mask), 1.0)


def local_sgd(apply_fn: Callable, params, X, y, mask, L: int, lr: float):
    """L full-batch GD steps (eq. (1)) on one device."""
    grad_fn = jax.grad(masked_loss, argnums=1)

    def body(p, _):
        g = grad_fn(apply_fn, p, X, y, mask)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), None

    params, _ = jax.lax.scan(body, params, None, length=L)
    return params


def cohort_local_sgd(apply_fn: Callable, params_per_dev, X, y, mask,
                     L: int, lr: float):
    """vmap of local_sgd over the device axis.

    params_per_dev: pytree with leading device axis; X: (H, Dmax, ...).
    Its ops carry the named scope ``local_train`` in every program that
    inlines it (the fused round, the async dispatch, the sweep).
    """
    def fn(p, xx, yy, mm):
        return local_sgd(apply_fn, p, xx, yy, mm, L, lr)
    with jax.named_scope("local_train"):
        return jax.vmap(fn)(params_per_dev, X, y, mask)
