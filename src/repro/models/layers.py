"""Core layer primitives (pure-functional: params are plain pytrees)."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp


def he_normal(key, shape, fan_in=None, dtype=jnp.float32):
    """He/Kaiming init [41] — used for both the HFL CNN and transformers."""
    if fan_in is None:
        fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = math.sqrt(2.0 / fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def dense_init(key, d_in, d_out, dtype=jnp.float32):
    return he_normal(key, (d_in, d_out), fan_in=d_in, dtype=dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Adapted:
    """A frozen matrix ``w`` (d_in, d_out) with a trainable low-rank
    update ``a`` (d_in, r), ``b`` (r, d_out): ``linear`` computes
    ``x w + scale (x a) b`` (LoRA, arXiv:2106.09685)."""
    w: jax.Array
    a: jax.Array
    b: jax.Array
    scale: float = dataclasses.field(metadata=dict(static=True))


def linear(w, x):
    """``x @ w`` with ``w`` cast to x's dtype; an ``Adapted`` ``w`` adds
    its low-rank term."""
    if isinstance(w, Adapted):
        dt = x.dtype
        return (x @ w.w.astype(dt)
                + w.scale * ((x @ w.a.astype(dt)) @ w.b.astype(dt)))
    return x @ w.astype(x.dtype)


def embed_init(key, vocab, d_model, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)


# ---------------------------------------------------------------- RMSNorm

def rmsnorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(dt)


# ------------------------------------------------------------------ RoPE

def rope_freqs(head_dim: int, theta: float, positions: jnp.ndarray):
    """positions: (...,) int -> cos/sin of shape (..., head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) or (S, hd//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:  # (S, hd//2) -> broadcast over batch
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:              # (B, S, hd//2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------- SwiGLU

def mlp_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp_apply(params, x):
    g = jax.nn.silu(linear(params["w_gate"], x))
    u = linear(params["w_up"], x)
    return linear(params["w_down"], g * u)


# ---------------------------------------------------- depthwise causal conv

def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv. x: (B, S, C), w: (C, W).

    If `state` (B, W-1, C) is given, runs in streaming mode (decode):
    returns (y, new_state) with y: (B, S, C).
    """
    B, S, C = x.shape
    W = w.shape[1]
    if state is None:
        pad = jnp.zeros((B, W - 1, C), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+W-1, C)
    # gather W shifted views and contract: y[t] = sum_j w[:, j] * xp[t+j]
    ys = 0.0
    for j in range(W):
        ys = ys + xp[:, j:j + S, :] * w[:, j]
    new_state = xp[:, S:, :] if W > 1 else jnp.zeros((B, 0, C), x.dtype)
    return ys, new_state
