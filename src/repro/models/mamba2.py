"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) block in JAX.

Two execution paths, both pure jnp:
  * ``ssd_chunked``  — the blocked SSD algorithm (intra-chunk quadratic
    "attention-like" term + inter-chunk recurrence via lax.scan over
    chunks). This is the train/prefill path; chunk size is MXU-friendly.
  * ``ssd_recurrent_step`` — O(1)-state single-token decode update.

A naive full-sequence recurrence (``ssd_reference``) is kept for tests:
chunked and reference must agree to ~1e-4 in f32.

Layout conventions:
  x        (B, S, H, P)      P = head_dim
  dt       (B, S, H)
  A_log    (H,)              A = -exp(A_log) (scalar per head, SSD)
  B_, C_   (B, S, G, N)      N = d_state, G groups broadcast to heads
  state    (B, H, P, N)
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (causal_conv1d, dense_init, linear, rmsnorm,
                                 rmsnorm_init)
from repro.parallel.sharder import NOOP, Sharder


# --------------------------------------------------------------- params

def mamba2_init(key, cfg: ModelConfig, dtype=jnp.float32):
    """Per-segment projections instead of one fused in_proj.

    The fused (D, 2*di+2*G*N+nh) projection's split boundaries do not
    align with 16-way model-axis shards, forcing XLA to replicate the
    whole matmul (~9x FLOP waste measured on mamba2-2.7b train_4k; §Perf
    hillclimb). Separate z/x/B/C/dt projections shard cleanly, and the
    depthwise conv distributes over the concatenation, so the math is
    identical.
    """
    s = cfg.ssm
    D = cfg.d_model
    di = s.d_inner(D)
    nh = s.n_heads(D)
    gn = s.n_groups * s.d_state
    k1, k2, k3, k4, k5, k6, k7, k8, k9 = jax.random.split(key, 9)
    def conv(k, c):
        return (jax.random.normal(k, (c, s.conv_width)) * 0.1).astype(dtype)
    bias = {}
    if s.conv_bias:
        bias = {f"conv_{n}_bias": jnp.zeros((c,), dtype)
                for n, c in (("x", di), ("b", gn), ("c", gn))}
    return {**bias,
        "wz": dense_init(k1, D, di, dtype),
        "wx": dense_init(k2, D, di, dtype),
        "wb": dense_init(k3, D, gn, dtype),
        "wc": dense_init(k4, D, gn, dtype),
        "wdt": dense_init(k5, D, nh, dtype),
        "conv_x": conv(k6, di),
        "conv_b": conv(k7, gn),
        "conv_c": conv(k8, gn),
        "A_log": jnp.zeros((nh,), jnp.float32),           # A = -1 at init
        "D_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.full((nh,), -1.0, jnp.float32),    # softplus(-1) ~ 0.31
        "gate_norm": rmsnorm_init(di),
        "out_proj": dense_init(k9, di, D, dtype),
    }


def _project(params, hidden):
    """hidden @ {wz,wx,wb,wc,wdt} -> (z, x, B_, C_, dt)."""
    return tuple(linear(params[n], hidden)
                 for n in ("wz", "wx", "wb", "wc", "wdt"))


def _conv_act(params, name, u, state=None):
    """Published Mamba-2 order: causal conv of ``u``, its bias if the
    block has one, then SiLU. Returns (activation, new conv state)."""
    y, state = causal_conv1d(u, params[f"conv_{name}"].astype(u.dtype),
                             state=state)
    if f"conv_{name}_bias" in params:
        y = y + params[f"conv_{name}_bias"].astype(u.dtype)
    return jax.nn.silu(y), state


# ----------------------------------------------------------- SSD math

def ssd_reference(x, dt, A, B_, C_, chunk=None):
    """Naive per-timestep recurrence (oracle). Shapes as module docstring."""
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    G = B_.shape[2]
    Bh = jnp.repeat(B_, H // G, axis=2)   # (B,S,H,N)
    Ch = jnp.repeat(C_, H // G, axis=2)
    dA = jnp.exp(dt * A)                  # (B,S,H)

    def step(state, inp):
        xt, dtt, dAt, Bt, Ct = inp
        state = dAt[..., None, None] * state + (dtt[..., None, None] * xt[..., None]) * Bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", state, Ct)
        return state, y

    state0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dA, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Bh, 1, 0).astype(jnp.float32),
          jnp.moveaxis(Ch, 1, 0).astype(jnp.float32))
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1)         # (B,S,H,P)


def ssd_chunked(x, dt, A, B_, C_, chunk: int, sharder: Sharder = NOOP):
    """Blocked SSD. Returns (B,S,H,P) in f32.

    The head axis H is explicitly sharding-constrained on every chunked
    intermediate: without the constraints XLA replicates the (cs, cs, H)
    decay/score tensors across the model axis (measured 64.8 GB/device
    temp for mamba2-2.7b train_4k; see EXPERIMENTS.md §Perf iteration 1).
    """
    Bsz, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    assert S % chunk == 0, f"seq {S} % chunk {chunk} != 0"
    nc, cs = S // chunk, chunk
    f32 = jnp.float32

    xr = x.reshape(Bsz, nc, cs, H, P).astype(f32)
    xr = sharder.act(xr, "ssm_chunk_x")
    dtr = dt.reshape(Bsz, nc, cs, H).astype(f32)
    Br = jnp.repeat(B_, H // G, axis=2).reshape(Bsz, nc, cs, H, N).astype(f32)
    Cr = jnp.repeat(C_, H // G, axis=2).reshape(Bsz, nc, cs, H, N).astype(f32)
    Br = sharder.act(Br, "ssm_chunk_bc")
    Cr = sharder.act(Cr, "ssm_chunk_bc")

    dA = dtr * A                                            # (B,nc,cs,H) log-decay
    cum = jnp.cumsum(dA, axis=2)                            # inclusive cumsum
    cum = sharder.act(cum, "ssm_chunk_cum")
    xdt = xr * dtr[..., None]

    # ---- intra-chunk (quadratic within chunk)
    # L[i,j] = exp(cum[i] - cum[j]) for i >= j  (i attends to j<=i)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # (B,nc,i,j,H)
    li = jnp.arange(cs)
    causal = (li[:, None] >= li[None, :])[None, None, :, :, None]
    # mask before the exp: above the diagonal diff > 0 and exp(diff)
    # overflows once a chunk decays past e^88, and inf times the zero
    # gradient of the masked entries is NaN
    L = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    L = sharder.act(L, "ssm_chunk_ij")
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cr, Br) * L
    scores = sharder.act(scores, "ssm_chunk_ij")
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt)

    # ---- per-chunk terminal states
    # S_c = sum_j exp(cum[last] - cum[j]) * B_j (x dt)_j
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)         # (B,nc,cs,H)
    states = jnp.einsum("bcjh,bcjhn,bcjhp->bchpn", decay_to_end, Br, xdt)

    # ---- inter-chunk recurrence over chunks
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # (B,nc,H)

    def step(carry, inp):
        st, dec = inp
        new = dec[:, :, None, None] * carry + st
        return new, carry                                   # emit state BEFORE this chunk

    init = jnp.zeros((Bsz, H, P, N), f32)
    _, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (B,nc,H,P,N)

    # ---- inter-chunk contribution: y[i] += exp(cum[i]) * C_i . state_prev
    in_decay = jnp.exp(cum)                                 # decay from chunk start
    y_inter = jnp.einsum("bcih,bcihn,bchpn->bcihp", in_decay, Cr, prev_states)

    return (y_intra + y_inter).reshape(Bsz, S, H, P)


def ssd_recurrent_step(state, x, dt, A, B_, C_):
    """Single-token update. x:(B,H,P) dt:(B,H) B_/C_:(B,G,N) state:(B,H,P,N)."""
    H = x.shape[1]
    G = B_.shape[1]
    Bh = jnp.repeat(B_, H // G, axis=1)
    Ch = jnp.repeat(C_, H // G, axis=1)
    dA = jnp.exp(dt * A)
    state = dA[..., None, None] * state + (dt[..., None, None] * x[..., None]) * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return state, y


# ------------------------------------------------------------ full block

def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    conv_dim = di + 2 * s.n_groups * s.d_state   # [x | B | C] stream
    return {
        "ssm": jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim,
                          s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
    }


def mamba2_forward(params, hidden, cfg: ModelConfig, *,
                   sharder: Sharder = NOOP) -> jnp.ndarray:
    """Full-sequence forward. hidden: (B, S, D)."""
    s = cfg.ssm
    B, S, D = hidden.shape
    di = s.d_inner(D)
    nh = s.n_heads(D)
    z, x, B_, C_, dt = _project(params, hidden)
    x, _ = _conv_act(params, "x", x)
    B_, _ = _conv_act(params, "b", B_)
    C_, _ = _conv_act(params, "c", C_)
    x = x.reshape(B, S, nh, s.head_dim)
    x = sharder.act(x, "ssm_heads")
    B_ = B_.reshape(B, S, s.n_groups, s.d_state)
    C_ = C_.reshape(B, S, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y = ssd_chunked(x, dt, A, B_, C_, min(s.chunk, S), sharder=sharder)
    y = y + params["D_skip"][None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(hidden.dtype)
    y = rmsnorm(params["gate_norm"], y * jax.nn.silu(z))
    out = linear(params["out_proj"], y)
    return sharder.act(out, "act_resid")


def mamba2_decode(params, hidden, cache, cfg: ModelConfig, *,
                  sharder: Sharder = NOOP) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. hidden: (B, 1, D)."""
    s = cfg.ssm
    B, S1, D = hidden.shape
    di = s.d_inner(D)
    nh = s.n_heads(D)
    gn = s.n_groups * s.d_state
    z, x, B_, C_, dt = _project(params, hidden)
    # one shared rolling conv state over the [x|B|C] stream
    st_x, st_b, st_c = jnp.split(cache["conv"], [di, di + gn], axis=-1)
    x, st_x = _conv_act(params, "x", x, st_x)
    B_, st_b = _conv_act(params, "b", B_, st_b)
    C_, st_c = _conv_act(params, "c", C_, st_c)
    conv_state = jnp.concatenate([st_x, st_b, st_c], axis=-1)
    x = x[:, 0].reshape(B, nh, s.head_dim)
    B_ = B_.reshape(B, s.n_groups, s.d_state)
    C_ = C_.reshape(B, s.n_groups, s.d_state)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    state, y = ssd_recurrent_step(cache["ssm"], x.astype(jnp.float32), dt1, A, B_, C_)
    y = y + params["D_skip"][None, :, None] * x.astype(jnp.float32)
    y = y.reshape(B, 1, di).astype(hidden.dtype)
    y = rmsnorm(params["gate_norm"], y * jax.nn.silu(z))
    out = linear(params["out_proj"], y)
    out = sharder.act(out, "act_resid")
    return out, {"ssm": state, "conv": conv_state}
