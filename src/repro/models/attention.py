"""Grouped-query attention with RoPE, full/sliding-window masks, KV cache.

Supports:
  * RoPE or no position embedding at all (``cfg.use_rope=False``, NoPE),
    and an explicit softmax scale (``cfg.attn_scale``; 1/sqrt(hd) when 0),
  * train/prefill forward (causal or banded-causal for SWA),
  * single-token decode against a full or rolling (SWA) KV cache,
  * GQA with any n_kv_heads <= n_heads (kv replicated across groups).

The XLA einsum path is the default; the Pallas flash-attention kernel in
``repro.kernels.flash_attention`` is selectable via ``impl='pallas'`` for
the non-cached forward (validated in interpret mode on CPU).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense_init, linear, rope_freqs
from repro.parallel.sharder import NOOP, Sharder

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    D, hd = cfg.d_model, cfg.hd
    return {
        "wq": dense_init(kq, D, cfg.n_heads * hd, dtype),
        "wk": dense_init(kk, D, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(kv, D, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ko, cfg.n_heads * hd, D, dtype),
    }


def _causal_mask(S: int, window: int, offset: int = 0) -> jnp.ndarray:
    """(S, S) additive mask; window>0 adds the sliding-window band."""
    q = jnp.arange(S)[:, None] + offset
    k = jnp.arange(S)[None, :] + offset
    ok = k <= q
    if window > 0:
        ok &= k > q - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _scaled(scores, hd: int, scale: float):
    """Scores times the softmax scale: ``scale``, or 1/sqrt(hd) if 0."""
    if scale:
        return scores * jnp.float32(scale)
    return scores / jnp.sqrt(hd).astype(jnp.float32)


def _sdpa(q, k, v, mask, scale: float = 0.0) -> jnp.ndarray:
    """q: (B,S,Hq,hd) k/v: (B,T,Hkv,hd); mask additive (S,T) or (B,1,1,S,T)."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = _scaled(scores, hd, scale)
    scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, Hq * hd)


CHUNK_Q_THRESHOLD = 8192   # chunk queries above this sequence length
CHUNK_Q = 2048


def _chunked_sdpa(q, k, v, window: int, sharder: Sharder,
                  score_kind: str = "attn_scores_seq",
                  unroll: bool = False, scale: float = 0.0) -> jnp.ndarray:
    """Query-chunked causal attention (flash-style, XLA level).

    Bounds the materialised score tile to (B, H, CHUNK_Q, S) — with the
    kv-sequence axis sharding-constrained over `model` ("attn_scores"),
    so 32k prefill fits even for archs whose head count cannot shard
    16-way (musicgen 24H, scout 40H: unchunked scores were 424/706
    GB/device; see §Perf).
    """
    B, S, Hq, hd = q.shape
    bq = min(CHUNK_Q, S)
    assert S % bq == 0, (S, bq)
    nq = S // bq
    qs = jnp.moveaxis(q.reshape(B, nq, bq, Hq, hd), 1, 0)   # (nq,B,bq,H,hd)
    kT = k
    vT = v

    def chunk(carry, inp):
        i, qc = inp
        rows = i * bq + jnp.arange(bq)[:, None]
        cols = jnp.arange(S)[None, :]
        ok = cols <= rows
        if window > 0:
            ok &= cols > rows - window
        mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
        G = Hq // kT.shape[2]
        qg = qc.reshape(B, bq, kT.shape[2], G, hd)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, kT).astype(jnp.float32)
        scores = _scaled(scores, hd, scale)
        scores = sharder.act(scores, score_kind)
        scores = scores + mask
        probs = jax.nn.softmax(scores, axis=-1).astype(vT.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, vT)
        return carry, out.reshape(B, bq, Hq * hd)

    _, outs = jax.lax.scan(chunk, 0, (jnp.arange(nq), qs),
                           unroll=nq if unroll else 1)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq * hd)


def attn_forward(params, x, cfg: ModelConfig, *, pos_offset: int = 0,
                 sharder: Sharder = NOOP, impl: str = "xla") -> jnp.ndarray:
    """Full-sequence causal attention (train / prefill)."""
    B, S, D = x.shape
    hd = cfg.hd
    q = linear(params["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = linear(params["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = linear(params["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    q = sharder.act(q, "act_heads")
    k = sharder.act(k, "act_kv_heads")
    v = sharder.act(v, "act_kv_heads")
    if cfg.use_rope:
        pos = jnp.arange(S) + pos_offset
        cos, sin = rope_freqs(hd, cfg.rope_theta, pos)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if impl == "pallas":
        if cfg.attn_scale:
            raise NotImplementedError("the flash-attention kernel scales "
                                      "by 1/sqrt(head_dim) only")
        from repro.kernels.flash_attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=True,
                              window=cfg.sliding_window).reshape(B, S, -1)
    elif S > CHUNK_Q_THRESHOLD:
        # long prefill: bound score memory via query chunking
        G = cfg.n_heads // cfg.n_kv_heads
        if G > 1 and cfg.tp_strategy == "heads":
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
            k = sharder.act(k, "act_heads")
            v = sharder.act(v, "act_heads")
        kind = ("attn_scores_heads" if cfg.tp_strategy == "heads"
                else "attn_scores_seq")
        out = _chunked_sdpa(q, k, v, cfg.sliding_window, sharder,
                            score_kind=kind, unroll=cfg.unroll_layers,
                            scale=cfg.attn_scale)
    else:
        # repeat kv to full q heads BEFORE the score einsum: with kv_heads
        # (2/4/8) < the 16-way model axis, the grouped (B,kv,G,S,T) score
        # layout cannot shard 16-way and XLA falls back to "involuntary
        # full rematerialization" (replicated S x T scores). Repeated keys
        # are head-sharded like q, so scores shard (B, Hq/16, S, T).
        G = cfg.n_heads // cfg.n_kv_heads
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
            k = sharder.act(k, "act_heads")
            v = sharder.act(v, "act_heads")
        mask = _causal_mask(S, cfg.sliding_window, 0)
        out = _sdpa(q, k, v, mask, cfg.attn_scale)
    out = linear(params["wo"], out)
    return sharder.act(out, "act_resid")


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Rolling cache if cfg.sliding_window>0 (slots = window), else max_len."""
    slots = cfg.sliding_window if cfg.sliding_window > 0 else max_len
    slots = min(slots, max_len)
    hd = cfg.hd
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, hd), dtype),
    }


def attn_decode(params, x, cache, pos, cfg: ModelConfig, *,
                sharder: Sharder = NOOP) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B, 1, D); pos: scalar int32 (current position).

    RoPE is applied at write time, so cached keys store rotated values.
    """
    B, S1, D = x.shape
    assert S1 == 1
    hd = cfg.hd
    slots = cache["k"].shape[1]
    q = linear(params["wq"], x).reshape(B, 1, cfg.n_heads, hd)
    k = linear(params["wk"], x).reshape(B, 1, cfg.n_kv_heads, hd)
    v = linear(params["wv"], x).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.use_rope:
        cos, sin = rope_freqs(hd, cfg.rope_theta, pos[None])
        q = apply_rope(q, cos[None], sin[None])
        k = apply_rope(k, cos[None], sin[None])
    slot = jnp.mod(pos, slots)
    new_k = jax.lax.dynamic_update_index_in_dim(cache["k"], k[:, 0].astype(cache["k"].dtype), slot, 1)
    new_v = jax.lax.dynamic_update_index_in_dim(cache["v"], v[:, 0].astype(cache["v"].dtype), slot, 1)
    # validity of each slot: token position stored in slot s is the largest
    # p <= pos with p % slots == s; valid iff p > pos - slots and p >= 0.
    s_idx = jnp.arange(slots)
    newest = pos - jnp.mod(pos - s_idx, slots)      # position held by slot s
    valid = newest >= jnp.maximum(0, pos - slots + 1)
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[None, :]  # (1, slots)
    out = _sdpa(q, new_k, new_v, mask, cfg.attn_scale)
    out = linear(params["wo"], out)
    out = sharder.act(out, "act_resid")
    return out, {"k": new_k, "v": new_v}
