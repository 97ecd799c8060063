"""Unified decoder-only model covering all assigned families.

One ``ModelConfig`` drives construction of dense / MoE / SSM / hybrid /
VLM / audio decoders from the same code path:

  * layers are grouped into *super-blocks* of ``SB = lcm(hybrid_period,
    moe.every)`` distinct layer templates; parameters for template j are
    stacked across the n_layers/SB blocks and the whole stack is executed
    with ``lax.scan`` (small HLO, scan-friendly remat);
  * mixed precision: parameters live in f32 (optimizer-owned), compute is
    cast to ``cfg.dtype`` (bf16 on TPU);
  * VLM ("vlm") prepends ``n_prefix_embeds`` dense patch embeddings from
    the (stubbed) vision frontend; audio ("audio") embeds K codebooks and
    predicts K vocab heads (EnCodec-token decoder, MusicGen-style).

API:
  init(key, cfg)                          -> params pytree
  forward(params, batch, cfg, ...)        -> (logits, aux_loss)
  init_cache(cfg, batch, max_len, ...)    -> decode cache pytree
  decode(params, tokens, cache, pos, ...) -> (logits, new_cache)
  loss_fn(params, batch, cfg, ...)        -> (scalar, metrics)
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_lib
from repro.models.layers import dense_init, embed_init, mlp_apply, mlp_init, rmsnorm, rmsnorm_init
from repro.parallel.sharder import NOOP, Sharder


def super_block(cfg: ModelConfig) -> int:
    p = cfg.hybrid_period if cfg.hybrid_period > 0 else 1
    e = cfg.moe.every if cfg.is_moe else 1
    sb = math.lcm(p, e)
    assert cfg.n_layers % sb == 0, (cfg.name, cfg.n_layers, sb)
    return sb


# ------------------------------------------------------------------ init

def _layer_init(key, cfg: ModelConfig, idx: int, dtype):
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model)}
    if cfg.layer_kind(idx) == "attn":
        p["mix"] = attn.attn_init(k1, cfg, dtype)
    else:
        p["mix"] = m2.mamba2_init(k1, cfg, dtype)
    kind = cfg.mlp_kind(idx)
    if kind != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model)
        if kind == "moe":
            p["mlp"] = moe_lib.moe_init(k2, cfg, dtype)
        else:
            p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def init(key, cfg: ModelConfig, dtype=jnp.float32):
    sb = super_block(cfg)
    nb = cfg.n_layers // sb
    keys = jax.random.split(key, 3 + sb)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab_size * max(1, cfg.n_codebooks),
                            cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[1], cfg.d_model, cfg.vocab_size * max(1, cfg.n_codebooks), dtype)
    blocks = []
    for j in range(sb):
        bkeys = jax.random.split(keys[3 + j], nb)
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_layer_init(bkeys[b], cfg, j, dtype) for b in range(nb)])
        blocks.append(stacked)
    params["blocks"] = blocks
    return params


# ----------------------------------------------------------------- embed

def _embed_tokens(params, tokens, cfg: ModelConfig):
    """tokens: (B, S) or (B, S, K) for audio -> (B, S, D) in compute dtype."""
    emb = params["embed"]
    if cfg.family == "audio" and cfg.n_codebooks > 1:
        # codebook k uses rows [k*V, (k+1)*V)
        offs = jnp.arange(cfg.n_codebooks) * cfg.vocab_size
        x = jnp.take(emb, tokens + offs[None, None, :], axis=0).sum(axis=2)
    else:
        x = jnp.take(emb, tokens, axis=0)
    return scale_embeddings(x.astype(cfg.compute_dtype), cfg)


def scale_embeddings(x, cfg: ModelConfig):
    """Token embeddings times ``cfg.embedding_multiplier`` (muP)."""
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _residual(x, h, cfg: ModelConfig):
    """``x + m h`` with m = ``cfg.residual_multiplier`` (muP)."""
    if cfg.residual_multiplier != 1.0:
        h = h * jnp.asarray(cfg.residual_multiplier, h.dtype)
    return x + h


def _lm_head(params, x, cfg: ModelConfig):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w.astype(x.dtype)
    if cfg.family == "audio" and cfg.n_codebooks > 1:
        B, S, _ = logits.shape
        return logits.reshape(B, S, cfg.n_codebooks, cfg.vocab_size)
    return logits


# --------------------------------------------------------------- forward

def _apply_layer(p, x, cfg: ModelConfig, idx: int, sharder: Sharder, impl: str):
    """One layer: the token mixer and the MLP, each pre-normed and added
    to the residual; device scopes ``attention`` / ``mamba`` and ``mlp``."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.layer_kind(idx) == "attn":
        with jax.named_scope("attention"):
            h = rmsnorm(p["norm1"], x, cfg.norm_eps)
            h = attn.attn_forward(p["mix"], h, cfg, sharder=sharder,
                                  impl=impl)
            x = _residual(x, h, cfg)
    else:
        with jax.named_scope("mamba"):
            h = rmsnorm(p["norm1"], x, cfg.norm_eps)
            h = m2.mamba2_forward(p["mix"], h, cfg, sharder=sharder)
            x = _residual(x, h, cfg)
    kind = cfg.mlp_kind(idx)
    if kind != "none":
        with jax.named_scope("mlp"):
            h = rmsnorm(p["norm2"], x, cfg.norm_eps)
            if kind == "moe":
                h, aux = moe_lib.moe_apply(p["mlp"], h, cfg, sharder=sharder)
            else:
                h = mlp_apply(p["mlp"], h)
            x = _residual(x, h, cfg)
    return sharder.act(x, "act_resid"), aux


def backbone(params, x, cfg: ModelConfig, *, sharder: Sharder = NOOP,
             impl: str = "xla", layer_remat: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) embedded input -> (hidden, total_aux_loss).

    ``layer_remat`` recomputes each layer in the backward pass (only
    layer inputs are kept): for a super-block as deep as a whole model
    (a hybrid period of 10), where ``cfg.remat`` keeps nothing back."""
    sb = super_block(cfg)

    def block_body(carry, block_params):
        h, aux = carry
        for j in range(sb):
            # [nested per-layer remat inside the super-block was tried for
            #  jamba's 73.5 GB/dev peak and REFUTED: +30% flops, +2 GB —
            #  the peak is not intra-block recompute; §Perf iteration 6]
            def layer(p, h, j=j):
                return _apply_layer(p, h, cfg, j, sharder, impl)
            if layer_remat:
                layer = jax.checkpoint(layer)
            h, a = layer(block_params[j], h)
            aux = aux + a
        return (h, aux), None

    body = block_body
    if cfg.remat:
        body = jax.checkpoint(block_body, prevent_cse=False)
    nb = cfg.n_layers // sb
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"],
                               unroll=nb if cfg.unroll_layers else 1)
    return x, aux


def forward(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
            sharder: Sharder = NOOP, impl: str = "xla"):
    """Train/prefill forward. batch: tokens (+ prefix_embeds for vlm/audio).

    Returns (logits over token positions, aux_loss).
    """
    x = _embed_tokens(params, batch["tokens"], cfg)
    n_prefix = 0
    if cfg.n_prefix_embeds > 0 and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(x.dtype)
        n_prefix = pre.shape[1]
        x = jnp.concatenate([pre, x], axis=1)
    x = sharder.act(x, "act_resid")
    x, aux = backbone(params, x, cfg, sharder=sharder, impl=impl)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if n_prefix > 0:
        x = x[:, n_prefix:]
    logits = _lm_head(params, x, cfg)
    return sharder.act(logits, "logits"), aux


# ----------------------------------------------------------------- loss

def loss_fn(params, batch, cfg: ModelConfig, *, sharder: Sharder = NOOP,
            impl: str = "xla"):
    logits, aux = forward(params, batch, cfg, sharder=sharder, impl=impl)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold).mean()
    total = nll
    if cfg.is_moe:
        total = total + cfg.moe.router_aux_weight * aux
    return total, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------- decode

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Any:
    """Per-super-block-position stacked caches (for scan over blocks)."""
    dtype = dtype or cfg.compute_dtype
    sb = super_block(cfg)
    nb = cfg.n_layers // sb
    caches = []
    for j in range(sb):
        if cfg.layer_kind(j) == "attn":
            one = attn.init_kv_cache(cfg, batch, max_len, dtype)
        else:
            one = m2.init_ssm_cache(cfg, batch, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (nb,) + x.shape), one))
    return caches


def decode(params, tokens, cache, pos, cfg: ModelConfig, *,
           sharder: Sharder = NOOP):
    """One decode step. tokens: (B, 1) or (B, 1, K); pos: scalar int32."""
    x = _embed_tokens(params, tokens, cfg)
    x = sharder.act(x, "act_resid_decode")
    sb = super_block(cfg)

    def block_body(h, scanned):
        block_params, block_cache = scanned
        new_caches = []
        for j in range(sb):
            p = block_params[j]
            c = block_cache[j]
            hn = rmsnorm(p["norm1"], h, cfg.norm_eps)
            if cfg.layer_kind(j) == "attn":
                hn, nc = attn.attn_decode(p["mix"], hn, c, pos, cfg, sharder=sharder)
            else:
                hn, nc = m2.mamba2_decode(p["mix"], hn, c, cfg, sharder=sharder)
            h = _residual(h, hn, cfg)
            kind = cfg.mlp_kind(j)
            if kind != "none":
                hn = rmsnorm(p["norm2"], h, cfg.norm_eps)
                if kind == "moe":
                    hn, _ = moe_lib.moe_apply(p["mlp"], hn, cfg, sharder=sharder)
                else:
                    hn = mlp_apply(p["mlp"], hn)
                h = _residual(h, hn, cfg)
            new_caches.append(nc)
        return h, new_caches

    nb = cfg.n_layers // sb
    x, new_cache = jax.lax.scan(block_body, x, (params["blocks"], cache),
                                unroll=nb if cfg.unroll_layers else 1)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x, cfg)
    return logits, new_cache
