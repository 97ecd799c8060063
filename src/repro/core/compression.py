"""Communication compression for uplink model updates — codecs + error
feedback, charged end-to-end in the cost model.

A family of jit-compatible *update codecs* applied to parameter deltas
against the reference model the sender pulled (device→edge: the edge
model at dispatch; edge→cloud: the global model):

* ``none``       — identity: the message is the sender's model itself,
                   aggregated exactly as eqs. (2)/(3) read; it carries no
                   state and no key, and traces no codec operation.
* ``bf16_delta`` — casts the delta to bfloat16 (16 bits/param).
* ``int8``       — stochastic-rounding quantization to int8 with one
                   per-tensor (per message, per leaf) f32 scale
                   ``max|x|/127``; unbiased: E[decode(encode(x))] = x.
* ``topk``       — magnitude top-k sparsification per leaf
                   (k = max(1, round(topk_frac·n))), sent as
                   (index, value) pairs.

Each lossy codec carries an **error-feedback residual** per sender
(Seide et al. 2014 / Karimireddy et al. 2019): the encoder compresses
``x = delta + residual`` and keeps ``residual' = x - decode(encode(x))``
for the next round, so the *accumulated* compression error stays bounded
and compressed training remains unbiased over rounds (property-tested in
``tests/test_compression.py``).

The compressed per-message size (:func:`message_bits`) is what the cost
model charges: engines patch ``SystemParams.model_bits`` with it, so
``t_com``/``e_com``/``cloud_cost`` (eqs. (7)-(8), (11)-(12)) and the
convex resource allocation all see the codec's actual bits-per-message.

The engines never ask which codec is active. They thread the codec's
state (:func:`init_state`) and key (:func:`round_key`, :func:`split`,
:func:`fold_in`) through unconditionally — both are ``None`` for the
identity codec — and ship every uplink through :func:`uplink`, then
either aggregate the messages (:func:`aggregate`, eqs. (2)/(3) over a
hop's :class:`Panel`) or buffer what the receiver reconstructs
(:func:`receive`, the async edge buffers). A lossy message is each
leaf's wire form ``(q, scale)``; the sync hops aggregate it in delta
space, ``base + Σ w·decode(q, scale)``, and a kernel panel streams the
undecoded q into ``kernels/hier_agg`` ``masked_decode_aggregate``.

Encoding is row-wise: leaves carry a leading message axis (H devices or
M edges) and every row is one message. ``encode_rows``/``decode_rows``
are the single source of codec math.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

CODECS = ("none", "bf16_delta", "int8", "topk")


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Uplink update-codec knobs (hashable — used as a static jit arg).

    ``codec="none"`` is the identity codec (see the module docstring).
    ``error_feedback`` keeps a per-sender residual accumulator across
    rounds; ``seed`` feeds the stochastic-rounding key stream (derived
    per (lane, round), never carried — host-loop and fused-scan engines
    draw identical keys).
    """
    codec: str = "none"             # none | bf16_delta | int8 | topk
    topk_frac: float = 0.05         # fraction of entries kept per leaf
    error_feedback: bool = True
    seed: int = 0

    def __post_init__(self):
        if self.codec not in CODECS:
            raise ValueError(f"unknown codec {self.codec!r}; "
                             f"valid: {CODECS}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], "
                             f"got {self.topk_frac}")

    @property
    def active(self) -> bool:
        return self.codec != "none"


IDENTITY = CompressionConfig()      # the default codec: none


def _topk_k(cfg: CompressionConfig, n: int) -> int:
    return min(n, max(1, int(round(cfg.topk_frac * n))))


def message_bits(cfg: CompressionConfig, params) -> float:
    """Bits per uplink message for one model shaped like ``params``.

    ``none`` counts raw parameter bytes; ``int8`` adds one f32 scale per
    leaf; ``topk`` charges (value + index) per kept entry, indices at
    ceil(log2(n)) bits.
    """
    leaves = jax.tree.leaves(params)
    if cfg.codec == "none":
        return float(sum(leaf.size * leaf.dtype.itemsize * 8
                         for leaf in leaves))
    if cfg.codec == "bf16_delta":
        return float(sum(leaf.size * 16 for leaf in leaves))
    if cfg.codec == "int8":
        return float(sum(leaf.size * 8 + 32 for leaf in leaves))
    # topk: (f32 value, index) pairs per leaf
    bits = 0.0
    for leaf in leaves:
        n = leaf.size
        bits += _topk_k(cfg, n) * (32 + max(1, math.ceil(math.log2(n))))
    return float(bits)


def require_identity(cfg: CompressionConfig, reason: str) -> None:
    """Refuse a lossy codec (``ValueError(reason)``) on a path that
    ships raw payloads."""
    if cfg.active:
        raise ValueError(reason)


def init_state(cfg: CompressionConfig, params, n_rows: int):
    """Zero error-feedback residuals: one per sender row, f32, shaped
    like ``params`` with a leading ``(n_rows,)`` axis. Returns None for
    the identity codec (no state to carry)."""
    if not cfg.active:
        return None
    return jax.tree.map(
        lambda p: jnp.zeros((n_rows,) + p.shape, jnp.float32), params)


# ------------------------------------------------------- row-wise codecs

def encode_rows(cfg: CompressionConfig, key, x):
    """Encode (R, p) f32 rows — R messages of one p-element tensor.

    Returns ``(q, scale)``: the wire form. q is (R, p) int8 (``int8``),
    bf16 (``bf16_delta``) or dense-masked f32 (``topk``, the simulated
    form of the (index, value) pairs); scale is (R,) f32 per-message
    decode scales (ones where the codec has none).
    """
    R = x.shape[0]
    ones = jnp.ones((R,), jnp.float32)
    if cfg.codec == "bf16_delta":
        return x.astype(jnp.bfloat16), ones
    if cfg.codec == "int8":
        absmax = jnp.max(jnp.abs(x), axis=1)
        scale = jnp.maximum(absmax / 127.0, 1e-30)
        u = jax.random.uniform(key, x.shape)
        q = jnp.clip(jnp.floor(x / scale[:, None] + u), -127, 127)
        return q.astype(jnp.int8), scale
    if cfg.codec == "topk":
        k = _topk_k(cfg, x.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(x), k)                  # (R, k)
        keep = jnp.zeros_like(x).at[jnp.arange(R)[:, None], idx].set(1.0)
        return x * keep, ones
    raise ValueError(f"encode_rows on codec {cfg.codec!r}")


def decode_rows(cfg: CompressionConfig, q, scale):
    """Decode the wire form back to (R, p) f32 rows."""
    return q.astype(jnp.float32) * scale[:, None]


def encode_leaf(cfg: CompressionConfig, key, delta, resid):
    """Error-feedback encode of one leaf: (R, p) delta + residual.

    Returns ``(q, scale, new_resid)`` — the wire form plus the updated
    residual ``x - decode(q, scale)`` (pass-through when
    ``error_feedback=False``).
    """
    x = delta + resid if cfg.error_feedback else delta
    q, scale = encode_rows(cfg, key, x)
    if cfg.error_feedback:
        resid = x - decode_rows(cfg, q, scale)
    return q, scale, resid


def round_key(cfg: CompressionConfig, lane_seed, round_idx):
    """Deterministic per-(lane, round) codec key — stateless, so the
    host-loop and fused-scan engines draw identical randomness without
    threading a key through their carries. ``lane_seed`` and
    ``round_idx`` broadcast: arrays give a key per element. None for the
    identity codec, which draws nothing."""
    if not cfg.active:
        return None
    base = jax.random.PRNGKey(cfg.seed)
    seeds, rounds = jnp.broadcast_arrays(lane_seed, round_idx)
    fold = lambda s, r: jax.random.fold_in(  # noqa: E731
        jax.random.fold_in(base, s), r)
    for _ in range(seeds.ndim):
        fold = jax.vmap(fold)
    return fold(seeds, rounds)


def split(key, n: int):
    """``jax.random.split(key, n)``; None stays None (identity codec)."""
    return None if key is None else jax.random.split(key, n)


def fold_in(key, data):
    """``jax.random.fold_in(key, data)``; None stays None."""
    return None if key is None else jax.random.fold_in(key, data)


def key_at(keys, i):
    """``keys[i]`` of a :func:`split`; None stays None."""
    return None if keys is None else keys[i]


class Panel(NamedTuple):
    """One hop's weight panel (eq. (2) per edge or eq. (3)), built once
    by the engine: ``dense(rows)`` weighs (R, p) f32 message rows;
    ``decoded(scale, q)`` weighs the rows ``decode_rows(q, scale)``
    without forming them where the panel can (the ``hier_agg`` kernel)."""
    dense: Callable
    decoded: Callable


def uplink(cfg: CompressionConfig, key, ref, new, resid):
    """Each sender's message for its trained model ``new`` against the
    reference ``ref`` it pulled, and its updated residual rows.

    ``new``/``resid``: pytrees whose leaves carry a leading sender axis
    (R, ...); ``ref`` the same per sender or, broadcast, one model.
    Identity codec: ``(new, resid)``, no operation. Lossy: each leaf's
    wire form ``(q (R, p), scale (R,))`` of ``new - ref + resid``, one
    key of ``split(key, n_leaves)`` per leaf.
    """
    if not cfg.active:
        return new, resid
    leaves, treedef = jax.tree.flatten(new)
    keys = jax.random.split(key, len(leaves))
    msgs, resids = [], []
    for n, r, e, k in zip(leaves, jax.tree.leaves(ref),
                          jax.tree.leaves(resid), keys):
        R = n.shape[0]
        q, scale, e_new = encode_leaf(
            cfg, k, (n - r).reshape(R, -1).astype(jnp.float32),
            e.reshape(R, -1))
        msgs.append((q, scale))
        resids.append(e_new.reshape(e.shape))
    return treedef.unflatten(msgs), treedef.unflatten(resids)


def aggregate(cfg: CompressionConfig, panel: Panel, msg, base):
    """Eqs. (2)/(3) over one hop's messages: f32 leaves shaped like
    ``base``, the receivers' current models. Identity codec: the panel
    weighs the senders' models. Lossy: ``base + Σ w·decode(msg)`` —
    exactly the identity's average when the codec is lossless, as every
    receiver's weights sum to 1 (an empty edge's to 0: it keeps
    ``base``)."""
    if not cfg.active:
        return jax.tree.map(
            lambda m, b: panel.dense(m.reshape(m.shape[0], -1))
            .reshape(b.shape), msg, base)

    def add(b, m):
        d = panel.decoded(m[1], m[0])       # (R', p) or (p,) weighed rows
        return (b.reshape(d.shape) + d).reshape(b.shape)
    return jax.tree.map(add, base, msg)


def receive(cfg: CompressionConfig, msg, ref):
    """The model a receiver reconstructs from ``msg`` sent against
    ``ref``: the message itself for the identity codec, ``ref +
    decode(msg)`` in ``ref``'s dtype otherwise."""
    if not cfg.active:
        return msg
    return jax.tree.map(
        lambda r, m: (r + decode_rows(cfg, m[0], m[1]).reshape(r.shape))
        .astype(r.dtype), ref, msg)
