"""The plain reference of the LoRA payload: federated LoRA fine-tuning of
one period of granite-4.0-h-micro as a sequence classifier.

Imports nothing of the system under test. Written from the published
model (config.json of ibm-granite/granite-4.0-h-micro; Mamba-2,
arXiv:2405.21060; LoRA, arXiv:2106.09685) and the paper's Algorithm 1
(arXiv:2402.02506): token embedding times ``embedding_multiplier``; per
layer RMSNorm, then the token mixer, added times
``residual_multiplier``; RMSNorm, SwiGLU MLP, added times
``residual_multiplier``; final RMSNorm. The mixer is Mamba-2 (in
projections z, x, B, C, dt; a depthwise causal convolution of [x|B|C]
with its bias, then SiLU; dt = softplus(dt + dt_bias), A = -exp(A_log);
the state recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
y_t = h_t C_t + D x_t, run one token after another; RMSNorm of
y * SiLU(z); out projection) or grouped-query attention without a
position embedding, softmax(q k^T * attention_multiplier) v by an
explicit causal mask. Every dense projection W of a layer carries an
adapter: x W + (alpha / rank) (x A) B.

Departures from the published model, each the configuration's: one
period of 10 layers (the configuration's cut); a sequence classifier,
mean pool over the sequence's tokens and a linear head, in place of the
tied LM head (so ``logits_scaling`` does not apply); Mamba-2's fused
in_proj held as its five segments, each with its own adapter (the same
linear map). The recurrence and each layer are recomputed in the
backward pass, so that a block of lanes fits the chip; that changes no
number.

Runs at ``jax.default_matmul_precision("highest")`` in float32
(``reference.precision``), in the adapters' dtype: a base held in
bfloat16 is read as its float32 values. Given bfloat16 weights and
adapters, with every intermediate in bfloat16, it is the control.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import reference

SEGMENT = 32          # tokens per recomputed piece of the recurrence


@dataclasses.dataclass(frozen=True)
class Dims:
    """The model's shape, from the configuration file's published keys."""
    layer_types: tuple
    d: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    conv: int
    attn_mult: float
    emb_mult: float
    res_mult: float
    eps: float
    rank: int
    alpha: float
    n_classes: int

    @property
    def d_inner(self):
        return self.ssm_heads * self.ssm_head_dim


def dims(config):
    """``Dims`` of a configuration file's dict."""
    c, lo = config, config["lora"]
    return Dims(
        layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
        d=c["hidden_size"], vocab=c["vocab_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        ff=c["shared_intermediate_size"], ssm_heads=c["mamba_n_heads"],
        ssm_head_dim=c["mamba_d_head"], d_state=c["mamba_d_state"],
        groups=c["mamba_n_groups"], conv=c["mamba_d_conv"],
        attn_mult=c["attention_multiplier"],
        emb_mult=c["embedding_multiplier"],
        res_mult=c["residual_multiplier"], eps=c["rms_norm_eps"],
        rank=lo["rank"], alpha=lo["alpha"],
        n_classes=config["data"]["n_classes"])


def shapes(m: Dims):
    """{name: (d_in, d_out)} of each adapted matrix, per layer kind."""
    gn = m.groups * m.d_state
    mlp = {"gate": (m.d, m.ff), "up": (m.d, m.ff), "down": (m.ff, m.d)}
    return {
        "mamba": {"in_z": (m.d, m.d_inner), "in_x": (m.d, m.d_inner),
                  "in_B": (m.d, gn), "in_C": (m.d, gn),
                  "in_dt": (m.d, m.ssm_heads), "out": (m.d_inner, m.d),
                  **mlp},
        "attention": {"q": (m.d, m.heads * m.head_dim),
                      "k": (m.d, m.kv_heads * m.head_dim),
                      "v": (m.d, m.kv_heads * m.head_dim),
                      "o": (m.heads * m.head_dim, m.d), **mlp}}


# ----------------------------------------------------------------- weights

def init(key, m: Dims):
    """Base weights in float32 from ``key``: N(0, 1/d_in) matrices, unit
    norm scales, embeddings N(0, 0.02^2), conv N(0, 0.1^2) with bias
    N(0, 0.05^2), A_log U(-1, 1), D 1, dt_bias U(-2, 0)."""
    keys = iter(jax.random.split(key, 16 * len(m.layer_types) + 2))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    layers = []
    for kind in m.layer_types:
        w = {name: normal(s, 1.0 / np.sqrt(s[0]))
             for name, s in shapes(m)[kind].items()}
        w["norm1"] = jnp.ones((m.d,), jnp.float32)
        w["norm2"] = jnp.ones((m.d,), jnp.float32)
        if kind == "mamba":
            c = m.d_inner + 2 * m.groups * m.d_state
            w["conv_w"] = normal((c, m.conv), 0.1)
            w["conv_b"] = normal((c,), 0.05)
            w["A_log"] = jax.random.uniform(next(keys), (m.ssm_heads,),
                                            jnp.float32, -1.0, 1.0)
            w["D"] = jnp.ones((m.ssm_heads,), jnp.float32)
            w["dt_bias"] = jax.random.uniform(next(keys), (m.ssm_heads,),
                                              jnp.float32, -2.0, 0.0)
            w["gate_norm"] = jnp.ones((m.d_inner,), jnp.float32)
        layers.append(w)
    return {"embed": normal((m.vocab, m.d), 0.02),
            "final_norm": jnp.ones((m.d,), jnp.float32), "layers": layers}


def adapters_init(key, m: Dims, b_std):
    """Adapters: A N(0, 1/d_in), B N(0, b_std^2) (nonzero, so that both
    get gradients from the first step), head N(0, 2/d)."""
    keys = iter(jax.random.split(key, 64 * len(m.layer_types) + 1))
    layers = []
    for kind in m.layer_types:
        layers.append({
            name: {"a": jax.random.normal(next(keys), (d_in, m.rank))
                   / np.sqrt(d_in),
                   "b": jax.random.normal(next(keys), (m.rank, d_out))
                   * b_std}
            for name, (d_in, d_out) in shapes(m)[kind].items()})
    head = jax.random.normal(next(keys), (m.d, m.n_classes)) \
        * np.sqrt(2.0 / m.d)
    return {"layers": layers, "head": head}


# ------------------------------------------------------------------- model

def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def _proj(w, ad, name, x, m: Dims):
    """x W + (alpha / rank) (x A) B."""
    a = ad[name]
    return x @ w[name] + (m.alpha / m.rank) * ((x @ a["a"]) @ a["b"])


def _recurrence(xh, dt, A, B, C):
    """Mamba-2's state recurrence, one token after another.
    xh (b, S, H, P), dt (b, S, H), B/C (b, S, H, N) -> y (b, S, H, P)."""
    b, S, H, P = xh.shape
    N = B.shape[-1]

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, C_t)

    @jax.checkpoint
    def piece(h, inp):
        return lax.scan(step, h, inp)

    seg = SEGMENT if S % SEGMENT == 0 else S
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((S // seg, seg) + a.shape[:1]
                                             + a.shape[2:])
               for a in (xh, dt, B, C))
    h0 = jnp.zeros((b, H, P, N), xh.dtype)
    _, ys = lax.scan(piece, h0, xs)
    return jnp.moveaxis(ys.reshape((S, b, H, P)), 0, 1)


def _mamba(w, ad, x, m: Dims):
    b, S, _ = x.shape
    gn = m.groups * m.d_state
    z = _proj(w, ad, "in_z", x, m)
    xbc = jnp.concatenate([_proj(w, ad, n, x, m)
                           for n in ("in_x", "in_B", "in_C")], axis=-1)
    c = xbc.shape[-1]
    xbc = lax.conv_general_dilated(
        xbc, w["conv_w"].T[:, None, :].astype(x.dtype), (1,),
        [(m.conv - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c) + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    xi, Bm, Cm = jnp.split(xbc, [m.d_inner, m.d_inner + gn], axis=-1)
    dt = jax.nn.softplus(_proj(w, ad, "in_dt", x, m) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    rep = m.ssm_heads // m.groups
    Bh = jnp.repeat(Bm.reshape(b, S, m.groups, m.d_state), rep, axis=2)
    Ch = jnp.repeat(Cm.reshape(b, S, m.groups, m.d_state), rep, axis=2)
    xh = xi.reshape(b, S, m.ssm_heads, m.ssm_head_dim)
    y = _recurrence(xh, dt, A, Bh, Ch) + w["D"][:, None] * xh
    y = _rms(y.reshape(b, S, m.d_inner) * jax.nn.silu(z), w["gate_norm"],
             m.eps)
    return _proj(w, ad, "out", y, m)


def _attention(w, ad, x, m: Dims):
    b, S, _ = x.shape
    q = _proj(w, ad, "q", x, m).reshape(b, S, m.heads, m.head_dim)
    k = _proj(w, ad, "k", x, m).reshape(b, S, m.kv_heads, m.head_dim)
    v = _proj(w, ad, "v", x, m).reshape(b, S, m.kv_heads, m.head_dim)
    rep = m.heads // m.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * m.attn_mult
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, S, -1)
    return _proj(w, ad, "o", o, m)


def _layer(kind, w, ad, x, m: Dims):
    h = _rms(x, w["norm1"], m.eps)
    h = _mamba(w, ad, h, m) if kind == "mamba" else _attention(w, ad, h, m)
    x = x + m.res_mult * h
    h = _rms(x, w["norm2"], m.eps)
    h = _proj(w, ad, "down", jax.nn.silu(_proj(w, ad, "gate", h, m))
              * _proj(w, ad, "up", h, m), m)
    return x + m.res_mult * h


def logits(weights, ad, tokens, m: Dims):
    """(b, S) int tokens -> (b, n_classes) float32 logits, computed in
    the adapters' dtype (the base may be held in a lower one)."""
    x = weights["embed"][tokens].astype(ad["head"].dtype) * m.emb_mult
    for kind, w, a in zip(m.layer_types, weights["layers"], ad["layers"]):
        x = jax.checkpoint(functools.partial(_layer, kind, m=m))(w, a, x)
    x = _rms(x, weights["final_norm"], m.eps)
    return x.astype(jnp.float32).mean(axis=1) @ ad["head"].astype(jnp.float32)


def loss(ad, weights, tokens, y, mask, m: Dims):
    """Mean cross-entropy over the valid sequences."""
    logp = jax.nn.log_softmax(logits(weights, ad, tokens, m))
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def local_gd(ad, weights, tokens, y, mask, L, lr, m: Dims):
    """Eq. (1) on the adapters and head alone: L full-batch steps."""
    def step(_, ad):
        g = jax.grad(loss)(ad, weights, tokens, y, mask, m)
        return jax.tree.map(lambda p, d: (p - lr * d).astype(p.dtype), ad, g)
    return lax.fori_loop(0, L, step, ad)


@functools.partial(jax.jit, static_argnames=("M", "L", "Q", "lr", "block",
                                             "m"))
def hfl_round(weights, ad, X, y, mask, sizes, assign, *, M, L, Q, lr, block,
              m: Dims):
    """One global iteration of Algorithm 1 over the adapters and head:
    the base ``weights`` are read, never moved. X (H, Dmax, S) tokens;
    sizes (H,) the D_n that weigh aggregation; assign (H,) edge ids.
    Devices train ``block`` at a time."""
    onehot = (assign[:, None] == jnp.arange(M)[None, :]).astype(jnp.float32)
    w = sizes.astype(jnp.float32)
    edge_tot = jnp.sum(onehot * w[:, None], axis=0)
    w_edge = (onehot * w[:, None]).T / jnp.maximum(edge_tot, 1.0)[:, None]
    has = edge_tot > 0
    edge = jax.tree.map(lambda g: jnp.broadcast_to(g, (M,) + g.shape), ad)

    def edge_iteration(edge, _):
        dev = jax.tree.map(lambda e: e[assign], edge)
        dev = lax.map(lambda a: local_gd(a[0], weights, *a[1:], L, lr, m),
                      (dev, X, y, mask), batch_size=block)
        new = reference._weighted(w_edge, dev)
        return jax.tree.map(
            lambda n, o: jnp.where(has.reshape((M,) + (1,) * (o.ndim - 1)),
                                   n, o), new, edge), None

    edge, _ = lax.scan(edge_iteration, edge, None, length=Q)
    w_cloud = jnp.where(has, edge_tot, 0.0)
    w_cloud = w_cloud / jnp.maximum(jnp.sum(w_cloud), 1.0)
    return reference._weighted(w_cloud, edge)


@functools.partial(jax.jit, static_argnames=("m",))
def _batch_nll(weights, ad, tokens, y, m: Dims):
    logp = jax.nn.log_softmax(logits(weights, ad, tokens, m))
    return -jnp.sum(jnp.take_along_axis(logp, y[:, None], axis=1))


def test_loss(weights, ad, X, y, m: Dims, batch=16):
    """Mean cross-entropy on the test set, ``batch`` sequences a call."""
    total = 0.0
    for i in range(0, len(y), batch):
        total += float(_batch_nll(weights, ad, jnp.asarray(X[i:i + batch]),
                                  jnp.asarray(y[i:i + batch]), m))
    return total / len(y)


def flat(ad):
    """{path: array} of an adapter tree, for ``reference.norm_gap``."""
    leaves = jax.tree_util.tree_leaves_with_path(ad)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in leaves}
