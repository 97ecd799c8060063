"""HFL training orchestration — Algorithm 1 (one global iteration) and the
hierarchical aggregation equations (2)-(3), plus test evaluation.

Faithful semantics: at global iteration i the scheduled cohort H_i is
partitioned over M edge servers (assignment Ψ_i). Each of Q edge
iterations runs L local full-batch GD steps per device from that device's
*edge* model, then data-size-weighted edge aggregation (2). After Q edge
iterations the cloud aggregates the edge models weighted by their cohort
data sizes (3).

Implementation: devices are vmapped. Edge/cloud aggregation has two
backends selected by ``agg_kernel``: the default masked XLA einsum
against the assignment one-hot, or (``agg_kernel=True``) the fused
masked-weight ``kernels/hier_agg`` Pallas kernel, which streams the
(H, P) delta matrix through VMEM once and builds the normalised (M, H)
weight panel in-kernel from the one-hot + device sizes (interpret mode
off-TPU). Both backends share the empty-edge fixup (edges with no
devices keep their model) and the eq.-(3) weights; the einsum path is
the parity oracle (``tests/test_kernels.py`` / ``test_round_engine.py``).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.local_train import apply_model, cohort_local_sgd
from repro.data.partition import FederatedData

def agg_matmul(a, b):
    """``a @ b`` at full f32 precision on every backend.

    Aggregation contracts parameters and integer data sizes (D_n up to
    700), neither of which survives a bf16 pass, and one bf16 pass is
    what an f32 matmul gets on TPU by default (the CPU computes f32
    either way)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def pad_device_data(fed: FederatedData, Dmax: Optional[int] = None):
    """-> X (N, Dmax, ...), y (N, Dmax), mask (N, Dmax).

    X keeps the source dtype: images stay float32, token sequences stay
    integer (the model-zoo payloads index embeddings with them).
    """
    N = fed.n_devices
    Dmax = Dmax or int(max(len(y) for y in fed.y))
    sample_shape = fed.X[0].shape[1:]
    X = np.zeros((N, Dmax, *sample_shape), fed.X[0].dtype)
    y = np.zeros((N, Dmax), np.int32)
    mask = np.zeros((N, Dmax), np.float32)
    for n in range(N):
        d = min(len(fed.y[n]), Dmax)
        X[n, :d] = fed.X[n][:d]
        y[n, :d] = fed.y[n][:d]
        mask[n, :d] = 1.0
    return jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)


def hfl_global_iteration_core(apply_fn: Callable, global_params, X, y, mask,
                              sizes, assign, *, M: int, L: int, Q: int,
                              lr: float, agg_kernel: bool = False,
                              codec=None, dev_resid=None, edge_resid=None,
                              codec_key=None, frozen=None,
                              lane_chunk: int = 0):
    """Algorithm 1, traceable core (no jit) — inlined by the fused round
    engine (``framework.round_step``) and vmapped by ``core.sweep``.

    ``global_params`` is the trainable tree; ``frozen`` is the payload's
    base (``ModelSpec.frozen_init_fn``), passed once to local training
    and never broadcast to edges or devices, aggregated or encoded.
    ``lane_chunk`` is local training's lanes per chunk
    (``cohort_local_sgd``; 0: the whole cohort at once).

    X/y/mask: (H, Dmax, ...) for the scheduled cohort; sizes: (H,) D_n;
    assign: (H,) edge ids. ``agg_kernel=True`` routes eqs. (2)-(3)
    through the fused masked-weight Pallas kernel (the one-hot + sizes go
    in raw; the normalised weight panel is built in-kernel, and vmapped
    callers hit the lane-batched grid). Returns new global params.

    With an active ``codec`` (:class:`repro.core.compression.
    CompressionConfig`, a static arg), both uplinks are compressed:
    devices encode their post-SGD delta vs the edge model they pulled
    and edges aggregate the decoded deltas in delta space
    (``edge' = edge + Σ w·decode(encode(delta))``, exactly eq. (2) when
    the codec is lossless); after Q edge iterations each edge encodes
    its delta vs the global model for the cloud hop. ``dev_resid``
    ((H, ...) cohort-gathered) and ``edge_resid`` ((M, ...)) are the
    error-feedback accumulators, updated every message; ``codec_key``
    seeds stochastic rounding. Returns
    ``(new_params, new_dev_resid, new_edge_resid)`` in this mode —
    ``codec=None`` / ``codec="none"`` keeps the uncompressed trace (and
    the single-value return) bit-for-bit.
    """
    compress = codec is not None and codec.active
    if compress:
        from repro.core import compression as comp
    H = sizes.shape[0]
    # named scopes: local_train (cohort_local_sgd and the pull of edge
    # models) and aggregate (eqs. (2)-(3), their weights, the codec)
    with jax.named_scope("aggregate"):
        onehot = jax.nn.one_hot(assign, M, dtype=jnp.float32)  # (H, M)
        w_dev = sizes.astype(jnp.float32)                      # D_n
        edge_tot = agg_matmul(onehot.T, w_dev)                 # (M,) D_{N_m}
        has_dev = edge_tot > 0

        if agg_kernel:
            from repro.kernels.hier_agg.ops import (masked_aggregate,
                                                    masked_decode_aggregate)
            # eq. (2): panel built in-kernel from membership rows + sizes
            edge_aggregate = functools.partial(masked_aggregate, onehot.T,
                                               w_dev)
            # eq. (3) = the same kernel with an all-ones (1, M) mask over
            # the per-edge cohort sizes D_{N_m} (empty edges weigh 0)
            cloud_aggregate = lambda flat: masked_aggregate(  # noqa: E731
                jnp.ones((1, M), jnp.float32), edge_tot, flat)[0]
            # compression path: scales fold into the in-kernel panel, the
            # wire-format q streams into the MXU undecoded
            edge_dec_aggregate = functools.partial(
                masked_decode_aggregate, onehot.T, w_dev)
            cloud_dec_aggregate = lambda sc, q: masked_decode_aggregate(  # noqa: E731
                jnp.ones((1, M), jnp.float32), edge_tot, sc, q)[0]
        else:
            # per-edge normalised device weights: (M, H)
            w_edge = (onehot.T * w_dev[None, :]) \
                / jnp.maximum(edge_tot, 1.0)[:, None]
            w_cloud = jnp.where(has_dev, edge_tot, 0.0)
            w_cloud = w_cloud / jnp.maximum(jnp.sum(w_cloud), 1.0)
            edge_aggregate = functools.partial(agg_matmul, w_edge)
            cloud_aggregate = functools.partial(agg_matmul, w_cloud)
            if compress:
                # einsum decode-aggregate oracle: dense decode, then matmul
                edge_dec_aggregate = lambda sc, q: agg_matmul(  # noqa: E731
                    w_edge, comp.decode_rows(codec, q, sc))
                cloud_dec_aggregate = lambda sc, q: agg_matmul(  # noqa: E731
                    w_cloud, comp.decode_rows(codec, q, sc))

    # edge models start from the global model
    edge_params = jax.tree.map(
        lambda g: jnp.broadcast_to(g[None], (M,) + g.shape), global_params)

    if not compress:
        def edge_iter(edge_params, _):
            # each device pulls its edge's model
            with jax.named_scope("local_train"):
                dev_params = jax.tree.map(
                    lambda e: jnp.take(e, assign, axis=0), edge_params)
            dev_params = cohort_local_sgd(apply_fn, dev_params, X, y, mask,
                                          L, lr, frozen, lane_chunk)
            # (2): weighted average per edge; empty edges keep their model
            # (aggregate in f32, carry the model dtype through the scan)
            def agg(delta, old):
                flat = delta.reshape(H, -1)
                new = edge_aggregate(flat).reshape((M,) + delta.shape[1:])
                keep = has_dev.reshape((M,) + (1,) * (delta.ndim - 1))
                return jnp.where(keep, new, old).astype(old.dtype)
            with jax.named_scope("aggregate"):
                new_edge = jax.tree.map(agg, dev_params, edge_params)
            return new_edge, None

        edge_params, _ = jax.lax.scan(edge_iter, edge_params, None, length=Q)

        # (3): cloud aggregation, weights D_{N_m} (empty edges weight 0)
        def cloud_agg(e):
            flat = e.reshape(M, -1)
            return cloud_aggregate(flat).reshape(e.shape[1:]).astype(e.dtype)

        with jax.named_scope("aggregate"):
            return jax.tree.map(cloud_agg, edge_params)

    # ---- compressed path: both uplinks ship encoded deltas; aggregation
    #      runs in delta space (edge' = edge + Σ w·decoded_delta, exactly
    #      eq. (2) for a lossless codec since the weights sum to 1 per
    #      non-empty edge — empty edges get zero weight mass and keep
    #      their model automatically).
    with jax.named_scope("aggregate"):     # stochastic-rounding keys
        keys = jax.random.split(codec_key, Q + 1)

    def edge_iter_c(carry, k_round):
        edge_params, resid = carry
        with jax.named_scope("local_train"):
            pulled = jax.tree.map(lambda e: jnp.take(e, assign, axis=0),
                                  edge_params)
        trained = cohort_local_sgd(apply_fn, pulled, X, y, mask, L, lr,
                                   frozen, lane_chunk)
        t_leaves, treedef = jax.tree.flatten(trained)
        p_leaves = jax.tree.leaves(pulled)
        r_leaves = jax.tree.leaves(resid)
        e_leaves = jax.tree.leaves(edge_params)
        new_e, new_r = [], []
        with jax.named_scope("aggregate"):
            ks = jax.random.split(k_round, len(t_leaves))
            for t, p_, r, e, k in zip(t_leaves, p_leaves, r_leaves,
                                      e_leaves, ks):
                d = (t - p_).reshape(H, -1).astype(jnp.float32)
                q, sc, nr = comp.encode_leaf(codec, k, d, r.reshape(H, -1))
                dm = edge_dec_aggregate(sc, q)                # (M, p)
                ef = e.reshape(M, -1) + dm
                new_e.append(ef.reshape(e.shape).astype(e.dtype))
                new_r.append(nr.reshape(r.shape))
        return (treedef.unflatten(new_e), treedef.unflatten(new_r)), None

    (edge_params, dev_resid), _ = jax.lax.scan(
        edge_iter_c, (edge_params, dev_resid), keys[:Q])

    # cloud hop: each edge encodes its delta vs the global model (3)
    e_leaves, treedef = jax.tree.flatten(edge_params)
    g_leaves = jax.tree.leaves(global_params)
    r_leaves = jax.tree.leaves(edge_resid)
    new_g, new_r = [], []
    with jax.named_scope("aggregate"):
        ks = jax.random.split(keys[Q], len(e_leaves))
        for e, g, r, k in zip(e_leaves, g_leaves, r_leaves, ks):
            d = (e.reshape(M, -1) - g.reshape(1, -1)).astype(jnp.float32)
            q, sc, nr = comp.encode_leaf(codec, k, d, r.reshape(M, -1))
            gf = g.reshape(-1) + cloud_dec_aggregate(sc, q)
            new_g.append(gf.reshape(g.shape).astype(g.dtype))
            new_r.append(nr.reshape(r.shape))
    return (treedef.unflatten(new_g), dev_resid, treedef.unflatten(new_r))


@functools.partial(jax.jit, static_argnames=("apply_fn", "M", "L", "Q",
                                             "agg_kernel", "codec",
                                             "lane_chunk"))
def hfl_global_iteration(apply_fn: Callable, global_params, X, y, mask,
                         sizes, assign, *, M: int, L: int, Q: int,
                         lr: float, agg_kernel: bool = False,
                         codec=None, dev_resid=None, edge_resid=None,
                         codec_key=None, frozen=None, lane_chunk: int = 0):
    """Jitted Algorithm 1 — see ``hfl_global_iteration_core``."""
    return hfl_global_iteration_core(apply_fn, global_params, X, y, mask,
                                     sizes, assign, M=M, L=L, Q=Q, lr=lr,
                                     agg_kernel=agg_kernel, codec=codec,
                                     dev_resid=dev_resid,
                                     edge_resid=edge_resid,
                                     codec_key=codec_key, frozen=frozen,
                                     lane_chunk=lane_chunk)


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def evaluate_accuracy(apply_fn: Callable, params, X_test, y_test):
    logits = apply_fn(params, X_test)
    return jnp.mean((jnp.argmax(logits, axis=-1) == y_test).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def _count_correct(apply_fn: Callable, params, X, y, valid, frozen=None):
    """Correct predictions among rows where ``valid > 0`` (exact int)."""
    logits = apply_model(apply_fn, params, X, frozen)
    hit = (jnp.argmax(logits, axis=-1) == y) & (valid > 0)
    return jnp.sum(hit.astype(jnp.int32))


def evaluate_in_batches(apply_fn, params, X_test, y_test, batch: int = 512,
                        frozen=None):
    """Test accuracy in device-sized batches, host-accumulated.

    Chunks the test set so evaluation never materialises one
    (n_test, ...) activation tensor. The final ragged chunk is padded up
    to the chunk shape with a validity mask instead of compiling a
    second XLA program per (arch, test-set-size) pair; correct counts
    are integers, so the result is the exact sample-weighted accuracy.
    Each chunk's host work and its wait are the profiler spans
    ``eval.upload`` and ``eval.wait``; they carry no round of their own
    and nest in the engine's ``hfl.eval`` / ``async.eval`` span.
    """
    X_test = np.asarray(X_test)
    y_test = np.asarray(y_test)
    n = len(y_test)
    if n == 0:
        return 0.0
    batch = min(batch, n)
    correct = 0
    for i in range(0, n, batch):
        # evaluation: host padding and upload of one test chunk
        with TraceAnnotation("eval.upload"):
            Xc, yc = X_test[i:i + batch], y_test[i:i + batch]
            k = len(yc)
            valid = np.zeros(batch, np.float32)
            valid[:k] = 1.0
            if k < batch:   # pad the ragged tail to the chunk shape
                Xc = np.concatenate(
                    [Xc, np.zeros((batch - k, *Xc.shape[1:]), Xc.dtype)])
                yc = np.concatenate([yc, np.zeros(batch - k, yc.dtype)])
            chunk = jnp.asarray(Xc), jnp.asarray(yc), jnp.asarray(valid)
        # evaluation: the chunk's forward pass and the wait for its count
        with TraceAnnotation("eval.wait"):
            correct += int(_count_correct(apply_fn, params, *chunk, frozen))
    return correct / n
