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
Both uplinks go through a ``core.compression`` codec in one scan body;
the identity codec (the default) sends the models themselves.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import compression as comp
from repro.core.local_train import apply_model, cohort_local_sgd
from repro.data.partition import FederatedData


def agg_matmul(a, b):
    """``a @ b`` at full f32 precision on every backend.

    Aggregation contracts parameters and integer data sizes (D_n up to
    700), neither of which survives a bf16 pass, and one bf16 pass is
    what an f32 matmul gets on TPU by default (the CPU computes f32
    either way)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def einsum_panel(codec, w) -> comp.Panel:
    """One hop's weights ``w`` ((M, H) per edge, or (M,) for the cloud)
    as a :class:`~repro.core.compression.Panel` on the einsum route: the
    codec's messages are decoded densely, then weighed."""
    return comp.Panel(
        functools.partial(agg_matmul, w),
        lambda scale, q: agg_matmul(w, comp.decode_rows(codec, q, scale)))


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
                              codec: comp.CompressionConfig = comp.IDENTITY,
                              codec_state=(None, None), codec_key=None,
                              frozen=None, lane_chunk: int = 0):
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
    callers hit the lane-batched grid).

    Both uplinks go through ``codec`` (a static
    :class:`repro.core.compression.CompressionConfig`): devices send
    their trained model against the edge model they pulled, edges theirs
    against the global model after Q edge iterations.
    ``codec_state`` is the ``(dev_resid (H, ...), edge_resid (M, ...))``
    error-feedback pair and ``codec_key`` the round's key — ``(None,
    None)`` and None for the identity codec. Returns
    ``(new_params, (dev_resid, edge_resid))``.
    """
    H = sizes.shape[0]
    dev_resid, edge_resid = codec_state
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
            # eq. (2): panel built in-kernel from membership rows + sizes;
            # a lossy codec's scales fold into it, q streams in undecoded
            edge_panel = comp.Panel(
                functools.partial(masked_aggregate, onehot.T, w_dev),
                functools.partial(masked_decode_aggregate, onehot.T, w_dev))
            # eq. (3) = the same kernel with an all-ones (1, M) mask over
            # the per-edge cohort sizes D_{N_m} (empty edges weigh 0)
            ones = jnp.ones((1, M), jnp.float32)
            cloud_panel = comp.Panel(
                lambda rows: masked_aggregate(ones, edge_tot, rows)[0],
                lambda sc, q: masked_decode_aggregate(ones, edge_tot, sc,
                                                      q)[0])
        else:
            # per-edge normalised device weights: (M, H)
            w_edge = (onehot.T * w_dev[None, :]) \
                / jnp.maximum(edge_tot, 1.0)[:, None]
            w_cloud = jnp.where(has_dev, edge_tot, 0.0)
            w_cloud = w_cloud / jnp.maximum(jnp.sum(w_cloud), 1.0)
            edge_panel = einsum_panel(codec, w_edge)
            cloud_panel = einsum_panel(codec, w_cloud)
        keys = comp.split(codec_key, Q + 1)     # stochastic rounding

    # edge models start from the global model
    edge_params = jax.tree.map(
        lambda g: jnp.broadcast_to(g[None], (M,) + g.shape), global_params)

    def edge_iter(carry, key):
        edge_params, dev_resid = carry
        # each device pulls its edge's model
        with jax.named_scope("local_train"):
            pulled = jax.tree.map(lambda e: jnp.take(e, assign, axis=0),
                                  edge_params)
        trained = cohort_local_sgd(apply_fn, pulled, X, y, mask, L, lr,
                                   frozen, lane_chunk)
        # (2): weighted average per edge; empty edges keep their model
        # (aggregate in f32, carry the model dtype through the scan)
        def keep(new, old):
            on = has_dev.reshape((M,) + (1,) * (old.ndim - 1))
            return jnp.where(on, new, old).astype(old.dtype)
        with jax.named_scope("aggregate"):
            msg, dev_resid = comp.uplink(codec, key, pulled, trained,
                                         dev_resid)
            new_edge = jax.tree.map(
                keep, comp.aggregate(codec, edge_panel, msg, edge_params),
                edge_params)
        return (new_edge, dev_resid), None

    (edge_params, dev_resid), _ = jax.lax.scan(
        edge_iter, (edge_params, dev_resid),
        comp.key_at(keys, slice(None, Q)), length=Q)

    # (3): cloud aggregation, weights D_{N_m} (empty edges weight 0)
    with jax.named_scope("aggregate"):
        msg, edge_resid = comp.uplink(codec, comp.key_at(keys, Q),
                                      global_params, edge_params,
                                      edge_resid)
        new_params = jax.tree.map(
            lambda n, g: n.astype(g.dtype),
            comp.aggregate(codec, cloud_panel, msg, global_params),
            global_params)
    return new_params, (dev_resid, edge_resid)


@functools.partial(jax.jit, static_argnames=("apply_fn", "M", "L", "Q",
                                             "agg_kernel", "codec",
                                             "lane_chunk"))
def hfl_global_iteration(apply_fn: Callable, global_params, X, y, mask,
                         sizes, assign, *, M: int, L: int, Q: int,
                         lr: float, agg_kernel: bool = False,
                         codec: comp.CompressionConfig = comp.IDENTITY,
                         codec_state=(None, None), codec_key=None,
                         frozen=None, lane_chunk: int = 0):
    """Jitted Algorithm 1 — see ``hfl_global_iteration_core``."""
    return hfl_global_iteration_core(apply_fn, global_params, X, y, mask,
                                     sizes, assign, M=M, L=L, Q=Q, lr=lr,
                                     agg_kernel=agg_kernel, codec=codec,
                                     codec_state=codec_state,
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
