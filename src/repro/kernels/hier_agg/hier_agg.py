"""Pallas TPU kernels: hierarchical weighted model aggregation (eqs. 2-3).

Edge aggregation is `M edge models = (M x H weight matrix) @ (H devices x
P parameters)`. P is the full flattened model (10^5..10^9), H ≤ a few
hundred — so this is a skinny matmul whose bandwidth cost is streaming the
(H, P) delta matrix through VMEM exactly once. We tile P into 512-lane
blocks, keep the tiny (Mp, Hp) weight panel resident, and emit f32.

Two kernels, each carrying a leading lane axis S with grid (S, P/BP):

* ``weighted_aggregate_batched_pallas`` — caller-supplied (S, M, H)
  weight panels. Per-step VMEM: Hp*BP + Mp*BP + Mp*Hp f32 ≈ 0.3 MiB.
* ``masked_aggregate_batched_pallas`` — the *fused masked-weight*
  variant: takes the raw assignment one-hot / membership mask (S, M, H)
  plus per-device data sizes (S, H) and builds the normalised panel
  ``w = mask·sizes / max(Σ_h mask·sizes, 1)`` INSIDE the kernel, so the
  round engine never materialises ``w_edge`` separately. The panel costs
  Mp·Hp VPU flops per grid step — noise next to the Mp·Hp·BP matmul.
  Cloud aggregation (3) is the same kernel with an all-ones (1, M) mask
  and the per-edge cohort sizes as ``sizes``.

The unbatched entry points (``weighted_aggregate_pallas`` /
``masked_aggregate_pallas``) are the S=1 case of the same kernels — one
kernel body per formula, so tiling/formula changes can't drift between
copies. ``ops.py`` wires the batched kernels up as the
``jax.custom_batching.custom_vmap`` rule of the public ops, so a vmapped
sweep (``core.sweep.SweepRunner``) is ONE kernel launch per round
instead of S per-lane interpret calls.

Empty edges (all-zero mask rows) produce all-zero output rows — callers
keep their ``jnp.where(has_dev, new, old)`` fixup outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BP = 512
SUB = 8      # f32 sublane multiple
# The panel multiplies parameters (and decode scales), which a bf16 pass
# would round to ~3 digits: ask for full f32 rather than rely on the
# backend's default for f32 operands.
HIGHEST = jax.lax.Precision.HIGHEST


def _pad2(a, s0, s1):
    """Pad the trailing two dims up to multiples of (s0, s1)."""
    pads = [(0, 0)] * (a.ndim - 2)
    pads += [(0, (-a.shape[-2]) % s0), (0, (-a.shape[-1]) % s1)]
    return jnp.pad(a, pads)


# ------------------------------------------------------- plain weights

def _kernel_batched(w_ref, d_ref, out_ref):
    w = w_ref[0].astype(jnp.float32)              # (Mp, Hp)
    d = d_ref[0].astype(jnp.float32)              # (Hp, BP)
    out_ref[0] = jax.lax.dot_general(
        w, d, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_aggregate_batched_pallas(weights: jnp.ndarray,
                                      deltas: jnp.ndarray,
                                      interpret: bool = True) -> jnp.ndarray:
    """weights: (S, M, H); deltas: (S, H, P) -> (S, M, P) f32, one
    launch with grid (S, P/BP)."""
    S, M, H = weights.shape
    S2, H2, P = deltas.shape
    assert S == S2 and H == H2
    wp = _pad2(weights, SUB, SUB)
    dp = _pad2(deltas, SUB, BP)
    Mp, Hp = wp.shape[1:]
    Pp = dp.shape[2]
    out = pl.pallas_call(
        _kernel_batched,
        grid=(S, Pp // BP),
        in_specs=[
            pl.BlockSpec((1, Mp, Hp), lambda s, p: (s, 0, 0)),
            pl.BlockSpec((1, Hp, BP), lambda s, p: (s, 0, p)),
        ],
        out_specs=pl.BlockSpec((1, Mp, BP), lambda s, p: (s, 0, p)),
        out_shape=jax.ShapeDtypeStruct((S, Mp, Pp), jnp.float32),
        interpret=interpret,
    )(wp, dp)
    return out[:, :M, :P]


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_aggregate_pallas(weights: jnp.ndarray, deltas: jnp.ndarray,
                              interpret: bool = True) -> jnp.ndarray:
    """weights: (M, H); deltas: (H, P) -> (M, P) f32 — the S=1 lane of
    the batched kernel (one kernel body to maintain)."""
    return weighted_aggregate_batched_pallas(weights[None], deltas[None],
                                             interpret=interpret)[0]


# ---------------------------------------------------- fused masked weights

def _masked_kernel_batched(m_ref, s_ref, d_ref, out_ref):
    m = m_ref[0].astype(jnp.float32)              # (Mp, Hp) membership
    s = s_ref[0].astype(jnp.float32)              # (SUB, Hp) sizes row 0
    w = m * s[0][None, :]                         # (Mp, Hp) mask·D_n
    tot = jnp.sum(w, axis=1, keepdims=True)       # (Mp, 1)  D_{N_m}
    w = w / jnp.maximum(tot, 1.0)
    d = d_ref[0].astype(jnp.float32)              # (Hp, BP)
    out_ref[0] = jax.lax.dot_general(
        w, d, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_aggregate_batched_pallas(mask: jnp.ndarray, sizes: jnp.ndarray,
                                    deltas: jnp.ndarray,
                                    interpret: bool = True) -> jnp.ndarray:
    """Fused masked-weight aggregation over a lane axis.

    mask: (S, M, H) membership rows; sizes: (S, H) per-device data
    sizes; deltas: (S, H, P) -> (S, M, P) f32 in ONE launch with grid
    (S, P/BP) — the ``custom_vmap`` target that keeps vmapped sweeps at
    one kernel call per round. Output row m is
    ``Σ_h mask[m,h]·sizes[h]·deltas[h] / max(Σ_h mask[m,h]·sizes[h], 1)``
    — eq. (2) per edge, and eq. (3) with mask=ones((1, M)), sizes=D_{N_m}.
    """
    S, M, H = mask.shape
    assert sizes.shape == (S, H) and deltas.shape[:2] == (S, H)
    P = deltas.shape[2]
    mp = _pad2(mask, SUB, SUB)
    sp = _pad2(jnp.broadcast_to(sizes[:, None, :], (S, SUB, H)), SUB, SUB)
    dp = _pad2(deltas, SUB, BP)
    Mp, Hp = mp.shape[1:]
    Pp = dp.shape[2]
    out = pl.pallas_call(
        _masked_kernel_batched,
        grid=(S, Pp // BP),
        in_specs=[
            pl.BlockSpec((1, Mp, Hp), lambda s, p: (s, 0, 0)),
            pl.BlockSpec((1, SUB, Hp), lambda s, p: (s, 0, 0)),
            pl.BlockSpec((1, Hp, BP), lambda s, p: (s, 0, p)),
        ],
        out_specs=pl.BlockSpec((1, Mp, BP), lambda s, p: (s, 0, p)),
        out_shape=jax.ShapeDtypeStruct((S, Mp, Pp), jnp.float32),
        interpret=interpret,
    )(mp, sp, dp)
    return out[:, :M, :P]


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_aggregate_pallas(mask: jnp.ndarray, sizes: jnp.ndarray,
                            deltas: jnp.ndarray,
                            interpret: bool = True) -> jnp.ndarray:
    """mask: (M, H); sizes: (H,); deltas: (H, P) -> (M, P) f32 — the S=1
    lane of the batched masked kernel (one kernel body to maintain)."""
    return masked_aggregate_batched_pallas(mask[None], sizes[None],
                                           deltas[None],
                                           interpret=interpret)[0]


# ------------------------------------------- fused masked decode-aggregate

def _masked_dec_kernel_batched(m_ref, s_ref, sc_ref, q_ref, out_ref):
    m = m_ref[0].astype(jnp.float32)              # (Mp, Hp) membership
    s = s_ref[0].astype(jnp.float32)              # (SUB, Hp) sizes row 0
    sc = sc_ref[0].astype(jnp.float32)            # (SUB, Hp) scales row 0
    w = m * s[0][None, :]                         # (Mp, Hp) mask·D_n
    tot = jnp.sum(w, axis=1, keepdims=True)       # (Mp, 1)  D_{N_m}
    # decode scale folded into the weight panel: the quantized update
    # matrix goes into the MXU as-is, no dense decoded (Hp, BP) temp.
    w = (w / jnp.maximum(tot, 1.0)) * sc[0][None, :]
    q = q_ref[0].astype(jnp.float32)              # (Hp, BP) wire dtype
    out_ref[0] = jax.lax.dot_general(
        w, q, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)


def _q_sublane(dtype) -> int:
    """Sublane multiple for the quantized operand's dtype (the int8/bf16
    min-tile constraint is tighter than the f32 SUB)."""
    return {1: 32, 2: 16}.get(jnp.dtype(dtype).itemsize, SUB)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_decode_aggregate_batched_pallas(mask: jnp.ndarray,
                                           sizes: jnp.ndarray,
                                           scales: jnp.ndarray,
                                           q: jnp.ndarray,
                                           interpret: bool = True
                                           ) -> jnp.ndarray:
    """Masked-weight aggregation of *encoded* updates over a lane axis.

    mask: (S, M, H); sizes: (S, H); scales: (S, H) per-message decode
    scales; q: (S, H, P) quantized updates (int8 / bf16 / masked f32)
    -> (S, M, P) f32 rows
    ``Σ_h mask[m,h]·sizes[h]·scales[h]·q[h] / max(Σ_h mask[m,h]·sizes[h], 1)``
    in ONE launch with grid (S, P/BP). This is eq. (2)/(3) applied to
    decoded deltas ``scales[h]·q[h]`` with the decode folded into the
    in-kernel weight panel — the dense decoded update matrix is never
    materialised; the MXU streams the wire-format q directly.
    """
    S, M, H = mask.shape
    assert sizes.shape == (S, H) and scales.shape == (S, H)
    assert q.shape[:2] == (S, H)
    P = q.shape[2]
    hsub = max(SUB, _q_sublane(q.dtype))          # shared H padding
    mp = _pad2(mask, SUB, hsub)
    sp = _pad2(jnp.broadcast_to(sizes[:, None, :], (S, SUB, H)), SUB, hsub)
    scp = _pad2(jnp.broadcast_to(scales[:, None, :], (S, SUB, H)), SUB, hsub)
    qp = _pad2(q, hsub, BP)
    Mp, Hp = mp.shape[1:]
    Pp = qp.shape[2]
    out = pl.pallas_call(
        _masked_dec_kernel_batched,
        grid=(S, Pp // BP),
        in_specs=[
            pl.BlockSpec((1, Mp, Hp), lambda s, p: (s, 0, 0)),
            pl.BlockSpec((1, SUB, Hp), lambda s, p: (s, 0, 0)),
            pl.BlockSpec((1, SUB, Hp), lambda s, p: (s, 0, 0)),
            pl.BlockSpec((1, Hp, BP), lambda s, p: (s, 0, p)),
        ],
        out_specs=pl.BlockSpec((1, Mp, BP), lambda s, p: (s, 0, p)),
        out_shape=jax.ShapeDtypeStruct((S, Mp, Pp), jnp.float32),
        interpret=interpret,
    )(mp, sp, scp, qp)
    return out[:, :M, :P]


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_decode_aggregate_pallas(mask: jnp.ndarray, sizes: jnp.ndarray,
                                   scales: jnp.ndarray, q: jnp.ndarray,
                                   interpret: bool = True) -> jnp.ndarray:
    """mask: (M, H); sizes: (H,); scales: (H,); q: (H, P) -> (M, P) f32
    — the S=1 lane of the batched decode-aggregate kernel."""
    return masked_decode_aggregate_batched_pallas(
        mask[None], sizes[None], scales[None], q[None],
        interpret=interpret)[0]
