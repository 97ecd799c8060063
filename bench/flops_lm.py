"""Useful training operations of the LoRA payload, counted from shapes.

Per token of one local step, in multiply-adds (MACs), with the model's
keys as the configuration file gives them (``reference_lm.Dims``):

- dense: every base projection, d_in * d_out (Mamba-2 z, x, B, C, dt,
  out; attention q, k, v, o; MLP gate, up, down);
- LoRA: r * (d_in + d_out) per adapted projection (x A, then (x A) B);
- attention: q k^T and p v over the causal triangle, 2 * heads *
  head_dim * (S + 1) / 2;
- SSD: the state recurrence, 3 * P * N per head (decay and update of
  the (P, N) state, then its product with C).

A training step counts the forward pass, the input gradients back
through the frozen base (one more dense pass), and the adapters' own
gradients: 2 x dense + 3 x (LoRA + attention + SSD); no weight gradient
of the base, which is never computed. Two operations per MAC. The head
adds 3 * d * n_classes MACs per sequence. Norms, activations, the
embedding lookup and recomputation are not counted. Only valid
sequences count, not the padding rows.
"""
from __future__ import annotations

from bench.reference_lm import shapes


def _dense_and_lora(m):
    """(dense MACs, LoRA MACs) per token of the whole model."""
    dense = lora = 0
    for kind in m.layer_types:
        for d_in, d_out in shapes(m)[kind].values():
            dense += d_in * d_out
            lora += m.rank * (d_in + d_out)
    return dense, lora


def mixer_macs(m, seq_len):
    """Attention and SSD MACs per token of the whole model."""
    n_attn = sum(k == "attention" for k in m.layer_types)
    n_ssm = len(m.layer_types) - n_attn
    attn = 2 * m.heads * m.head_dim * (seq_len + 1) / 2
    ssd = 3 * m.ssm_heads * m.ssm_head_dim * m.d_state
    return n_attn * attn + n_ssm * ssd


def forward_macs(m, seq_len):
    """MACs of one token's forward pass (the head left out)."""
    dense, lora = _dense_and_lora(m)
    return dense + lora + mixer_macs(m, seq_len)


def train_flops_per_token(m, seq_len):
    """Operations of one token's local step: forward, input gradients
    through the frozen base, and the adapters' gradients."""
    dense, lora = _dense_and_lora(m)
    rest = lora + mixer_macs(m, seq_len) + m.d * m.n_classes / seq_len
    return 2 * (2 * dense + 3 * rest)


def train_flops(m, seq_len, tokens):
    """Operations of ``tokens`` valid token-steps of local training."""
    return train_flops_per_token(m, seq_len) * tokens
