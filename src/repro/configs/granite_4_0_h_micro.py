"""granite-4.0-h-micro — hybrid Mamba-2 + NoPE GQA decoder, 3B.

[https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json]
IBM Granite 4.0-H Micro (Oct 2025): 40 layers, 36 Mamba-2 and 4
attention at ``layer_types`` positions 5, 15, 25, 35 (a period of 10,
attention at offset 5); hidden 2048; attention 32 query / 8 KV heads of
64, no position embedding, softmax scale ``attention_multiplier``
0.015625; Mamba-2 64 heads x 64 (d_inner 4096, expand 2), d_state 128,
one group, conv width 4 with bias, chunk 256; a SwiGLU MLP of 8192 after
every layer; muP ``embedding_multiplier`` 12 and ``residual_multiplier``
0.22; RMSNorm eps 1e-5; tied embeddings, vocabulary 100,352.

As an HFL payload it is federated LoRA fine-tuning (``LORA``: rank 16,
alpha 32 on every dense projection) of the frozen base as a sequence
classifier. The classifier head replaces the tied LM head, so
``logits_scaling`` (8) has nothing to divide and does not apply.
"""
import dataclasses

from repro.configs.base import LoRAConfig, ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  chunk=256, n_groups=1, conv_bias=True),
    hybrid_period=10,
    hybrid_attn_pos=5,
    use_rope=False,
    attn_scale=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    norm_eps=1e-5,
    tie_embeddings=True,
    citation="https://huggingface.co/ibm-granite/granite-4.0-h-micro/"
             "blob/main/config.json",
)

LORA = LoRAConfig(rank=16, alpha=32.0)

# the stage payload trains at most this many lanes at once, and
# evaluates this many test sequences per chunk (activation memory)
STAGE_LANE_CHUNK = 6
STAGE_EVAL_BATCH = 16


def stage_config() -> ModelConfig:
    """Published widths, one whole period of 10 layers (9 Mamba-2, one
    attention at offset 5): the share of a 4-stage pipeline that one
    chip holds."""
    return dataclasses.replace(CONFIG, n_layers=10, remat=False)


def smoke_config() -> ModelConfig:
    """The layer pattern at CPU size: one period of 10 layers."""
    return dataclasses.replace(
        CONFIG, name="granite-smoke", n_layers=10, d_model=128, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=503,
        ssm=SSMConfig(d_state=16, head_dim=32, expand=2, conv_width=4,
                      chunk=16, n_groups=1, conv_bias=True),
        attn_scale=1.0 / 32, dtype="float32")
