"""Federated LoRA fine-tuning payload: a frozen decoder base with
low-rank adapters, as a sequence classifier.

The base (embedding, norms, every dense projection, the Mamba-2 conv,
``A_log``, ``D_skip`` and ``dt_bias``) is the *frozen* tree: it lives on
the device once and is never trained, aggregated or sent. The trainable
tree holds one adapter pair per dense projection of every layer
(Mamba-2 ``wz wx wb wc wdt out_proj``, attention ``wq wk wv wo``, MLP
``w_gate w_up w_down``) and the classification head. Each adapted
projection computes ``x W + (alpha / r) (x A) B`` (Hu et al.,
arXiv:2106.09685); ``A`` starts Gaussian and ``B`` at zero, so the
adapted model starts as the base.

``LoRAClassifierApply`` is ``(params, tokens, frozen) -> logits``:
embed (times the embedding multiplier), the backbone of
``models/transformer.py`` over the base with the adapters merged in as
``layers.Adapted`` matrices, final RMSNorm, mean pool over the sequence,
linear head. The LM head (and with it any logits scaling) is not used.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import LoRAConfig, ModelConfig
from repro.models import transformer as transformer_lib
from repro.models.layers import Adapted, he_normal, rmsnorm

# adapted projections of each sublayer kind
TARGETS = {"attn": ("wq", "wk", "wv", "wo"),
           "ssm": ("wz", "wx", "wb", "wc", "wdt", "out_proj"),
           "mlp": ("w_gate", "w_up", "w_down")}


def frozen_init(key, cfg: ModelConfig) -> Dict:
    """The base in ``cfg.compute_dtype``: the backbone's own
    initialisation without its LM head."""
    params = transformer_lib.init(key, cfg, dtype=cfg.compute_dtype)
    params.pop("lm_head", None)
    return params


def _targets(cfg: ModelConfig, j: int, template: Dict):
    """(group, name, base matrix) of each adapted projection of layer
    template ``j``; matrices are stacked (n_blocks, d_in, d_out)."""
    mix = TARGETS[cfg.layer_kind(j)]
    out = [("mix", n, template["mix"][n]) for n in mix]
    if "mlp" in template:
        out += [("mlp", n, template["mlp"][n]) for n in TARGETS["mlp"]]
    return out


def adapters_init(key, cfg: ModelConfig, lora: LoRAConfig, n_classes: int,
                  frozen: Dict, b_std: float = 0.0) -> Dict:
    """Adapters shaped by the base ``frozen``: ``a`` N(0, 1/d_in), ``b``
    zero (or N(0, b_std^2), so that tests see gradients of both), and
    the head; all float32."""
    k_head, key = jax.random.split(key)
    blocks = []
    for j, template in enumerate(frozen["blocks"]):
        tree: Dict = {}
        for group, name, w in _targets(cfg, j, template):
            nb, d_in, d_out = w.shape
            key, ka, kb = jax.random.split(key, 3)
            a = jax.random.normal(ka, (nb, d_in, lora.rank)) / jnp.sqrt(d_in)
            b = jax.random.normal(kb, (nb, lora.rank, d_out)) * b_std
            tree.setdefault(group, {})[name] = {"a": a, "b": b}
        blocks.append(tree)
    return {"blocks": blocks,
            "cls_head": he_normal(k_head, (cfg.d_model, n_classes),
                                  fan_in=cfg.d_model)}


def merge(cfg: ModelConfig, lora: LoRAConfig, frozen_blocks, adapter_blocks):
    """The base's blocks with each adapted matrix an ``Adapted``."""
    merged = []
    for j, template in enumerate(frozen_blocks):
        t = {k: dict(v) if isinstance(v, dict) else v
             for k, v in template.items()}
        for group, name, w in _targets(cfg, j, template):
            ad = adapter_blocks[j][group][name]
            t[group][name] = Adapted(w, ad["a"], ad["b"], lora.scale)
        merged.append(t)
    return merged


@dataclasses.dataclass(frozen=True)
class LoRAClassifierApply:
    """``(params, tokens (B, S), frozen) -> logits (B, n_classes)``.

    Hashable (a static jit argument). ``remat`` recomputes each layer in
    the backward pass."""
    cfg: ModelConfig
    lora: LoRAConfig
    remat: bool = False

    def __call__(self, params, tokens, frozen) -> jnp.ndarray:
        cfg = self.cfg
        x = jnp.take(frozen["embed"], tokens.astype(jnp.int32), axis=0)
        x = transformer_lib.scale_embeddings(x.astype(cfg.compute_dtype),
                                             cfg)
        blocks = merge(cfg, self.lora, frozen["blocks"], params["blocks"])
        x, _ = transformer_lib.backbone({"blocks": blocks}, x, cfg,
                                        layer_remat=self.remat)
        x = rmsnorm(frozen["final_norm"], x, cfg.norm_eps)
        pooled = x.astype(jnp.float32).mean(axis=1)
        return pooled @ params["cls_head"]
