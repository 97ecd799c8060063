"""ModelSpec — the payload contract every HFL engine trains over.

The paper's scheduling/assignment machinery is payload-agnostic (eqs.
(6)/(9)/(12) only read ``model_bits``), so the engines bind to a spec
instead of a concrete model:

* ``init_fn(key, fed) -> params`` — model init shaped by the federated
  task (input geometry, ``fed.n_classes``).
* ``apply_fn(params, X) -> logits`` — hashable and equality-stable: the
  engines pass it as a static jit argument, so the SAME object must come
  back for a given arch (``configs.registry.get_hfl_spec`` caches specs)
  or jit caches fragment. Any input adaptation (e.g. casting padded
  token tensors back to int32) is folded into ``apply_fn`` so the
  engines' call sites stay identical across payloads.
* ``eval_fn(params, X_test, y_test, frozen=None) -> float`` — chunked
  test accuracy.
* ``frozen_init_fn(key, fed) -> frozen`` — None for a payload trained
  whole. Otherwise the payload is fine-tuned: ``init_fn`` gives the
  trainable tree (what devices train, edges aggregate and the cost model
  prices), ``frozen_init_fn`` the base it adapts, held once on the
  device, and ``apply_fn`` is ``(params, X, frozen) -> logits``
  (``lora_spec``).
* ``lane_chunk`` — lanes ``cohort_local_sgd`` trains in one vmapped
  chunk, a payload too large to train the cohort at once (0: all).
* ``mini_init_fn`` / ``mini_apply_fn`` / ``mini_preprocess_fn`` — the
  IKC auxiliary model ξ and its input crop (Table I/II clustering path);
  ``mini_preprocess_fn(X, key)`` maps the padded (N, Dmax, ...) cohort
  tensor to the clustering inputs, splitting ``key`` per device.

``cnn_spec()`` reproduces the pre-spec engines' construction bit for bit
(same ``cnn.cnn_apply`` function object, same key-split order), which is
what keeps ``arch="hfl-cnn"`` on the engines' existing jit cache
entries — pinned by ``tests/test_model_zoo.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax

from repro.configs.base import LoRAConfig, ModelConfig
from repro.core.hfl import evaluate_in_batches
from repro.models import cnn
from repro.models import lora as lora_lib
from repro.models import seq_classifier as seqc


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    arch: str                       # registry id (``hfl-cnn``, ...)
    family: str                     # cnn | dense | moe | ssm | hybrid
    init_fn: Callable               # (key, fed) -> params
    apply_fn: Callable              # (params, X) -> logits (static-jit-safe)
    eval_fn: Callable               # (params, X_test, y_test) -> accuracy
    mini_init_fn: Callable          # (key, fed) -> aux params (IKC ξ)
    mini_apply_fn: Callable         # (params, crop) -> logits
    mini_preprocess_fn: Callable    # (X (N, Dmax, ...), key) -> crops
    frozen_init_fn: Optional[Callable] = None   # (key, fed) -> base
    lane_chunk: int = 0             # lanes local training vmaps at once


# ------------------------------------------------------------- hfl-cnn

def _cnn_init(key, fed):
    return cnn.cnn_init(key, fed.X_test.shape[1:3], fed.X_test.shape[3],
                        fed.n_classes)


def _cnn_mini_init(key, fed):
    return cnn.mini_init(key, fed.n_classes)


def _cnn_mini_preprocess(X, key):
    """Channel 0, random 10x10 crop per device (IKC preprocessing)."""
    return jax.vmap(cnn.mini_preprocess)(
        X[:, :, :, :, :1], jax.random.split(key, X.shape[0]))


def cnn_spec() -> ModelSpec:
    return ModelSpec(
        arch="hfl-cnn", family="cnn",
        init_fn=_cnn_init, apply_fn=cnn.cnn_apply,
        eval_fn=functools.partial(evaluate_in_batches, cnn.cnn_apply),
        mini_init_fn=_cnn_mini_init, mini_apply_fn=cnn.mini_apply,
        mini_preprocess_fn=_cnn_mini_preprocess)


# ----------------------------------------------- registry decoder archs

@dataclasses.dataclass(frozen=True)
class _SeqInit:
    cfg: ModelConfig

    def __call__(self, key, fed):
        return seqc.seq_cls_init(key, self.cfg, fed.n_classes)


@dataclasses.dataclass(frozen=True)
class _SeqMiniInit:
    vocab: int

    def __call__(self, key, fed):
        return seqc.seq_mini_init(key, self.vocab, fed.n_classes)


def _seq_mini_preprocess(X, key):
    return jax.vmap(seqc.seq_mini_preprocess)(
        X, jax.random.split(key, X.shape[0]))


def seq_spec(arch: str, cfg: ModelConfig) -> ModelSpec:
    """Sequence-classification spec over a registry ``ModelConfig``."""
    apply_fn = seqc.SeqClassifierApply(cfg)
    return ModelSpec(
        arch=arch, family=cfg.family,
        init_fn=_SeqInit(cfg), apply_fn=apply_fn,
        eval_fn=functools.partial(evaluate_in_batches, apply_fn),
        mini_init_fn=_SeqMiniInit(cfg.vocab_size),
        mini_apply_fn=seqc.seq_mini_apply,
        mini_preprocess_fn=_seq_mini_preprocess)


# ------------------------------------------- LoRA fine-tuning payloads

@dataclasses.dataclass(frozen=True)
class _FrozenInit:
    cfg: ModelConfig

    def __call__(self, key, fed):
        return lora_lib.frozen_init(key, self.cfg)


@dataclasses.dataclass(frozen=True)
class _AdapterInit:
    cfg: ModelConfig
    lora: LoRAConfig

    def __call__(self, key, fed):
        # adapters are shaped by the base alone: its shapes, no values
        frozen = jax.eval_shape(
            functools.partial(lora_lib.frozen_init, cfg=self.cfg), key)
        return lora_lib.adapters_init(key, self.cfg, self.lora,
                                      fed.n_classes, frozen)


def lora_spec(arch: str, cfg: ModelConfig, lora: LoRAConfig, *,
              remat: bool = False, lane_chunk: int = 0,
              eval_batch: int = 512) -> ModelSpec:
    """LoRA fine-tuning of a frozen registry decoder as a sequence
    classifier (``models/lora.py``): adapters and head trainable, the
    base frozen and held once."""
    apply_fn = lora_lib.LoRAClassifierApply(cfg, lora, remat=remat)
    return ModelSpec(
        arch=arch, family=cfg.family,
        init_fn=_AdapterInit(cfg, lora), apply_fn=apply_fn,
        eval_fn=functools.partial(evaluate_in_batches, apply_fn,
                                  batch=eval_batch),
        mini_init_fn=_SeqMiniInit(cfg.vocab_size),
        mini_apply_fn=seqc.seq_mini_apply,
        mini_preprocess_fn=_seq_mini_preprocess,
        frozen_init_fn=_FrozenInit(cfg), lane_chunk=lane_chunk)
