"""Architecture registry: ``--arch <id>`` resolution for all launchers."""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import List, Tuple

from repro.configs.base import InputShape, ModelConfig

_MODULES = {
    "jamba-1.5-large-398b": "repro.configs.jamba_1_5_large_398b",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "mamba2-2.7b": "repro.configs.mamba2_2_7b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "mistral-nemo-12b": "repro.configs.mistral_nemo_12b",
    "musicgen-medium": "repro.configs.musicgen_medium",
    "llama4-scout-17b-a16e": "repro.configs.llama4_scout_17b_a16e",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b_a22b",
    "llama3-405b": "repro.configs.llama3_405b",
    "mistral-large-123b": "repro.configs.mistral_large_123b",
    "granite-4.0-h-micro": "repro.configs.granite_4_0_h_micro",
    "hfl-cnn": "repro.configs.hfl_cnn",
}

ARCH_IDS: List[str] = [a for a in _MODULES if a != "hfl-cnn"]

# HFL payload archs exercised by tests/bench_model_zoo: the paper CNN
# plus one arch per decoder family (dense / ssm / moe). Any _MODULES id
# resolves through get_hfl_spec; these are the CI smoke set.
HFL_SMOKE_ARCHS: Tuple[str, ...] = (
    "hfl-cnn", "mistral-nemo-12b", "mamba2-2.7b", "qwen3-moe-235b-a22b")


def get_hfl_spec(arch: str, scale: str = "smoke"):
    """Resolve ``--arch`` to the :class:`repro.models.spec.ModelSpec`
    the HFL engines train over.

    ``hfl-cnn`` is the paper's FashionMNIST/CIFAR CNN (the default —
    bitwise-identical to the pre-spec engines). Every other registry id
    maps to its CPU-trainable ``smoke_config()`` variant (remat off,
    f32) wrapped as a sequence classifier over the synthetic
    ``make_seq_dataset`` task; the cost model prices whatever payload
    comes back via ``model_bits``. An arch whose module defines ``LORA``
    is LoRA fine-tuning of its frozen base (``spec.lora_spec``), and
    ``scale="stage"`` gives it at the published widths with the layers
    one pipeline stage holds (the module's ``stage_config()``, in its
    own dtype, each layer recomputed in the backward pass and the lanes
    trained ``STAGE_LANE_CHUNK`` at a time). Cached so repeated
    resolution returns the SAME spec object — ``apply_fn`` is a static
    jit argument and must not fragment the engines' jit caches.
    """
    return _hfl_spec(arch, scale)


@functools.lru_cache(maxsize=None)
def _hfl_spec(arch: str, scale: str):
    from repro.models import spec as spec_lib
    if arch == "hfl-cnn" and scale == "smoke":
        return spec_lib.cnn_spec()
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(_MODULES[arch])
    lora = getattr(mod, "LORA", None)
    if scale == "stage" and lora is not None:
        return spec_lib.lora_spec(arch, mod.stage_config(), lora,
                                  remat=True,
                                  lane_chunk=mod.STAGE_LANE_CHUNK,
                                  eval_batch=mod.STAGE_EVAL_BATCH)
    if scale != "smoke":
        raise ValueError(f"{arch!r} has no {scale!r} payload")
    cfg = dataclasses.replace(get_smoke_config(arch),
                              remat=False, dtype="float32")
    if lora is not None:
        return spec_lib.lora_spec(arch, cfg, lora)
    return spec_lib.seq_spec(arch, cfg)


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(_MODULES[arch])
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(_MODULES[arch])
    return mod.smoke_config()


def variant_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Shape-conditioned config variant.

    long_500k decode requires sub-quadratic state: SSM/hybrid keep their
    constant-size state; any config with attention layers switches to a
    sliding-window KV cache (window 8192) — Jamba's own long-context
    choice, applied to the dense/vlm/moe/audio archs as the documented
    SWA variant (DESIGN.md §Arch-applicability).
    """
    if shape.name == "long_500k" and cfg.family != "ssm":
        return dataclasses.replace(cfg, sliding_window=8192)
    return cfg


def decode_supported(cfg: ModelConfig) -> bool:
    """All assigned archs are decoders; encoder-only archs would return
    False here and skip decode shapes."""
    return True
