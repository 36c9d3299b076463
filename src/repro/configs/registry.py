"""``--arch`` registry: maps architecture ids to configs and families.

``config_for_shape`` applies the per-shape adaptations from DESIGN.md §4:
the long_500k decode shape switches full-attention families (dense, moe,
vlm) to the sliding-window variant (window 8192); ssm/hybrid run it
natively; whisper skips it (enc-dec) — ``supported`` returns False.
"""
from __future__ import annotations

import importlib

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig

# arch id -> config module name (under repro.configs)
ASSIGNED = {
    "olmoe-1b-7b": "olmoe_1b_7b",
    "hymba-1.5b": "hymba_1_5b",
    "xlstm-1.3b": "xlstm_1_3b",
    "internvl2-26b": "internvl2_26b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "deepseek-67b": "deepseek_67b",
    "whisper-small": "whisper_small",
    "granite-3-2b": "granite_3_2b",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
}

# the paper's own evaluation models (benchmarks + tests, not dry-run pairs)
PAPER_MODELS = {
    "resnet50": "resnet50",
    "resnext50": "resnext50",
    "bert-base": "bert_base",
    "xlnet-base": "xlnet_base",
}

ALL = {**ASSIGNED, **PAPER_MODELS}

LONG_CONTEXT_WINDOW = 8192  # sliding window used by full-attention archs at 500k


def _module(arch: str):
    if arch not in ALL:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ALL)}")
    return importlib.import_module(f"repro.configs.{ALL[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


def inference_config(cfg: ModelConfig) -> ModelConfig:
    """Inference deployments carry bf16 weights (f32 masters are a
    training-only concern)."""
    return cfg.with_(param_dtype="bfloat16")


def serving_config(arch: str) -> ModelConfig:
    """The published config as the serving path holds it."""
    return inference_config(get_config(arch))


def supported(arch: str, shape: ShapeConfig | str) -> bool:
    shape = SHAPES[shape] if isinstance(shape, str) else shape
    cfg = get_config(arch)
    if cfg.family in ("cnn", "encoder"):
        return False  # paper eval models: benchmark-only
    if shape.name == "long_500k" and cfg.family == "audio":
        return False  # enc-dec decoder horizon (DESIGN.md §4)
    return True


def config_for_shape(arch: str, shape: ShapeConfig | str, *, num_instances: int = 1) -> ModelConfig:
    shape = SHAPES[shape] if isinstance(shape, str) else shape
    cfg = get_config(arch)
    if not supported(arch, shape):
        raise ValueError(f"{arch} does not run shape {shape.name} (see DESIGN.md §4)")
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm"):
        cfg = cfg.with_(sliding_window=LONG_CONTEXT_WINDOW)
    if shape.kind in ("prefill", "decode"):
        cfg = inference_config(cfg)
    if num_instances != 1:
        cfg = cfg.with_(num_instances=num_instances)
    return cfg
