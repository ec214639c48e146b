"""--arch <id> registry: maps arch ids to config modules."""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig, INPUT_SHAPES, ShapeConfig

_ARCH_MODULES = {
    "zamba2-7b":        "repro.configs.zamba2_7b",
    "smollm-135m":      "repro.configs.smollm_135m",
    "chameleon-34b":    "repro.configs.chameleon_34b",
    "whisper-base":     "repro.configs.whisper_base",
    "xlstm-1.3b":       "repro.configs.xlstm_1_3b",
    "qwen2-moe-a2.7b":  "repro.configs.qwen2_moe_a2_7b",
    "olmoe-1b-7b":      "repro.configs.olmoe_1b_7b",
    "yi-6b":            "repro.configs.yi_6b",
    "minicpm3-4b":      "repro.configs.minicpm3_4b",
    "h2o-danube-1.8b":  "repro.configs.h2o_danube_1_8b",
    "paper-net":        "repro.configs.paper_net",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
}

# the zoo the launchers, dry run and per-architecture tests sweep: models
# trained whole and served. paper-net (the paper's CNN) and
# moonlight-16b-a3b (LoRA federation over a frozen base, held expert
# shares; tests/test_moonlight.py) run through the federated round only.
ARCH_IDS = [a for a in _ARCH_MODULES
            if a not in ("paper-net", "moonlight-16b-a3b")]


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).smoke_config()


def get_shape(shape: str) -> ShapeConfig:
    return INPUT_SHAPES[shape]


def applicable(arch: str, shape: str) -> tuple[bool, str]:
    """Whether (arch, shape) is exercised; reason when skipped (DESIGN.md §5)."""
    cfg = get_config(arch)
    sh = get_shape(shape)
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, "full-attention arch: 524k decode requires sub-quadratic attention (skip per spec)"
    if sh.kind == "decode" and cfg.family == "cnn":
        return False, "cnn classifier has no decode step"
    return True, ""
