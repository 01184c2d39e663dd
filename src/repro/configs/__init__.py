"""Architecture registry: ``--arch <id>`` → (full config, smoke config)."""

from repro.configs import (
    deepseek_moe_16b,
    deepseek_v2_lite,
    gemma3_27b,
    grok1_314b,
    llama3_405b,
    musicgen_large,
    phi3_vision_4_2b,
    qwen15_32b,
    qwen2_1_5b,
    recurrentgemma_2b,
    rwkv6_3b,
)
from repro.configs.base import SHAPES, ModelConfig

_MODULES = {
    "rwkv6-3b": rwkv6_3b,
    "qwen1.5-32b": qwen15_32b,
    "qwen2-1.5b": qwen2_1_5b,
    "llama3-405b": llama3_405b,
    "gemma3-27b": gemma3_27b,
    "musicgen-large": musicgen_large,
    "phi-3-vision-4.2b": phi3_vision_4_2b,
    "grok-1-314b": grok1_314b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "recurrentgemma-2b": recurrentgemma_2b,
    "deepseek-v2-lite": deepseek_v2_lite,
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    return _MODULES[arch].CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _MODULES[arch].SMOKE


def runnable_cells():
    """All (arch, shape) dry-run cells, honoring long-context skips."""
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            if shape == "long_500k" and not cfg.supports_long_context:
                continue  # pure full-attention: documented skip (DESIGN.md §4)
            cells.append((arch, shape))
    return cells
