"""--arch <id> resolution for every assigned architecture."""

from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig, reduce_for_smoke

_MODULES = {
    "yi-6b": "yi_6b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "minicpm3-4b": "minicpm3_4b",
    "arctic-480b": "arctic_480b",
    "chatglm3-6b": "chatglm3_6b",
    "mamba2-2.7b": "mamba2_2_7b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "grok-1-314b": "grok_1_314b",
    "whisper-small": "whisper_small",
    "deepseek-7b": "deepseek_7b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return reduce_for_smoke(get_config(arch))
