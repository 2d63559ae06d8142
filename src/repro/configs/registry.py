"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig

_MODULES = {
    "llama-3.2-vision-11b": "repro.configs.llama_3_2_vision_11b",
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "yi-6b": "repro.configs.yi_6b",
    "llama3.2-3b": "repro.configs.llama3_2_3b",
    "qwen3-32b": "repro.configs.qwen3_32b",
    "granite-8b": "repro.configs.granite_8b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "whisper-medium": "repro.configs.whisper_medium",
    "deepseek-v3": "repro.configs.deepseek_v3",
    "deepseek-v3-ep32": "repro.configs.deepseek_v3",
}

ARCH_IDS = tuple(_MODULES)


def _module(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; have {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch_id])


def get_config(arch_id: str) -> ModelConfig:
    """The whole model, or one chip's share of it (a module's ``SHARES``)."""
    mod = _module(arch_id)
    return getattr(mod, "SHARES", {}).get(arch_id, mod.CONFIG)


def get_smoke_config(arch_id: str) -> ModelConfig:
    mod = _module(arch_id)
    share = getattr(mod, "SHARES", {}).get(arch_id)
    return mod.smoke_config() if share is None else mod.smoke_config(share)
