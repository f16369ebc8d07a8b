"""Architecture registry: ``--arch <id>`` → ModelConfig."""

from __future__ import annotations

import importlib

from repro.configs.base import (ModelConfig, MoEConfig, PrefixCacheConfig,
                                SpecConfig, SSMConfig, TaylorConfig)

_ARCH_MODULES = {
    "whisper-large-v3": "whisper_large_v3",
    "gemma3-1b": "gemma3_1b",
    "yi-9b": "yi_9b",
    "stablelm-1.6b": "stablelm_1_6b",
    "gemma2-27b": "gemma2_27b",
    "llava-next-34b": "llava_next_34b",
    "zamba2-7b": "zamba2_7b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
    "llama4-maverick-400b-a17b": "llama4_maverick",
    "grok-1-314b": "grok_1",
    "xlstm-125m": "xlstm_125m",
    "taylorshift-lra": "taylorshift_lra",   # the paper's own encoder
}

ARCH_IDS = [a for a in _ARCH_MODULES if a != "taylorshift-lra"]


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.CONFIG


__all__ = ["ModelConfig", "MoEConfig", "PrefixCacheConfig", "SpecConfig",
           "SSMConfig", "TaylorConfig", "get_config", "ARCH_IDS"]
