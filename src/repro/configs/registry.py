"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig

_MODULES = {
    "qwen3-4b": "repro.configs.qwen3_4b",
    "hymba-1.5b": "repro.configs.hymba_1_5b",
    "musicgen-medium": "repro.configs.musicgen_medium",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "gemma3-27b": "repro.configs.gemma3_27b",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "phi3-mini-3.8b": "repro.configs.phi3_mini_3_8b",
    "internvl2-1b": "repro.configs.internvl2_1b",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b_a22b",
    "gemma2-2b": "repro.configs.gemma2_2b",
    "unimo-text": "repro.configs.unimo_text",
    "qwen3-30b-a3b": "repro.configs.qwen3_30b_a3b",
    "qwen3-30b-a3b-stage6": "repro.configs.qwen3_30b_a3b_stage6",
}

# the architectures of the original brief; the others were added later
ASSIGNED: List[str] = [
    "qwen3-4b", "hymba-1.5b", "musicgen-medium", "deepseek-v3-671b",
    "gemma3-27b", "xlstm-125m", "phi3-mini-3.8b", "internvl2-1b",
    "qwen3-moe-235b-a22b", "gemma2-2b"]


def get_config(arch: str) -> ModelConfig:
    return importlib.import_module(_MODULES[arch]).config()


def get_reduced(arch: str) -> ModelConfig:
    return importlib.import_module(_MODULES[arch]).reduced()


def list_archs() -> List[str]:
    return list(_MODULES)


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in _MODULES}
