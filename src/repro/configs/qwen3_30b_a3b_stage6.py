"""qwen3-30b-a3b-stage6 [moe] — one chip's share of Qwen3-30B-A3B served as
an eight-stage pipeline: 6 whole layers at the published widths, all 128
experts of each.  [hf:Qwen/Qwen3-30B-A3B]
"""
from repro.configs import qwen3_30b_a3b as full
from repro.configs.base import ModelConfig

LAYERS = 6


def config() -> ModelConfig:
    return full.config(LAYERS).replace(name=full.ARCH + "-stage6")


def reduced() -> ModelConfig:
    return full.reduced()
