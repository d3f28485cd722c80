"""qwen3-30b-a3b [moe] — 48L d_model=2048 32H (GQA kv=4, head 128)
128 experts of width 768, top-8 with renormalised weights, no shared
expert; qk-norm, RoPE theta 1e6, untied head, vocab=151936.
[hf:Qwen/Qwen3-30B-A3B]

``qwen3_30b_a3b_stage6`` registers one chip's share of it.
"""
from repro.configs.base import (MOE_FFN, LayerSpec, ModelConfig, MoEConfig,
                                uniform_stack)

ARCH = "qwen3-30b-a3b"


def config(num_layers: int = 48) -> ModelConfig:
    """``d_ff`` is the published (unused) ``intermediate_size``, which
    equals the expert width a token meets: top_k x d_ff_expert."""
    return ModelConfig(
        name=ARCH, family="moe", source="hf:Qwen/Qwen3-30B-A3B",
        d_model=2048, num_heads=32, num_kv_heads=4, head_dim=128,
        d_ff=6144, vocab_size=151936,
        stacks=uniform_stack(num_layers, LayerSpec(ffn=MOE_FFN)),
        moe=MoEConfig(num_experts=128, top_k=8, num_shared_experts=0,
                      d_ff_expert=768),
        qk_norm=True, rope_theta=1e6, activation="swiglu", norm="rmsnorm",
        tie_embeddings=False, native_context=40960,
        long_context_override=None,
    )


def reduced() -> ModelConfig:
    return config().replace(
        d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=512, stacks=uniform_stack(2, LayerSpec(ffn=MOE_FFN)),
        moe=MoEConfig(num_experts=8, top_k=2, num_shared_experts=0,
                      d_ff_expert=128),
        native_context=256)
