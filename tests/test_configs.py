"""Structural assertions: every assigned config matches the brief exactly."""
import pytest

from repro.configs.base import MOE_FFN
from repro.configs.registry import ASSIGNED, get_config, get_reduced

EXPECT = {
    "qwen3-4b": dict(L=36, d=2560, H=32, kv=8, ff=9728, V=151936),
    "hymba-1.5b": dict(L=32, d=1600, H=25, kv=5, ff=5504, V=32001),
    "musicgen-medium": dict(L=48, d=1536, H=24, kv=24, ff=6144, V=2048),
    "deepseek-v3-671b": dict(L=61, d=7168, H=128, kv=128, V=129280),
    "gemma3-27b": dict(L=62, d=5376, H=32, kv=16, ff=21504, V=262144),
    "xlstm-125m": dict(L=12, d=768, H=4, kv=4, V=50304),
    "phi3-mini-3.8b": dict(L=32, d=3072, H=32, kv=32, ff=8192, V=32064),
    "internvl2-1b": dict(L=24, d=896, H=14, kv=2, ff=4864, V=151655),
    "qwen3-moe-235b-a22b": dict(L=94, d=4096, H=64, kv=4, V=151936),
    "gemma2-2b": dict(L=26, d=2304, H=8, kv=4, ff=9216, V=256000),
    "qwen3-30b-a3b": dict(L=48, d=2048, H=32, kv=4, ff=6144, V=151936),
    "qwen3-30b-a3b-stage6": dict(L=6, d=2048, H=32, kv=4, ff=6144,
                                 V=151936),
}


@pytest.mark.parametrize("arch", list(EXPECT))
def test_exact_config(arch):
    cfg = get_config(arch)
    e = EXPECT[arch]
    assert cfg.num_layers == e["L"]
    assert cfg.d_model == e["d"]
    assert cfg.num_heads == e["H"]
    assert cfg.num_kv_heads == e["kv"]
    assert cfg.vocab_size == e["V"]
    if "ff" in e:
        assert cfg.d_ff == e["ff"]
    assert cfg.source


def test_assigned_count():
    assert len(ASSIGNED) == 10


def test_moe_configs():
    ds = get_config("deepseek-v3-671b")
    assert ds.moe.num_experts == 256 and ds.moe.top_k == 8
    assert ds.moe.num_shared_experts == 1 and ds.moe.d_ff_expert == 2048
    assert ds.mla is not None and ds.mtp
    qw = get_config("qwen3-moe-235b-a22b")
    assert qw.moe.num_experts == 128 and qw.moe.top_k == 8
    assert all(s.pattern[0].ffn == MOE_FFN for s in qw.stacks)


def test_qwen3_30b_a3b_and_its_stage():
    """The published Qwen3-30B-A3B: 128 experts of 768, top 8, no shared
    expert, qk-norm, untied; 30.5B parameters, 3.3B active.  The stage
    is the same widths at 6 layers."""
    full, stage = get_config("qwen3-30b-a3b"), get_config(
        "qwen3-30b-a3b-stage6")
    for cfg in (full, stage):
        m = cfg.moe
        assert (m.num_experts, m.top_k, m.num_shared_experts,
                m.d_ff_expert) == (128, 8, 0, 768)
        assert cfg.qk_norm and not cfg.tie_embeddings
        assert cfg.rope_theta == 1e6 and cfg.resolved_head_dim == 128
        assert cfg.long_context_override is None
        assert all(s.pattern[0].ffn == MOE_FFN for s in cfg.stacks)
    assert 3.0e10 < full.param_counts()["total"] < 3.1e10
    assert 3.2e9 < full.param_counts()["active"] < 3.4e9
    assert 4.3e9 < stage.param_counts()["total"] < 4.4e9
    r = get_reduced("qwen3-30b-a3b")
    assert r.num_layers == 2 and r.moe.num_experts >= 8 \
        and r.moe.top_k == 2


def test_param_counts_scale():
    """Total parameter counts should land near the model names."""
    ds = get_config("deepseek-v3-671b").param_counts()
    assert 5.5e11 < ds["total"] < 8e11, ds["total"]
    assert 2e10 < ds["active"] < 4.5e10, ds["active"]
    qw = get_config("qwen3-moe-235b-a22b").param_counts()
    assert 1.7e11 < qw["total"] < 3e11, qw["total"]
    g3 = get_config("gemma3-27b").param_counts()
    assert 2.0e10 < g3["total"] < 3.5e10, g3["total"]
    x = get_config("xlstm-125m").param_counts()
    assert 0.7e8 < x["total"] < 3e8, x["total"]


@pytest.mark.parametrize("arch", ASSIGNED)
def test_reduced_bounds(arch):
    """Reduced smoke variants respect the brief: <=2-ish layers,
    d_model<=512, <=4 experts."""
    r = get_reduced(arch)
    assert r.num_layers <= 2
    assert r.d_model <= 512
    if r.moe:
        assert r.moe.num_experts <= 4


def test_gemma_patterns():
    g3 = get_config("gemma3-27b")
    first = g3.stacks[0].pattern
    assert len(first) == 6
    assert [s.window for s in first] == [1024] * 5 + [None]
    g2 = get_config("gemma2-2b")
    assert [s.window for s in g2.stacks[0].pattern] == [4096, None]
    assert g2.attn_softcap == 50.0 and g2.final_softcap == 30.0
