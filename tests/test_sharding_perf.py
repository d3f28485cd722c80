"""Sharding rules + §Perf machinery (perf flags, factored opt, grad accum,
attn_bf16 equivalence)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.registry import get_config, get_reduced
from repro.core.precision import FP32
from repro.models import transformer as T
from repro.models import layers as L
from repro.sharding import partition as SH
from repro.training import optimizer as OPT
from repro.training.train_loop import make_train_step
from repro import perf_flags


def _fake_mesh():
    """Abstract 16x16 mesh for spec computation (no devices needed)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


def test_param_pspecs_shapes():
    cfg = get_reduced("qwen3-moe-235b-a22b")
    struct = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    specs = SH.param_pspecs(struct, cfg, fsdp=False)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    by_name = {}
    for path, spec in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        by_name.setdefault(name, spec)
    # MoE expert weights shard experts over `model` (after leading repeat)
    assert tuple(by_name["wi"])[:2] == (None, "model")
    # embeddings shard vocab over model
    assert tuple(by_name["tokens"])[0] == "model"
    # norms replicated
    assert by_name["w"] == P()


def test_sanitize_drops_nondivisible():
    mesh = _fake_mesh()
    spec = SH.sanitize_spec(P("model", None), (32001, 16), mesh)
    assert tuple(spec) == (None, None)
    spec2 = SH.sanitize_spec(P("model", "data"), (32000, 160), mesh)
    assert tuple(spec2) == ("model", "data")


def test_perf_flags_parse(monkeypatch):
    monkeypatch.setenv("REPRO_PERF_OPTS", "attn_bf16,grad_accum=4")
    assert perf_flags.flag("attn_bf16")
    assert perf_flags.flag_value("grad_accum") == "4"
    assert not perf_flags.flag("tp_attn_guard")
    monkeypatch.setenv("REPRO_PERF_OPTS", "")
    assert not perf_flags.flag("attn_bf16")


def test_tp_attn_guard_replicates(monkeypatch):
    monkeypatch.setenv("REPRO_PERF_OPTS", "tp_attn_guard")
    cfg = get_config("internvl2-1b")          # 14 heads: 14 % 16 != 0
    struct = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0),
                              get_reduced("internvl2-1b")))
    # use the full cfg's head count with the reduced struct for the rule
    specs = SH.param_pspecs(struct, cfg, fsdp=False, mesh=_fake_mesh())
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    for path, spec in flat:
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if "attn" in names and names[-1] in ("wq", "wo"):
            assert spec == P(*(None,) * len(tuple(spec))) or spec == P()


def test_attn_bf16_equivalence(monkeypatch, rng):
    """attn_bf16 must be a pure layout/precision change: fp32 inputs give
    bit-identical results; bf16 inputs stay within bf16 tolerance."""
    B, S, H, D = 1, 32, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    monkeypatch.setenv("REPRO_PERF_OPTS", "")
    base = L.attention_ref(q, q, q, pos, pos, window=None, scale=0.25)
    monkeypatch.setenv("REPRO_PERF_OPTS", "attn_bf16")
    opt = L.attention_ref(q, q, q, pos, pos, window=None, scale=0.25)
    np.testing.assert_allclose(np.asarray(base), np.asarray(opt),
                               rtol=1e-6, atol=1e-6)
    qb = q.astype(jnp.bfloat16)
    optb = L.attention_ref(qb, qb, qb, pos, pos, window=None, scale=0.25)
    np.testing.assert_allclose(np.asarray(optb, np.float32),
                               np.asarray(base), rtol=3e-2, atol=3e-2)


def test_factored_optimizer_trains(key):
    """Factored mode must reduce loss comparably on a small problem."""
    from repro.core.tokenizer import FastTokenizer
    from repro.data.pipeline import packed_batches, synthetic_corpus
    cfg = get_reduced("unimo-text").replace(vocab_size=256)
    corpus = synthetic_corpus(200, seed=2)
    tok = FastTokenizer.train(corpus, 256)
    params = T.init_params(key, cfg)
    batches = packed_batches(tok, corpus, batch_size=4, seq_len=32)
    oc = OPT.AdamWConfig(lr=1e-3, warmup_steps=3, total_steps=40,
                         factored=True)
    step = jax.jit(make_train_step(cfg, oc, policy=FP32))
    st = OPT.init_state(params, factored=True)
    losses = []
    for i in range(40):
        b = {k: jnp.asarray(v) for k, v in next(batches).items()}
        params, st, m = step(params, st, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_factored_state_memory():
    """The point of factoring: second-moment bytes collapse for matrices."""
    p = {"w": jnp.zeros((512, 512))}
    full = OPT.init_state(p)
    fact = OPT.init_state(p, factored=True)
    full_b = sum(x.size * 4 for x in jax.tree.leaves(full.nu))
    fact_b = sum(x.size * 4 for x in jax.tree.leaves(fact.nu))
    assert fact_b < full_b / 100


def test_factored_nu_pspecs():
    specs = {"w": P(None, "model", None), "r3": P()}
    structs = {"w": jax.ShapeDtypeStruct((4, 16, 8), jnp.float32),
               "r3": jax.ShapeDtypeStruct((2, 3, 5), jnp.float32)}
    out = OPT.factored_nu_pspecs(specs, structs)
    # dict order: "r3" flattens before "w"
    assert tuple(out[1]["r"]) == (None, "model")
    assert tuple(out[1]["c"]) == (None, None)
    assert tuple(out[0]["r"]) == (None, None)   # replicated 3D param


def test_grad_accum_matches_single(key, rng):
    cfg = get_reduced("gemma2-2b")
    params = T.init_params(key, cfg)
    toks = jnp.asarray(rng.integers(4, cfg.vocab_size, size=(4, 16)),
                       jnp.int32)
    batch = {"tokens": toks, "labels": toks,
             "loss_mask": jnp.ones((4, 16), jnp.float32)}
    oc = OPT.AdamWConfig(warmup_steps=1, total_steps=10)
    p1, _, m1 = jax.jit(make_train_step(cfg, oc, policy=FP32))(
        params, OPT.init_state(params), batch)
    p2, _, m2 = jax.jit(make_train_step(cfg, oc, policy=FP32,
                                        grad_accum=2))(
        params, OPT.init_state(params), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 1e-4
