"""Sparse experts on the normal serving path (qwen3-30b-a3b, reduced).

Contracts under test, each at the reduced size on seeded random weights:

  * the dropless dispatch equals the all-experts dense computation, and
    padding lanes and inactive slots add no rows and no counts;
  * ``serve_continuous``'s prefill and cached decode agree with the
    float32 reference's full forward (``bench/configs/qwen3-30b-a3b.py``);
  * greedy tokens are identical across token budgets and with the prefix
    cache on and off (dropless routing makes a row independent of its
    batch);
  * the model is shareable, so it serves through chunked prefill and the
    packed forward, and distinct prompt lengths compile nothing beyond
    the stream-width buckets.
"""
import copy
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, get_reduced
from repro.core import prefix_cache as PC
from repro.core.engine import InferenceEngine, packed_width_buckets
from repro.core.precision import FP32
from repro.core.scheduler import Request
from repro.kernels import ops as KOPS
from repro.models import moe as MOE
from repro.models import transformer as T

ARCH = "qwen3-30b-a3b"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced(ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(rng, cfg, shapes, prefix=()):
    return [Request(uid=i, tokens=list(prefix) + [int(t) for t in rng.integers(
                4, cfg.vocab_size, size=n)], max_new_tokens=m)
            for i, (n, m) in enumerate(shapes)]


def _serve(cfg, params, reqs, **kw):
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=3)
    done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8, **kw)
    return {r.uid: list(r.result) for r in done}, m, eng


def _all_experts(cfg, p, x):
    """Every expert on every row, weighted by its renormalised gate and
    zero where the router did not choose it (float32, highest precision)."""
    m = cfg.moe
    hp = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(hp(xf, p["router"]), -1)
    topw, tope = jax.lax.top_k(probs, m.top_k)
    gate = jnp.zeros_like(probs).at[jnp.arange(xf.shape[0])[:, None],
                                    tope].set(topw / topw.sum(-1,
                                                              keepdims=True))
    out = sum(gate[:, e:e + 1] * hp(jax.nn.silu(hp(xf, p["wg"][e]))
                                    * hp(xf, p["wi"][e]), p["wo"][e])
              for e in range(m.num_experts))
    return out.reshape(x.shape), tope


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_dropless_matches_all_experts_and_skips_padding(mode, model, rng):
    """``off`` runs lax.ragged_dot, ``interpret`` the Pallas grouped
    matmul; rows marked invalid get no expert, no rows and zero output.
    Tolerance: float32 products at default precision against highest,
    summed over two experts of width 128 (~1e-5 here)."""
    cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["stacks"][0][0]["ffn"])
    B, S = 3, 8
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
    valid = jnp.asarray(rng.random((B, S)) < 0.6)
    KOPS.reset_counts()
    with KOPS.kernel_mode_ctx(mode):
        out, counts = jax.jit(functools.partial(
            MOE.moe_apply_dropless, cfg))(p, x, valid=valid)
        full, cnt_full = jax.jit(functools.partial(
            MOE.moe_apply_dropless, cfg))(p, x)
    if mode == "interpret":
        assert KOPS.kernel_counts().get("expert_matmul", 0) >= 3
        assert not KOPS.fallback_counts()
    ref, tope = _all_experts(cfg, p, x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    v = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(out)[v], np.asarray(ref)[v],
                               rtol=1e-4, atol=1e-4)
    assert not np.asarray(out)[~v].any()
    # counters: the valid rows' assignments only
    hits = np.bincount(np.asarray(tope).reshape(B, S, -1)[v].ravel(),
                       minlength=cfg.moe.num_experts)
    assert list(np.asarray(counts)) == [hits.sum(), (hits > 0).sum(),
                                        hits.max()]
    assert int(counts[0]) == v.sum() * cfg.moe.top_k
    assert int(cnt_full[0]) == B * S * cfg.moe.top_k


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_dropless_reads_a_whole_stack_in_place(mode, model, rng):
    """Serving hands the dispatch every layer's expert stacks whole with
    the scanned layer's index; the result and counters equal the
    dispatch of that layer's own stacks, bit for bit, and the kernel
    still runs (the layer is indexed inside it, not sliced out)."""
    cfg, params = model
    ffn = params["stacks"][0][0]["ffn"]
    layer = cfg.num_layers - 1
    own = jax.tree.map(lambda a: a[layer], ffn)
    whole = {**own, **{n: ffn[n] for n in MOE.EXPERT_MATS},
             "layer": jnp.int32(layer)}
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32)
    valid = jnp.asarray(rng.random((2, 8)) < 0.7)
    f = jax.jit(functools.partial(MOE.moe_apply_dropless, cfg))
    KOPS.reset_counts()
    with KOPS.kernel_mode_ctx(mode):
        a, ca = f(own, x, valid=valid)
        b, cb = f(whole, x, valid=valid)
    if mode == "interpret":
        assert KOPS.kernel_counts().get("expert_matmul", 0) >= 6
        assert not KOPS.fallback_counts()
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))


def _reference():
    """The benchmark's plain float32 reference of qwen3-30b-a3b."""
    if os.path.join(REPO, "bench") not in sys.path:
        sys.path.insert(0, os.path.join(REPO, "bench"))
    spec = importlib.util.spec_from_file_location(
        "qwen3_ref", os.path.join(REPO, "bench", "configs",
                                  "qwen3-30b-a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_config(cfg):
    """The bench configuration file's keys at the reduced size."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
            "num_experts": cfg.moe.num_experts,
            "num_experts_per_tok": cfg.moe.top_k,
            "moe_intermediate_size": cfg.moe.d_ff_expert,
            "rms_norm_eps": 1e-6, "rope_theta": cfg.rope_theta}


def test_serve_logits_match_the_float32_reference(model, rng):
    """Every greedy token ``serve_continuous`` served (paged pool, packed
    forward, fused decode) is the reference's argmax, and the model's
    prefill and cached decode logits of the same tokens equal the
    reference's full forward within 1e-4 at every position and vocab
    entry (float32 program against float32 reference; the two differ in
    summation order and in the reference's highest-precision products)."""
    cfg, params = model
    ref = _reference()
    c = _ref_config(cfg)
    reqs = _requests(rng, cfg, [(37, 9), (20, 12), (5, 7)])
    got, _, _ = _serve(cfg, params, reqs, max_batched_tokens=16)
    stats = jax.jit(functools.partial(ref.reference_stats, c=c))
    V = cfg.vocab_size
    for r in reqs:
        seq = r.tokens + got[r.uid]
        toks = np.zeros(256, np.int32)
        toks[:len(seq)] = seq
        targets = np.broadcast_to(np.arange(V, dtype=np.int32), (256, V))
        mx, am, full = (np.asarray(a) for a in stats(
            params, jnp.asarray(toks), jnp.asarray(targets)))
        # the program's logits: prefill the prompt, then decode through
        # the cache what was served
        cache = T.init_cache(cfg, 1, 64, jnp.float32)
        P = len(r.tokens)
        lg, cache, _ = T.forward_prefill(
            params, cfg, jnp.asarray([r.tokens]), jnp.asarray([P]), cache,
            policy=FP32)
        prog = [np.asarray(lg[0])]
        for j, t in enumerate(got[r.uid][:-1]):
            lg, cache, _ = T.forward_decode(params, cfg, jnp.asarray([[t]]),
                                         cache, jnp.asarray([P + j]),
                                         policy=FP32)
            prog.append(np.asarray(lg[0]))
        prog = np.concatenate(prog)
        n = len(seq) - 1
        np.testing.assert_allclose(prog, full[:n], rtol=1e-4, atol=1e-4)
        served = np.asarray(got[r.uid])
        assert (am[P - 1:n] == served).all()


@pytest.mark.parametrize("budget,prefix_cache", [(16, True), (64, True),
                                                 (16, False)])
def test_greedy_tokens_identical_across_budgets_and_prefix_cache(
        model, rng, budget, prefix_cache):
    cfg, params = model
    shared = [int(t) for t in rng.integers(4, cfg.vocab_size, size=17)]
    reqs = _requests(np.random.default_rng(7), cfg,
                     [(6, 6), (23, 5), (11, 8), (3, 6)], prefix=shared)
    base, _, _ = _serve(cfg, params, reqs, max_batched_tokens=64,
                        prefix_cache=False)
    got, m, _ = _serve(cfg, params, reqs, max_batched_tokens=budget,
                       prefix_cache=prefix_cache)
    assert got == base
    assert m.scheduler == "unified"
    if prefix_cache:
        assert m.prefix_matched_tokens > 0


def test_shareable_and_no_compile_beyond_width_buckets(model, rng):
    cfg, params = model
    for arch in (ARCH, ARCH + "-stage6"):
        assert PC.shareable(get_config(arch), 2048) is None
    assert PC.shareable(cfg, 64) is None
    reqs = _requests(rng, cfg, [(5, 3), (13, 3), (29, 3)])
    _, m, eng = _serve(cfg, params, reqs, max_batched_tokens=16)
    assert m.scheduler == "unified" and m.mixed_iters > 0
    fns = eng._cont_cache
    from repro.core.sampling import SamplingParams
    packed = fns[("packed", SamplingParams())]
    admit, admit_prefix, _ = fns[(SamplingParams(), 4)]
    # one packed program per stream width used, none for admission
    assert 0 < packed._cache_size() <= len(packed_width_buckets(16))
    assert admit._cache_size() == admit_prefix._cache_size() == 0
