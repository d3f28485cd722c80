"""KV-cache correctness (paper P1): prefill+decode == full forward,
ring-buffer windows, ragged batches, MLA latent cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_reduced
from repro.core.precision import FP32
from repro.models import transformer as T

settings.register_profile("cache", deadline=None, max_examples=8)
settings.load_profile("cache")

ARCHS = ["qwen3-4b", "gemma2-2b", "deepseek-v3-671b", "hymba-1.5b",
         "xlstm-125m", "musicgen-medium"]


def _toks(cfg, rng, B, S):
    shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks else (B, S)
    return jnp.asarray(rng.integers(4, cfg.vocab_size, size=shape),
                       jnp.int32)


def _decode_fn(cfg, params):
    def step(tok, cache, lens):
        return T.forward_decode(params, cfg, tok, cache, lens,
                                policy=FP32)[:2]
    return jax.jit(step)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_teacher_forcing(arch, rng, key):
    """Token-by-token decode logits == full-sequence forward logits."""
    cfg = get_reduced(arch)
    params = T.init_params(key, cfg)
    B, S = 2, 12
    toks = _toks(cfg, rng, B, S)
    full, _ = T.forward_train(params, cfg, toks, policy=FP32, remat=False)

    cache = T.init_cache(cfg, B, 64, jnp.float32)
    lens = jnp.full((B,), 4, jnp.int32)
    lg, cache, _ = T.forward_prefill(params, cfg, toks[:, :4], lens, cache,
                                  policy=FP32)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :4]),
                               rtol=3e-4, atol=3e-4)
    step = _decode_fn(cfg, params)
    for t in range(4, S):
        lg1, cache = step(toks[:, t:t+1], cache,
                          jnp.full((B,), t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(lg1[:, 0]), np.asarray(full[:, t]),
            rtol=3e-4, atol=3e-4, err_msg=f"{arch} step {t}")


@pytest.mark.parametrize("arch", ["gemma2-2b", "hymba-1.5b"])
def test_ring_cache_eviction_matches_window(arch, rng, key):
    """With a cache sized to the window, decoding far past the window must
    still match teacher forcing (ring eviction is harmless by masking)."""
    cfg = get_reduced(arch)
    params = T.init_params(key, cfg)
    B, S = 1, 100                        # window in reduced configs is 64
    toks = _toks(cfg, rng, B, S)
    full, _ = T.forward_train(params, cfg, toks, policy=FP32, remat=False)
    cache = T.init_cache(cfg, B, S, jnp.float32)
    lens = jnp.full((B,), 1, jnp.int32)
    _, cache, _ = T.forward_prefill(params, cfg, toks[:, :1], lens, cache,
                                 policy=FP32)
    step = _decode_fn(cfg, params)
    for t in range(1, S):
        lg1, cache = step(toks[:, t:t+1], cache,
                          jnp.full((B,), t, jnp.int32))
        if t > 70:                      # deep past the window
            np.testing.assert_allclose(
                np.asarray(lg1[:, 0]), np.asarray(full[:, t]),
                rtol=5e-4, atol=5e-4, err_msg=f"step {t}")


@given(st.integers(0, 2 ** 31))
def test_ragged_prefill_matches_per_row(seed):
    """Right-padded ragged batch prefill == each row prefilled alone."""
    rng = np.random.default_rng(seed)
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    B, S = 3, 10
    lens = rng.integers(1, S + 1, size=B)
    toks = jnp.asarray(rng.integers(4, cfg.vocab_size, size=(B, S)),
                       jnp.int32)
    cache = T.init_cache(cfg, B, 32, jnp.float32)
    lg, cache, _ = T.forward_prefill(params, cfg, toks,
                                  jnp.asarray(lens, jnp.int32), cache,
                                  policy=FP32)
    nxt, cache2, _ = T.forward_decode(
        params, cfg, toks[:, :1], cache, jnp.asarray(lens, jnp.int32),
        policy=FP32)
    for b in range(int(B)):
        lb = int(lens[b])
        c1 = T.init_cache(cfg, 1, 32, jnp.float32)
        lg1, c1, _ = T.forward_prefill(params, cfg, toks[b:b+1, :lb],
                                    jnp.asarray([lb], jnp.int32), c1,
                                    policy=FP32)
        np.testing.assert_allclose(np.asarray(lg[b, :lb]),
                                   np.asarray(lg1[0]),
                                   rtol=3e-4, atol=3e-4)
        n1, _, _ = T.forward_decode(params, cfg, toks[b:b+1, :1], c1,
                                 jnp.asarray([lb], jnp.int32), policy=FP32)
        np.testing.assert_allclose(np.asarray(nxt[b]), np.asarray(n1[0]),
                                   rtol=3e-4, atol=3e-4)


def test_long_context_override_ring_bounded(rng, key):
    """The beyond-paper long_500k sliding-window override: past the native
    context, global attention layers get a bounded ring cache, and decode
    matches teacher forcing *within the override window*."""
    from repro.core import kv_cache as KVC
    from repro.configs.base import LayerSpec
    cfg = get_reduced("phi3-mini-3.8b").replace(
        long_context_override=32, native_context=48)
    spec = LayerSpec()                      # global attention layer
    # below native context: full cache, no window
    assert KVC.effective_window(cfg, spec, 40) is None
    # beyond native context: override window applies, ring-bounded alloc
    assert KVC.effective_window(cfg, spec, 128) == 32
    c = KVC.layer_cache_shape(cfg, spec, 1, 128, jnp.float32)
    assert c["k"].shape[1] <= 33 + 255      # window+dump, 256-rounded

    # teacher-forcing equivalence with a window-limited reference:
    # compare decode (ring cache) vs full forward where positions beyond
    # the window are excluded by construction of the mask
    params = T.init_params(key, cfg)
    B, S = 1, 96
    toks = _toks(cfg, rng, B, S)
    cache = T.init_cache(cfg, B, 128, jnp.float32)   # 128 > native 48
    lens = jnp.full((B,), 1, jnp.int32)
    _, cache, _ = T.forward_prefill(params, cfg, toks[:, :1], lens, cache,
                                 policy=FP32, max_len=128)
    step = _decode_fn(cfg, params)
    outs = []
    for t in range(1, S):
        lg1, cache = step(toks[:, t:t+1], cache,
                          jnp.full((B,), t, jnp.int32))
        outs.append(lg1[:, 0])
    # reference: full forward with the SAME effective window everywhere
    cfg_win = cfg.replace(stacks=tuple(
        type(st)(tuple(LayerSpec(mixer=sp.mixer, ffn=sp.ffn, window=32)
                       for sp in st.pattern), st.repeats)
        for st in cfg.stacks))
    full, _ = T.forward_train(params, cfg_win, toks, policy=FP32,
                              remat=False)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, 1:]),
                               rtol=5e-4, atol=5e-4)


def test_prefill_last_only_matches_full(rng, key):
    cfg = get_reduced("gemma3-27b")
    params = T.init_params(key, cfg)
    B, S = 2, 9
    toks = _toks(cfg, rng, B, S)
    lens = jnp.asarray([S, S - 3], jnp.int32)
    c1 = T.init_cache(cfg, B, 32, jnp.float32)
    lg_all, _, _ = T.forward_prefill(params, cfg, toks, lens, c1, policy=FP32)
    c2 = T.init_cache(cfg, B, 32, jnp.float32)
    lg_last, _, _ = T.forward_prefill(params, cfg, toks, lens, c2, policy=FP32,
                                   last_only=True)
    picked = np.stack([np.asarray(lg_all)[b, int(lens[b]) - 1]
                       for b in range(B)])
    np.testing.assert_allclose(np.asarray(lg_last[:, 0]), picked,
                               rtol=1e-5, atol=1e-5)


def test_cache_struct_matches_init(key):
    cfg = get_reduced("hymba-1.5b")
    struct = T.cache_struct(cfg, 2, 64)
    real = T.init_cache(cfg, 2, 64)
    s_shapes = jax.tree.map(lambda s: (s.shape, str(s.dtype)), struct)
    r_shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)), real)
    assert s_shapes == r_shapes
