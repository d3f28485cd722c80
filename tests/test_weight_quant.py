"""Serve-time int8 weight-only quantization (weights_dtype policy axis).

Contracts under test:
  * quantize/dequantize round-trip error is bounded by half a
    quantization step at each output channel's absmax scale;
  * ``weights_store_dtype`` resolves the policy axis (and rejects
    unknown values);
  * the fused-dequant Pallas matmul kernel (interpret mode) matches the
    fp32 oracle on tile-aligned AND non-tile-multiple shapes;
  * ``compress_weights`` rewrites exactly the serve-path dense matmul
    set — attention qkv/out, dense FFN, the unembed head — and leaves
    MoE expert stacks (router present) and the embedding gather table
    untouched; tied-embedding archs gain a separate quantized head;
  * weight_bytes accounting: int8 codes + fp32 scales land near 1/4 of
    the fp32 dense bytes (a bit above — the scales);
  * int8 first-step logits stay within the error the per-channel
    absmax bound allows against fp32;
  * serving with int8 weights works on every execution path — bucketed
    admission, fused decode, mixed chunked, token-packed, speculative
    verify — with identical greedy outputs across paths;
  * the Pallas kernel path (interpret mode) is greedy-bit-identical to
    the jnp fallback through the full serve loop;
  * ServeMetrics weight fields and zero-guards.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core.continuous import ServeMetrics
from repro.core.engine import InferenceEngine
from repro.core.precision import (FP32, compress_weights,
                                  dequantize_weights, is_quantized_weight,
                                  quantize_weights, weights_store_dtype)
from repro.core.scheduler import Request
from repro.kernels import ops as KOPS
from repro.kernels import quant_matmul as QM
from repro.kernels import ref as KREF
from repro.models import transformer as T

W8 = dataclasses.replace(FP32, weights_dtype="int8")


def _trace(rng, spec=((6, 4), (12, 4), (9, 3))):
    return [Request(uid=i, tokens=[2] + list(map(int, rng.integers(
        4, 400, size=ln))), max_new_tokens=mn)
        for i, (ln, mn) in enumerate(spec)]


# ---------------------------------------------------------------------------
# Quantize / dequantize primitives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(16, 8), (300, 520), (2, 64, 48)])
def test_weight_quant_roundtrip_error_bound(rng, shape):
    """|dequant(quant(w)) - w| <= absmax(col)/127/2 per element (half a
    quantization step at the output channel's scale)."""
    w = jnp.asarray(rng.normal(size=shape) * 2.0, jnp.float32)
    rec = quantize_weights(w)
    assert is_quantized_weight(rec)
    assert rec["q"].dtype == jnp.int8 and rec["s"].dtype == jnp.float32
    assert rec["q"].shape == shape
    assert rec["s"].shape == shape[:-2] + shape[-1:]
    back = np.asarray(dequantize_weights(rec))
    bound = np.abs(np.asarray(w)).max(axis=-2, keepdims=True) / 127.0 / 2.0
    assert (np.abs(back - np.asarray(w)) <= bound + 1e-7).all()


def test_weight_quant_zero_columns(rng):
    z = jnp.zeros((8, 4), jnp.float32)
    rec = quantize_weights(z)
    assert (np.asarray(rec["q"]) == 0).all()
    assert (np.asarray(rec["s"]) == 0).all()
    assert (np.asarray(dequantize_weights(rec)) == 0).all()


def test_weights_store_dtype_resolution():
    assert weights_store_dtype("auto", jnp.bfloat16) == jnp.bfloat16
    assert weights_store_dtype("bf16", jnp.float32) == jnp.bfloat16
    assert weights_store_dtype("fp16", jnp.float32) == jnp.float16
    assert weights_store_dtype("int8", jnp.float32) == jnp.int8
    with pytest.raises(ValueError):
        weights_store_dtype("int4", jnp.float32)


# ---------------------------------------------------------------------------
# Pallas kernel vs fp32 oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (32, 128, 128),        # exactly one tile
    (64, 256, 256),        # multi-tile, aligned
    (1, 256, 200),         # decode row (M pads 1 -> 32), ragged N
    (7, 130, 257),         # off-by-one over tile edges
    (33, 128, 129),
])
def test_quant_matmul_kernel_matches_oracle(rng, m, k, n):
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rec = quantize_weights(jnp.asarray(rng.normal(size=(k, n)),
                                       jnp.float32))
    assert QM.shape_supported(x, rec["q"], rec["s"])
    out = QM.quant_matmul(x, rec["q"], rec["s"], interpret=True)
    ref = KREF.quant_matmul_ref(x, rec["q"], rec["s"])
    assert out.shape == (m, n) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_quant_matmul_kernel_batched_lead_dims(rng):
    """(B, S, K) activations flatten through the kernel unchanged."""
    x = jnp.asarray(rng.normal(size=(3, 5, 96)), jnp.float32)
    rec = quantize_weights(jnp.asarray(rng.normal(size=(96, 72)),
                                       jnp.float32))
    out = QM.quant_matmul(x, rec["q"], rec["s"], interpret=True)
    ref = KREF.quant_matmul_ref(x, rec["q"], rec["s"])
    assert out.shape == (3, 5, 72)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_quant_matmul_shape_guards(rng):
    rec = quantize_weights(jnp.asarray(rng.normal(size=(16, 8)),
                                       jnp.float32))
    x = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    assert not QM.shape_supported(x[0], rec["q"], rec["s"])     # 1-D x
    assert not QM.shape_supported(x, rec["q"].astype(jnp.int32),
                                  rec["s"])                     # not int8
    assert not QM.shape_supported(
        jnp.zeros((2, 17), jnp.float32), rec["q"], rec["s"])    # K mismatch
    # pathological padding blowup is refused (1x1 weight -> 128x128 tile)
    tiny = quantize_weights(jnp.ones((1, 1), jnp.float32))
    assert not QM.shape_supported(jnp.ones((1, 1), jnp.float32),
                                  tiny["q"], tiny["s"])


def test_dispatcher_off_mode_returns_none(rng):
    rec = quantize_weights(jnp.asarray(rng.normal(size=(256, 256)),
                                       jnp.float32))
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    with KOPS.kernel_mode_ctx("off"):
        assert KOPS.maybe_quant_matmul(x, rec["q"], rec["s"]) is None
    with KOPS.kernel_mode_ctx("interpret"):
        out = KOPS.maybe_quant_matmul(x, rec["q"], rec["s"])
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(KREF.quant_matmul_ref(x, rec["q"], rec["s"])),
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# compress_weights: structure + byte accounting
# ---------------------------------------------------------------------------


def test_compress_weights_structure_untied(key):
    cfg = get_reduced("phi3-mini-3.8b")
    assert not cfg.tie_embeddings
    params = T.init_params(key, cfg)
    comp, stats = compress_weights(params, W8)
    assert stats["weights_dtype"] == "int8"
    assert stats["n_quantized"] > 0
    # untied: the unembed head quantizes in place; the gather table and
    # norm weights stay full precision
    assert is_quantized_weight(comp["embed"]["head"])
    assert not isinstance(comp["embed"]["tokens"], dict)
    assert not isinstance(comp["final_norm"]["w"], dict)
    blk = comp["stacks"][0][0]
    for k in ("wq", "wk", "wv", "wo"):
        assert is_quantized_weight(blk["attn"][k])
    assert is_quantized_weight(blk["ffn"]["wi"])
    # int8 codes + fp32 scales vs fp32 dense: near 1/4, scales on top
    assert stats["weight_bytes"] < 0.3 * stats["weight_bytes_dense"]
    assert stats["weight_bytes"] + stats["weight_bytes_saved"] \
        == stats["weight_bytes_dense"]
    # the original tree is untouched (fresh containers, not mutation)
    assert not isinstance(params["embed"]["head"], dict)


def test_compress_weights_structure_tied(key):
    cfg = get_reduced("qwen3-4b")
    assert cfg.tie_embeddings
    comp, stats = compress_weights(T.init_params(key, cfg), W8)
    # tied: the gather table stays dense (exact lookups); a SEPARATE
    # transposed quantized head carries the unembed matmul
    assert not isinstance(comp["embed"]["tokens"], dict)
    assert is_quantized_weight(comp["embed"]["head_q8"])
    d, v = comp["embed"]["tokens"].shape[::-1]
    assert comp["embed"]["head_q8"]["q"].shape == (d, v)
    assert stats["n_quantized"] > 0


def test_compress_weights_skips_moe_experts(key):
    cfg = get_reduced("qwen3-moe-235b-a22b")
    comp, stats = compress_weights(T.init_params(key, cfg), W8)
    ffn = comp["stacks"][0][0]["ffn"]
    # expert stacks feed ragged_dot and must stay dense arrays
    assert "router" in ffn
    for k in ("router", "wi", "wg", "wo"):
        assert not isinstance(ffn[k], dict)
    # attention + head still quantize
    assert is_quantized_weight(comp["stacks"][0][0]["attn"]["wq"])
    assert is_quantized_weight(comp["embed"]["head"])
    assert stats["n_quantized"] > 0


def test_compress_weights_noop_modes(key):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(key, cfg)
    same, stats = compress_weights(params, FP32)       # auto = no-op
    assert stats["n_quantized"] == 0
    assert stats["weight_bytes"] == stats["weight_bytes_dense"]
    assert same["stacks"][0][0]["attn"]["wq"] is \
        params["stacks"][0][0]["attn"]["wq"]
    # bf16 storage halves bytes without records (exactly half on an
    # untied arch; tied archs keep the shared gather table fp32)
    up = T.init_params(key, get_reduced("phi3-mini-3.8b"))
    bf, bst = compress_weights(
        up, dataclasses.replace(FP32, weights_dtype="bf16"))
    assert bf["stacks"][0][0]["attn"]["wq"].dtype == jnp.bfloat16
    assert bf["embed"]["head"].dtype == jnp.bfloat16
    assert bst["weight_bytes"] * 2 == bst["weight_bytes_dense"]


# ---------------------------------------------------------------------------
# Serving: every execution path, int8 weights
# ---------------------------------------------------------------------------


def _quant_rel_step(params):
    """Worst per-matmul relative output error the per-channel absmax
    bound allows: each code is off by at most half a step s_j =
    absmax_j / 127, so a column's output error is at most s_j / 2 in
    RMS units of the inputs, against an output scale of rms(W[:, j])."""
    worst, n = 0.0, 0
    for leaf in jax.tree.leaves(params, is_leaf=is_quantized_weight):
        if not is_quantized_weight(leaf):
            continue
        w = np.asarray(dequantize_weights(leaf))
        s = np.asarray(leaf["s"])
        rms = np.sqrt(np.mean(np.square(w), axis=-2))
        worst = max(worst, float(np.max(s / 2 / np.maximum(rms, 1e-30))))
        n += int(np.prod(s.shape[:-1]))     # stacked repeats count each
    return worst, n


def test_int8_weights_serve_all_paths_match_fp32(rng):
    """int8 weights against fp32, and every int8 execution path against
    the others.  Greedy tokens are not compared with fp32: on random
    weights, margins below the quantization noise flip.  Instead the
    first-step logits of every prompt must stay within the error the
    per-channel absmax bound allows (first order: one relative step per
    quantized matmul on the path), and the int8 serving paths —
    bucketed, fused decode, mixed chunked, packed — must agree bit for
    bit."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _trace(rng)

    q8 = InferenceEngine(cfg, params, policy=W8, max_len=64,
                         max_batch=2).params
    rel, n_mm = _quant_rel_step(q8)
    assert 0.0 < rel < 0.05 and n_mm > 0
    for r in reqs:
        toks = jnp.asarray([r.tokens], jnp.int32)
        fp = np.asarray(T.forward_train(params, cfg, toks, remat=False)[0])
        qq = np.asarray(T.forward_train(q8, cfg, toks, remat=False)[0])
        fp, qq = fp[0, -1], qq[0, -1]
        err = np.sqrt(np.mean(np.square(qq - fp)))
        assert err <= n_mm * rel * np.sqrt(np.mean(np.square(fp))), \
            (r.uid, err, n_mm, rel)
        assert err > 0.0               # the int8 weights were really used

    modes = {
        "bucketed": dict(chunked_prefill=False),
        "fused_decode": dict(chunked_prefill=False, steps_per_sync=3),
        "mixed": dict(max_batched_tokens=16, packed=False),
        "packed": dict(max_batched_tokens=16, packed=True),
    }
    outs = {}
    for name, kw in modes.items():
        eng = InferenceEngine(cfg, params, policy=W8, max_len=64,
                              max_batch=2)
        done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                       prefix_cache=True, **kw)
        outs[name] = [r.result for r in done]
        assert all(r.result for r in done)
        assert m.weight_dtype == "int8"
        assert m.weight_bytes > 0
        assert m.weight_bytes_saved > m.weight_bytes * 2
    base = outs["bucketed"]
    for name in modes:
        assert outs[name] == base, name


def test_int8_weights_spec_verify_path(rng):
    """Speculative verify runs through the quantized unembed/qkv path
    and stays bit-identical to non-speculative int8 serving."""
    from repro.core.speculative import SpecConfig
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _trace(rng, spec=((8, 6), (14, 6)))
    base, _ = InferenceEngine(cfg, params, policy=W8, max_len=64,
                              max_batch=2).serve_continuous(
        copy.deepcopy(reqs), page_size=8, prefix_cache=False)
    spec, m = InferenceEngine(cfg, params, policy=W8, max_len=64,
                              max_batch=2).serve_continuous(
        copy.deepcopy(reqs), page_size=8, prefix_cache=False,
        spec=SpecConfig(k=3, drafter="ngram"))
    assert [r.result for r in spec] == [r.result for r in base]
    assert m.spec_mode == "ngram"


def test_int8_weights_kernel_vs_fallback_bit_identical(rng):
    """kernel_mode interpret (Pallas quant matmul) vs off (jnp
    fallback): the serve loop's greedy streams must be bit-identical —
    both paths accumulate codes in fp32 and rescale once per column."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _trace(rng)
    eng = InferenceEngine(cfg, params, policy=W8, max_len=64, max_batch=2)
    base, _ = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   max_batched_tokens=16,
                                   prefix_cache=True)
    eng2 = InferenceEngine(cfg, params, policy=W8, max_len=64, max_batch=2)
    with KOPS.kernel_mode_ctx("interpret"):
        done, _ = eng2.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                        max_batched_tokens=16,
                                        prefix_cache=True)
    for a, b in zip(base, done):
        assert a.result == b.result


def test_weights_trace_event_and_span(rng):
    """Traced int8 serving emits a schema-valid 'weights' event carrying
    the construction-time quantization wall as ``quantize_s``, and no
    span for it: every span is a device dispatch of this serve call (the
    device track's sum reconciles with device_s)."""
    from repro.core.trace import ServeTracer, validate_events
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tr = ServeTracer()
    eng = InferenceEngine(cfg, params, policy=W8, max_len=64, max_batch=2)
    eng.serve_continuous(_trace(rng), page_size=8,
                         max_batched_tokens=16, trace=tr)
    assert validate_events(tr.events) == []
    wev = [e for e in tr.events if e["kind"] == "weights"]
    assert len(wev) == 1
    assert wev[0]["dtype"] == "int8"
    assert 0 < wev[0]["weight_bytes"] < wev[0]["weight_bytes_dense"]
    assert wev[0]["quantize_s"] == eng.weight_quant_s > 0
    spans = [e for e in tr.events if e["kind"] == "span"]
    assert spans and all(e["track"] == "device" for e in spans)
    assert "quantize_weights" not in {e["name"] for e in spans}
    # fp32 runs carry no quantize_s (byte-determinism of fake-clock
    # traces) but still stamp the weights gauge
    tr2 = ServeTracer()
    InferenceEngine(cfg, params, policy=FP32, max_len=64,
                    max_batch=2).serve_continuous(
        _trace(rng), page_size=8, max_batched_tokens=16, trace=tr2)
    wev2 = [e for e in tr2.events if e["kind"] == "weights"]
    assert len(wev2) == 1 and "quantize_s" not in wev2[0]
    assert all(e["track"] == "device" for e in tr2.events
               if e["kind"] == "span")
    assert validate_events(tr2.events) == []


# ---------------------------------------------------------------------------
# Metrics guards
# ---------------------------------------------------------------------------


def test_servemetrics_weight_defaults_and_dict():
    m = ServeMetrics()
    assert m.weight_dtype == "auto"
    assert m.weight_bytes == 0 and m.weight_bytes_saved == 0
    assert m.host_syncs == 0
    d = m.to_dict()
    for k in ("weight_dtype", "weight_bytes", "weight_bytes_saved",
              "host_syncs"):
        assert k in d


def test_host_syncs_counted_per_iteration(rng):
    """On the coalesced mixed path every iteration blocks exactly once,
    so host_syncs stays at/below the dispatch count and above zero."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                          max_batch=2)
    _, m = eng.serve_continuous(_trace(rng), page_size=8,
                                max_batched_tokens=16, packed=False,
                                prefix_cache=True)
    assert 0 < m.host_syncs <= m.mixed_iters + m.steps
    assert m.host_syncs < m.mixed_dispatches + m.steps
