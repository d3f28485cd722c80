"""Int8 quantized KV-cache pages (PR-3 tentpole).

Contracts under test:
  * quantize/dequantize round-trip error is bounded by the per-entry
    absmax scale (half a quantization step per element);
  * paged pool construction honors the ``kv_dtype`` policy axis per
    layer family — attention layers get int8 pools + scale pools, the
    dense-state families (MLA / recurrent / hybrid) keep full precision;
  * write -> gather round-trips through the quantized pool stay within
    the quantization error bound, for prefill scatter and decode scatter
    alike;
  * the fused-dequant paged Pallas decode kernel (interpret mode)
    matches the dense-gather fp32 oracle;
  * COW page copies carry the scale pools with the K/V codes;
  * serve_continuous on an int8 pool: shared-prefix serving is
    bit-identical to unshared serving (per-entry quantization is
    deterministic per token row, so who wrote a page cannot matter);
  * ServeMetrics capacity counters report the pool geometry and the
    zero-token-trace guards hold.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ATTN, HYBRID, MLA, MLSTM
from repro.configs.registry import get_reduced
from repro.core import kv_cache as KV
from repro.core.continuous import ServeMetrics
from repro.core.engine import InferenceEngine
from repro.core.precision import FP32, kv_store_dtype
from repro.core.scheduler import Request
from repro.kernels import decode_attention as DA
from repro.kernels import ref as R
from repro.models import transformer as T

INT8 = dataclasses.replace(FP32, kv_dtype="int8")


# ---------------------------------------------------------------------------
# Quantize / dequantize primitives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 2, 16), (3, 8, 4, 64), (1, 1, 128)])
def test_quant_roundtrip_error_bound(rng, shape):
    """|dequant(quant(x)) - x| <= absmax(row)/127/2 per element (half a
    quantization step at the row's scale)."""
    x = jnp.asarray(rng.normal(size=shape) * 3.0, jnp.float32)
    q, s = KV.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == shape[:-1]
    back = KV.dequantize_kv(q, s)
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127.0 / 2.0
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound + 1e-7).all()


def test_quant_zero_rows_and_determinism(rng):
    z = jnp.zeros((2, 3, 8), jnp.float32)
    q, s = KV.quantize_kv(z)
    assert (np.asarray(q) == 0).all() and (np.asarray(s) == 0).all()
    assert (np.asarray(KV.dequantize_kv(q, s)) == 0).all()
    # identical rows quantize identically regardless of batch context —
    # the property shared-prefix bit-exactness rests on
    x = jnp.asarray(rng.normal(size=(5, 2, 16)), jnp.float32)
    q1, s1 = KV.quantize_kv(x)
    q2, s2 = KV.quantize_kv(jnp.concatenate([x, x * 7.0], axis=0))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2)[:5])
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2)[:5])


# ---------------------------------------------------------------------------
# Pool construction per layer family / policy axis
# ---------------------------------------------------------------------------


def test_kv_store_dtype_resolution():
    assert kv_store_dtype("auto", jnp.float32) == jnp.float32
    assert kv_store_dtype("bf16", jnp.float32) == jnp.bfloat16
    assert kv_store_dtype("fp16", jnp.float32) == jnp.float16
    assert kv_store_dtype("int8", jnp.float32) == jnp.int8
    assert kv_store_dtype("int8", jnp.float32,
                          allow_int8=False) == jnp.float32
    with pytest.raises(ValueError):
        kv_store_dtype("fp8", jnp.float32)


@pytest.mark.parametrize("arch,family", [
    ("qwen3-4b", ATTN), ("deepseek-v3-671b", MLA),
    ("xlstm-125m", MLSTM), ("hymba-1.5b", HYBRID)])
def test_paged_pool_dtypes_per_family(arch, family):
    """int8 applies to pure-attention pools only; MLA / recurrent /
    hybrid keep dense (or pool) full-precision state — the same opt-out
    families as prefix sharing."""
    cfg = get_reduced(arch)
    cache = T.init_paged_cache(cfg, num_pages=4, page_size=8, max_slots=2,
                               max_len=32, dtype=jnp.float32,
                               kv_dtype="int8")
    for stack_c, stack in zip(cache["layers"], cfg.stacks):
        for c, spec in zip(stack_c, stack.pattern):
            if spec.mixer == ATTN:
                assert c["pk"].dtype == jnp.int8
                assert c["pk_scale"].dtype == jnp.float32
                assert c["pk_scale"].shape == c["pk"].shape[:-1]
                assert c["pv_scale"].shape == c["pv"].shape[:-1]
            elif spec.mixer == HYBRID:
                assert c["pk"].dtype == jnp.float32      # opt-out
                assert "pk_scale" not in c
            else:
                assert "pk" not in c and "pk_scale" not in c


def test_paged_pool_bytes_halves_under_int8():
    cfg = get_reduced("qwen3-4b")
    kw = dict(num_pages=8, page_size=8, max_slots=2, max_len=32,
              dtype=jnp.bfloat16)
    full = KV.paged_pool_bytes(T.init_paged_cache(cfg, **kw))
    quant = KV.paged_pool_bytes(
        T.init_paged_cache(cfg, kv_dtype="int8", **kw))
    # int8 codes are half the bf16 bytes; scales + ppos add back a little
    assert quant < full
    D = cfg.resolved_head_dim
    assert quant < full * (0.5 + 4.0 / (2 * D) + 0.25)


# ---------------------------------------------------------------------------
# Write / gather round-trip on a quantized pool
# ---------------------------------------------------------------------------


def _int8_pool(P, page, H, D):
    return {"pk": jnp.zeros((P, page, H, D), jnp.int8),
            "pv": jnp.zeros((P, page, H, D), jnp.int8),
            "pk_scale": jnp.zeros((P, page, H), jnp.float32),
            "pv_scale": jnp.zeros((P, page, H), jnp.float32),
            "ppos": jnp.full((P, page), -1, jnp.int32)}


def test_paged_write_gather_roundtrip_int8(rng):
    P, page, H, D = 6, 8, 2, 16
    pool = _int8_pool(P, page, H, D)
    bt = jnp.asarray([[0, 3, -1, -1]], jnp.int32)
    S = 11
    k = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    cache_pos = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7, 8, -1, -1]], jnp.int32)
    ring = KV.paged_ring_len(None, page, 4)
    pool = KV.paged_write_prefill(pool, {"k": k, "v": v}, cache_pos, bt,
                                  ring_len=ring)
    for t in range(9, 11):
        pool = KV.paged_write_decode(
            pool, {"k": k[:, t:t + 1], "v": v[:, t:t + 1]},
            jnp.asarray([t], jnp.int32), bt, jnp.asarray([True]),
            ring_len=ring)
    kk, vv, kp = KV.paged_gather(pool, bt)
    np.testing.assert_array_equal(np.asarray(kp[0, :11]), np.arange(11))
    for got, want in ((kk, k), (vv, v)):
        bound = np.abs(np.asarray(want[0])).max(axis=-1,
                                                keepdims=True) / 254.0
        err = np.abs(np.asarray(got[0, :11]) - np.asarray(want[0]))
        assert (err <= bound + 1e-7).all()


@pytest.mark.parametrize("lanes", [1, 128])
@pytest.mark.parametrize("layer", [None, 1], ids=["one", "stacked"])
@pytest.mark.parametrize("kind", ["prefill", "packed", "verify"])
def test_paged_writes_land_entry_by_entry(rng, kind, layer, lanes):
    """Each write of new tokens into an int8 pool, with many tokens on
    one page and invalid tokens sent to the dump page, leaves every
    valid token's codes and scales at its (page, offset), in the
    layer it names of a stacked pool, zero-padded to the rows of a pool
    widened to whole lanes, and every other entry as it was (the dump
    page aside): what an entry-by-entry scatter leaves."""
    P, page, H, D, L = 9, 4, 2, 8, 3
    Dw, Hw = max(D, lanes), max(H, lanes)
    rows = {"pk": (H, Dw), "pv": (H, Dw), "pk_scale": (Hw,),
            "pv_scale": (Hw,), "ppos": ()}
    pool = {k: jnp.asarray(rng.normal(size=(L, P, page) + rows[k]) * 3)
            .astype(a.dtype) for k, a in _int8_pool(P, page, H, D).items()}
    one = pool if layer is not None else {k: a[0] for k, a in pool.items()}
    bt = np.asarray([[2, 5, 0], [7, 1, -1]], np.int32)
    if kind == "prefill":
        pos = np.asarray([[3, 4, 5, 6, 7, 8, 9, -1],
                          [0, 1, 2, 3, 4, 5, -1, -1]], np.int32)
        slots, new_pos = np.repeat([0, 1], 8), pos.reshape(-1)
    elif kind == "packed":
        slots = np.asarray([0, 0, 0, 0, 0, 1, 1, 1, 1, -1], np.int32)
        new_pos = np.asarray([5, 6, 7, 8, 9, 2, 3, 4, 5, -1], np.int32)
    else:
        pos = np.asarray([6, 2], np.int32)
        slots = np.repeat([0, 1], 3)
        new_pos = (pos[:, None] + np.arange(3)).reshape(-1)
    n = slots.shape[0]
    k = rng.normal(size=(n, H, D)).astype(np.float32)
    v = rng.normal(size=(n, H, D)).astype(np.float32)
    ring = KV.paged_ring_len(None, page, bt.shape[1])
    kw = dict(ring_len=ring, layer=layer)
    if kind == "prefill":
        out = KV.paged_write_prefill(
            one, {"k": k.reshape(2, 8, H, D), "v": v.reshape(2, 8, H, D)},
            jnp.asarray(pos), jnp.asarray(bt), **kw)
    elif kind == "packed":
        out = KV.paged_write_packed(
            one, {"k": k[None], "v": v[None]}, jnp.asarray(slots),
            jnp.asarray(new_pos), jnp.asarray(bt), **kw)
    else:
        out = KV.paged_write_decode_multi(
            one, {"k": k.reshape(2, 3, H, D), "v": v.reshape(2, 3, H, D)},
            jnp.asarray(pos), jnp.asarray(bt), **kw)
    want = {key: np.array(a) for key, a in one.items()}
    at = () if layer is None else (layer,)
    for t in range(n):
        if slots[t] < 0 or new_pos[t] < 0:
            continue
        phys, off = bt[slots[t], new_pos[t] // page], new_pos[t] % page
        for key, val in (("pk", k[t]), ("pv", v[t])):
            q, sc = KV.quantize_kv(jnp.asarray(val))
            row = want[key][at + (phys, off)]
            row[...] = 0
            row[..., :D] = np.asarray(q)
            row = want[key + "_scale"][at + (phys, off)]
            row[...] = 0
            row[:H] = np.asarray(sc)
        want["ppos"][at + (phys, off)] = new_pos[t]
    kept = np.arange(P - 1)                 # every page but the dump page
    for key, a in out.items():
        np.testing.assert_array_equal(np.take(np.asarray(a), kept, len(at)),
                                      np.take(want[key], kept, len(at)),
                                      err_msg=key)


def test_copy_pages_carries_scales(rng):
    """A COW clone must copy scale rows with the int8 codes — otherwise
    the private tail page dequantizes with the wrong magnitudes."""
    P, page, H, D = 4, 4, 2, 8
    pool = _int8_pool(P, page, H, D)
    k = jnp.asarray(rng.normal(size=(1, page, H, D)) * 5.0, jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, page, H, D)), jnp.float32)
    bt = jnp.asarray([[0]], jnp.int32)
    cache_pos = jnp.asarray([[4, 5, 6, 7]], jnp.int32)
    pool = KV.paged_write_prefill(pool, {"k": k, "v": v}, cache_pos, bt,
                                  ring_len=page)
    out = KV.copy_pages(pool, jnp.asarray([0]), jnp.asarray([2]),
                        jnp.asarray([6]))
    np.testing.assert_array_equal(np.asarray(out["ppos"][2]),
                                  [4, 5, -1, -1])
    for key in ("pk", "pv", "pk_scale", "pv_scale"):
        np.testing.assert_array_equal(np.asarray(out[key][2]),
                                      np.asarray(pool[key][0]))
        # source page untouched (copy, not move)
        np.testing.assert_array_equal(np.asarray(out[key][0]),
                                      np.asarray(pool[key][0]))
    # scan-repeats layout variant
    pool_r = {kk_: jnp.tile(vv_[None], (3,) + (1,) * vv_.ndim)
              for kk_, vv_ in pool.items()}
    out_r = KV.copy_pages(pool_r, jnp.asarray([0]), jnp.asarray([2]),
                          jnp.asarray([6]))
    for key in ("pk_scale", "pv_scale"):
        np.testing.assert_array_equal(np.asarray(out_r[key][:, 2]),
                                      np.asarray(pool_r[key][:, 0]))


# ---------------------------------------------------------------------------
# Fused-dequant Pallas kernel vs the fp32 oracle (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,P,page,npages,Hq,Hkv,D,Dv,window,cap",
    [
        (2, 9, 16, 4, 4, 4, 64, 64, None, None),      # MHA
        (3, 13, 32, 3, 8, 2, 64, 64, None, None),     # GQA 4:1
        (2, 9, 16, 4, 16, 4, 128, 128, 24, None),     # GQA + window
        (2, 9, 16, 4, 4, 2, 64, 64, None, 50.0),      # softcap (gemma2)
        (1, 7, 16, 4, 6, 2, 32, 32, 20, 30.0),        # window + cap
    ])
def test_paged_decode_q8_kernel_vs_oracle(rng, B, P, page, npages, Hq, Hkv,
                                          D, Dv, window, cap):
    """int8 pools with random block tables / holes / per-slot context
    lengths: the fused-dequant kernel must match the dense-gather
    dequantizing oracle to fp32 online-softmax tolerance."""
    kq, ks = KV.quantize_kv(
        jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32))
    vq, vs = KV.quantize_kv(
        jnp.asarray(rng.normal(size=(P, page, Hkv, Dv)), jnp.float32))
    ppos = np.full((P, page), -1, np.int32)
    bt = np.full((B, npages), -1, np.int32)
    perm = rng.permutation(P - 1)           # page P-1 stays the dump page
    q_pos = np.zeros((B, 1), np.int32)
    next_page = 0
    for b in range(B):
        ctx = int(rng.integers(1, npages * page))
        q_pos[b, 0] = ctx - 1
        used = -(-ctx // page)
        bt[b, :used] = perm[next_page:next_page + used]
        next_page += used
        for t in range(ctx):
            ppos[bt[b, t // page], t % page] = t
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    assert DA.paged_shape_supported(q, kq, jnp.asarray(bt))
    out = DA.paged_decode_attention_q8(
        q, kq, ks, vq, vs, jnp.asarray(ppos), jnp.asarray(bt),
        jnp.asarray(q_pos), window=window, scale=D ** -0.5,
        attn_softcap=cap, interpret=True)
    ref = R.paged_decode_attention_ref(
        q, kq, vq, jnp.asarray(ppos), jnp.asarray(bt), jnp.asarray(q_pos),
        window=window, scale=D ** -0.5, attn_softcap=cap,
        k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_q8_kernel_dispatches_through_model(rng):
    """serve_continuous on an int8 pool with kernel mode on: the fused
    int8 kernel path must produce the same greedy outputs as the jnp
    dequant-gather fallback."""
    from repro.kernels import ops as KOPS
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(uid=i, tokens=[2] + list(map(int, rng.integers(
        4, 400, size=ln))), max_new_tokens=mn)
        for i, (ln, mn) in enumerate([(5, 4), (9, 4), (14, 4)])]
    eng = InferenceEngine(cfg, params, policy=INT8, max_len=64, max_batch=3)
    base, _ = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   steps_per_sync=2, prefix_cache=False)
    eng2 = InferenceEngine(cfg, params, policy=INT8, max_len=64, max_batch=3)
    with KOPS.kernel_mode_ctx("interpret"):
        done, _ = eng2.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                        steps_per_sync=2,
                                        prefix_cache=False)
    for a, b in zip(base, done):
        assert a.result == b.result


# ---------------------------------------------------------------------------
# Serving: shared-prefix int8 == unshared int8, bit-exact
# ---------------------------------------------------------------------------


def test_int8_shared_prefix_bit_identical_to_unshared(rng):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefix = list(map(int, rng.integers(4, 400, size=21)))
    reqs = []
    for i, (ln, mn) in enumerate([(5, 5), (3, 4), (7, 5), (4, 4), (6, 5)]):
        body = list(map(int, rng.integers(4, 400, size=ln)))
        reqs.append(Request(uid=i, tokens=[2] + prefix + body,
                            max_new_tokens=mn))
    eng_off = InferenceEngine(cfg, params, policy=INT8, max_len=64,
                              max_batch=2)
    off, m_off = eng_off.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                          steps_per_sync=3,
                                          prefix_cache=False)
    eng_on = InferenceEngine(cfg, params, policy=INT8, max_len=64,
                             max_batch=2)
    on, m_on = eng_on.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                       steps_per_sync=3, prefix_cache=True)
    for a, b in zip(off, on):
        assert a.result == b.result, f"uid {a.uid}"
        assert a.result            # non-empty: the pool actually decoded
    assert m_on.prefix_matched_tokens > 0 and m_on.pages_shared > 0
    assert m_on.cow_copies > 0          # partial tail pages were COW'd
    assert m_off.kv_dtype == "int8"
    # int8 pool reports fewer bytes per token than the fp32 pool
    eng_fp = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                             max_batch=2)
    _, m_fp = eng_fp.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                      prefix_cache=False)
    assert m_off.kv_bytes_per_token < 0.5 * m_fp.kv_bytes_per_token
    assert 0 < m_off.kv_pool_bytes < m_fp.kv_pool_bytes
    assert m_off.peak_pages_in_use > 0


@pytest.mark.parametrize("policy", [FP32, INT8], ids=["fp", "q8"])
def test_pool_widened_to_lanes_serves_the_same_tokens(rng, monkeypatch,
                                                      policy):
    """A pool whose rows are widened to whole lanes, as the engine makes
    it on a TPU (``kv_cache.pool_lanes``), serves the tokens of the
    unwidened pool, through the paged kernels and through the gather
    fallback, prefix sharing and copy-on-write tails included."""
    from repro.kernels import ops as KOPS
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefix = list(map(int, rng.integers(4, 400, size=13)))
    reqs = [Request(uid=i, tokens=[2] + prefix + list(map(int, rng.integers(
        4, 400, size=ln))), max_new_tokens=4)
        for i, ln in enumerate([3, 6, 2])]

    def serve(mode):
        eng = InferenceEngine(cfg, params, policy=policy, max_len=64,
                              max_batch=2)
        with KOPS.kernel_mode_ctx(mode):
            done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                           steps_per_sync=2)
        return [r.result for r in done], m

    base, _ = serve("off")
    monkeypatch.setattr(KV, "pool_lanes", lambda device: 128)
    for mode in ("off", "interpret"):
        got, m = serve(mode)
        assert got == base, mode
        assert m.cow_copies > 0
    assert all(base)


def test_int8_serving_stays_close_to_fp(rng):
    """Quantization noise must not derail generation: int8 greedy outputs
    agree with the fp32 path on a small smoke trace (observed logit
    perturbations are ~1e-2 at unit-variance K/V; see README)."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(uid=i, tokens=[2] + list(map(int, rng.integers(
        4, 400, size=ln))), max_new_tokens=mn)
        for i, (ln, mn) in enumerate([(6, 4), (12, 4)])]
    fp, _ = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                            max_batch=2).serve_continuous(
        copy.deepcopy(reqs), page_size=8, prefix_cache=False)
    q8, _ = InferenceEngine(cfg, params, policy=INT8, max_len=64,
                            max_batch=2).serve_continuous(
        copy.deepcopy(reqs), page_size=8, prefix_cache=False)
    match = sum(a.result == b.result for a, b in zip(fp, q8))
    assert match == len(reqs)


# ---------------------------------------------------------------------------
# Metrics guards
# ---------------------------------------------------------------------------


def test_serve_metrics_zero_token_guards():
    m = ServeMetrics()
    assert m.prefill_pad_frac == 0.0
    assert m.decode_idle_frac == 0.0
    assert m.prefix_hit_rate == 0.0
    assert m.percentile_latency(50) == 0.0
    assert m.kv_pool_bytes == 0 and m.kv_bytes_per_token == 0.0
