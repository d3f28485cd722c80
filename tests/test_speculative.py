"""Speculative decoding subsystem (PR-4 tentpole).

Contracts under test:
  * top_p (nucleus) sampling filters exactly like a sorted-cumsum
    numpy reference, and the drawn distribution matches the renormalized
    nucleus;
  * the rejection sampler is distribution preserving (empirically: the
    combined accept-or-resample output of a drafted position is the
    target distribution), and is exact-match greedy at temperature 0;
  * drafters: n-gram prompt lookup proposes the continuation of the most
    recent match; the draft-model drafter proposes its own greedy
    continuation;
  * multi-token paged write + truncate: rollback across a page boundary,
    on int8 pools (stale codes/scales unreachable), never wraps a ring,
    and PageAllocator invariants hold after randomized accept/reject
    serving (hypothesis + seeded fallback);
  * the multi-query paged verify Pallas kernel matches the ref.py oracle
    (fp and int8, GQA, window, softcap), and forward_verify is
    bit-identical to sequential forward_decode;
  * serve_continuous with speculation (both drafters) emits bit-identical
    greedy streams vs non-speculative serving — with prefix sharing on
    and off, with kv_dtype=int8, across EOS/budget edges — and the
    ServeMetrics speculative counters behave (zero guards included).
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("spec", deadline=None, max_examples=15)
    settings.load_profile("spec")
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.configs.registry import get_reduced
from repro.core import kv_cache as KV
from repro.core import sampling as SMP
from repro.core import speculative as SPEC
from repro.core.continuous import ServeMetrics
from repro.core.engine import InferenceEngine
from repro.core.precision import FP32
from repro.core.sampling import SamplingParams
from repro.core.scheduler import Request
from repro.core.tokenizer import EOS
from repro.kernels import decode_attention as DA
from repro.kernels import ops as KOPS
from repro.kernels import ref as R
from repro.models import transformer as T

INT8 = dataclasses.replace(FP32, kv_dtype="int8")


# ---------------------------------------------------------------------------
# top_p (nucleus) sampling
# ---------------------------------------------------------------------------


def _nucleus_reference(logits, top_p):
    """Independent numpy nucleus filter: smallest top set reaching
    top_p (the crossing token included)."""
    order = np.argsort(-logits)
    probs = np.exp(logits - logits.max())
    probs /= probs.sum()
    cum = np.cumsum(probs[order])
    cut = int(np.searchsorted(cum, top_p) + 1)       # include the crosser
    return set(order[:cut].tolist())


@pytest.mark.parametrize("top_p", [0.1, 0.5, 0.9])
def test_top_p_filters_to_nucleus(rng, top_p):
    logits = rng.normal(size=(16,)).astype(np.float32) * 3.0
    keep = _nucleus_reference(logits, top_p)
    sp = SamplingParams(temperature=1.0, top_p=top_p)
    keys = jax.random.split(jax.random.PRNGKey(0), 64)
    draws = {int(SMP.sample(jnp.asarray(logits)[None], k_, sp)[0])
             for k_ in keys}
    assert draws <= keep
    # filtered probs match the renormalized nucleus exactly
    p = np.asarray(SMP.target_probs(jnp.asarray(logits), sp))
    probs = np.exp(logits - logits.max())
    probs /= probs.sum()
    want = np.where([i in keep for i in range(16)], probs, 0.0)
    want /= want.sum()
    np.testing.assert_allclose(p, want, rtol=1e-5, atol=1e-6)


def test_top_p_one_is_identity_and_combines_with_top_k(rng):
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    sp_full = SamplingParams(temperature=0.7)
    p = np.asarray(SMP.target_probs(logits, sp_full))
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-5)
    sp_both = SamplingParams(temperature=0.7, top_k=8, top_p=0.6)
    pb = np.asarray(SMP.target_probs(logits, sp_both))
    assert ((pb > 0).sum(-1) <= 8).all()
    np.testing.assert_allclose(pb.sum(-1), 1.0, rtol=1e-5)


def test_top_p_always_keeps_argmax():
    logits = jnp.asarray([[10.0, 0.0, -1.0, -2.0]])
    sp = SamplingParams(temperature=1.0, top_p=0.01)
    p = np.asarray(SMP.target_probs(logits, sp))[0]
    assert p.argmax() == 0 and p[0] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Rejection sampler
# ---------------------------------------------------------------------------


def test_speculative_verify_greedy_exact_match(rng):
    B, K, V = 4, 3, 12
    logits = jnp.asarray(rng.normal(size=(B, K + 1, V)), jnp.float32)
    pred = np.asarray(jnp.argmax(logits, -1))
    drafts = pred[:, :K].copy()
    drafts[1, 1] = (drafts[1, 1] + 1) % V        # mismatch at j=1
    drafts[2, 0] = (drafts[2, 0] + 1) % V        # mismatch at j=0
    a, nxt = SMP.speculative_verify(logits, jnp.asarray(drafts),
                                    jax.random.PRNGKey(0), SamplingParams())
    np.testing.assert_array_equal(np.asarray(a), [K, 1, 0, K])
    np.testing.assert_array_equal(np.asarray(nxt),
                                  pred[np.arange(B), np.asarray(a)])


def test_speculative_verify_distribution_preserving(rng):
    """P(emitted token at a drafted position) must equal the target
    distribution regardless of what was drafted: accept d w.p. p(d),
    else resample from p with d removed — the mixture is exactly p."""
    V, B = 6, 8000
    row = np.log(np.asarray([0.35, 0.25, 0.2, 0.1, 0.07, 0.03], np.float32))
    sp = SamplingParams(temperature=1.0)
    logits = jnp.broadcast_to(jnp.asarray(row), (B, 2, V))
    for d in (0, 3, 5):                      # well-, mid- and badly-drafted
        drafts = jnp.full((B, 1), d, jnp.int32)
        a, nxt = SMP.speculative_verify(logits, drafts,
                                        jax.random.PRNGKey(d), sp)
        a, nxt = np.asarray(a), np.asarray(nxt)
        emitted = np.where(a == 1, d, nxt)   # the token at position 0
        freq = np.bincount(emitted, minlength=V) / B
        np.testing.assert_allclose(freq, np.exp(row), atol=0.02)
        # acceptance rate itself is p(d)
        assert abs(a.mean() - np.exp(row[d])) < 0.02


def test_speculative_verify_temperature_zero_equals_greedy(rng):
    B, K, V = 3, 2, 9
    logits = jnp.asarray(rng.normal(size=(B, K + 1, V)), jnp.float32)
    drafts = jnp.asarray(rng.integers(0, V, size=(B, K)), jnp.int32)
    a0, n0 = SMP.speculative_verify(logits, drafts, jax.random.PRNGKey(1),
                                    SamplingParams(temperature=0.0))
    pred = np.asarray(jnp.argmax(logits, -1))
    ok = pred[:, :K] == np.asarray(drafts)
    want_a = np.cumprod(ok, 1).sum(1)
    np.testing.assert_array_equal(np.asarray(a0), want_a)
    np.testing.assert_array_equal(np.asarray(n0),
                                  pred[np.arange(B), want_a])


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = SPEC.NgramDrafter(k=3, max_ngram=3)
    # context repeats "7 8 9 10" — trailing [8, 9] matched earlier,
    # propose what followed: [10, 5, 6]
    ctx = [5, 6, 7, 8, 9, 10, 5, 6, 7, 8, 9]
    assert d.propose(ctx) == [10, 5, 6]
    # no match anywhere: repeat the last token
    assert d.propose([1, 2, 3]) == [3, 3, 3]
    # match with a short continuation pads by repeating its last token
    assert d.propose([4, 9, 4, 9])[0] == 4


def test_ngram_drafter_slots_mask_inactive():
    d = SPEC.NgramDrafter(k=2)
    out = d.propose_slots([None, [1, 2, 1, 2], None])
    assert out.shape == (3, 2)
    assert (out[0] == 0).all() and (out[2] == 0).all()
    assert out[1].tolist() == [1, 2]


def test_draft_model_drafter_matches_own_greedy(rng):
    """Self-drafting proposes exactly the model's own greedy
    continuation (which is why self-draft verify accepts everything)."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    ctx = [2] + list(map(int, rng.integers(4, 400, size=7)))
    gen = eng.generate_batch(np.asarray([ctx], np.int32),
                             np.asarray([len(ctx)], np.int32), 3,
                             stop_at_eos=False)
    d = SPEC.DraftModelDrafter(cfg, params, k=3, policy=FP32)
    prop = d.propose(ctx)
    assert prop == [int(t) for t in gen[0]]
    # batched slot drafting agrees with per-context drafting
    ctx2 = [2] + list(map(int, rng.integers(4, 400, size=12)))
    both = d.propose_slots([ctx, None, ctx2])
    assert both[0].tolist() == prop
    assert (both[1] == 0).all()
    assert both[2].tolist() == d.propose(ctx2)


def test_get_drafter_resolution():
    spec = SPEC.SpecConfig(k=2, drafter="ngram", max_ngram=4)
    d = SPEC.get_drafter(spec)
    assert isinstance(d, SPEC.NgramDrafter) and d.max_ngram == 4
    with pytest.raises(ValueError):
        SPEC.get_drafter(SPEC.SpecConfig(drafter="draft_model"))
    with pytest.raises(ValueError):
        SPEC.get_drafter(SPEC.SpecConfig(drafter="wat"))


# ---------------------------------------------------------------------------
# Multi-token paged write + truncate (rollback)
# ---------------------------------------------------------------------------


def _pool(P, page, H, D, int8=False):
    if int8:
        return {"pk": jnp.zeros((P, page, H, D), jnp.int8),
                "pv": jnp.zeros((P, page, H, D), jnp.int8),
                "pk_scale": jnp.zeros((P, page, H), jnp.float32),
                "pv_scale": jnp.zeros((P, page, H), jnp.float32),
                "ppos": jnp.full((P, page), -1, jnp.int32)}
    return {"pk": jnp.zeros((P, page, H, D)),
            "pv": jnp.zeros((P, page, H, D)),
            "ppos": jnp.full((P, page), -1, jnp.int32)}


@pytest.mark.parametrize("int8", [False, True])
def test_multi_write_truncate_across_page_boundary(rng, int8):
    """Write a K+1 window straddling a page boundary, roll back to an
    accepted prefix, and check the gather sees exactly the accepted
    tokens (int8: stale codes/scales unreachable, live ones within the
    quantization bound)."""
    P, page, H, D = 6, 8, 2, 16
    pool = _pool(P, page, H, D, int8)
    bt = jnp.asarray([[0, 3, -1, -1]], jnp.int32)
    ring = KV.paged_ring_len(None, page, 4)
    k = jnp.asarray(rng.normal(size=(1, 4, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 4, H, D)), jnp.float32)
    # window at positions 6..9 crosses the page-0/page-1 boundary
    pool = KV.paged_write_decode_multi(pool, {"k": k, "v": v},
                                       jnp.asarray([6], jnp.int32), bt,
                                       jnp.asarray([True]), ring_len=ring)
    _, _, kp = KV.paged_gather(pool, bt)
    assert set(np.asarray(kp[0])[np.asarray(kp[0]) >= 0]) == {6, 7, 8, 9}
    # accept 1 draft: keep positions < 8 (pending@6 + draft@7)
    pool = KV.paged_truncate(pool, bt, jnp.asarray([8], jnp.int32))
    kk, vv, kp = KV.paged_gather(pool, bt)
    live = np.asarray(kp[0])
    assert set(live[live >= 0]) == {6, 7}
    got_k = np.asarray(kk[0])[live >= 0]
    want_k = np.asarray(k[0, :2])
    if int8:
        bound = np.abs(want_k).max(-1, keepdims=True) / 254.0
        assert (np.abs(got_k - want_k) <= bound + 1e-7).all()
    else:
        np.testing.assert_allclose(got_k, want_k, rtol=1e-6)
    # the rewound entries' codes are unreachable: rewriting those
    # positions with new values fully defines what a later gather sees
    k2, v2 = k + 5.0, v - 5.0
    pool = KV.paged_write_decode_multi(pool, {"k": k2, "v": v2},
                                       jnp.asarray([8], jnp.int32), bt,
                                       jnp.asarray([True]), ring_len=ring)
    kk, _, kp = KV.paged_gather(pool, bt)
    live = np.asarray(kp[0])
    assert set(live[live >= 0]) == {6, 7, 8, 9, 10, 11}


def test_multi_write_respects_active_and_allocation(rng):
    P, page, H, D = 5, 8, 1, 8
    pool = _pool(P, page, H, D)
    bt = jnp.asarray([[0, -1, -1], [1, -1, -1]], jnp.int32)
    ring = KV.paged_ring_len(None, page, 3)
    k = jnp.asarray(rng.normal(size=(2, 3, H, D)), jnp.float32)
    # slot 0 inactive -> dump; slot 1 window runs past its single
    # allocated page -> overflow entries dump, no wrap
    pool = KV.paged_write_decode_multi(
        pool, {"k": k, "v": k}, jnp.asarray([2, 6], jnp.int32), bt,
        jnp.asarray([False, True]), ring_len=ring)
    assert int(pool["ppos"][0].max()) == -1          # inactive: untouched
    assert int(pool["ppos"][P - 1].max()) == -1      # dump stays empty
    live = np.asarray(pool["ppos"][1])
    assert set(live[live >= 0]) == {6, 7}            # 8 fell off page 0
    # beyond ring_len is dumped, never wrapped onto early pages
    pool2 = KV.paged_write_decode_multi(
        pool, {"k": k, "v": k}, jnp.asarray([22, 22], jnp.int32),
        jnp.asarray([[0, 2, 3], [1, 2, 3]], jnp.int32),
        None, ring_len=ring)
    for p in range(P - 1):
        live = np.asarray(pool2["ppos"][p])
        assert not ((live >= 0) & (live < 6)).any()


def test_truncate_scan_repeats_layout_and_shared_rows(rng):
    """The (R, P, page) scan-stacked layout truncates correctly, and a
    page mapped by two slots (shared prefix) survives both rows'
    write-backs."""
    P, page, R = 7, 4, 3
    ppos = np.full((R, P, page), -1, np.int32)
    ppos[:, 2] = [0, 1, 2, 3]                 # shared prefix page
    ppos[:, 0, :3] = [4, 5, 6]                # slot 0 tail
    ppos[:, 4, :2] = [4, 5]                   # slot 1 tail
    pool = {"pk": jnp.zeros((R, P, page, 1, 8)),
            "pv": jnp.zeros((R, P, page, 1, 8)),
            "ppos": jnp.asarray(ppos)}
    bt = jnp.asarray([[2, 0, -1], [2, 4, -1]], jnp.int32)
    out = KV.paged_truncate(pool, bt, jnp.asarray([6, 5], jnp.int32))
    got = np.asarray(out["ppos"])
    for r in range(R):
        assert got[r, 2].tolist() == [0, 1, 2, 3]         # shared intact
        assert got[r, 0].tolist() == [4, 5, -1, -1]       # 6 rewound
        assert got[r, 4].tolist() == [4, -1, -1, -1]      # 5 rewound
        assert got[r, P - 1].tolist() == [-1] * page      # dump intact


class _RandomDrafter(SPEC.Drafter):
    """Adversarial drafter: random tokens (mostly rejected) with
    occasional EOS proposals — exercises rollback, EOS-in-window and
    budget edges."""

    name = "random"

    def __init__(self, k, seed=0):
        super().__init__(k)
        self.rng = np.random.default_rng(seed)

    def propose(self, context):
        out = self.rng.integers(4, 400, size=self.k)
        if self.rng.random() < 0.15:
            out[self.rng.integers(0, self.k)] = EOS
        return [int(t) for t in out]


def _spec_invariant_trial(seed: int, k: int):
    """Serve a random trace with an adversarial drafter; the engine's
    own end-of-serve audit (allocator.check() + trie residency) plus
    greedy parity vs the non-speculative run make up the invariant."""
    rng = np.random.default_rng(seed)
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(uid=i,
                    tokens=[2] + list(map(int, rng.integers(4, 400,
                                                            size=ln))),
                    max_new_tokens=mn)
            for i, (ln, mn) in enumerate(
                zip(rng.integers(2, 18, size=5), rng.integers(1, 8, size=5)))]
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    base, _ = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   prefix_cache=True)
    eng2 = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                           max_batch=2)
    import repro.core.engine as E
    orig = E.get_drafter
    E.get_drafter = lambda spec, *_a, **_k: _RandomDrafter(spec.k,
                                                           seed=seed)
    try:
        done, m = eng2.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                        spec=SPEC.SpecConfig(k=k),
                                        prefix_cache=True)
    finally:
        E.get_drafter = orig
    for a, b in zip(base, done):
        assert a.result == b.result, f"seed {seed} uid {a.uid}"
    assert m.drafted_tokens >= m.accepted_tokens >= 0


SEED_TRIALS = [(0, 2), (1, 3), (2, 4)]


@pytest.mark.parametrize("seed,k", SEED_TRIALS)
def test_spec_rollback_invariants_seeded(seed, k):
    _spec_invariant_trial(seed, k)


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 10_000), st.integers(1, 4))
    @settings(max_examples=8, deadline=None)
    def test_spec_rollback_invariants_hypothesis(seed, k):
        _spec_invariant_trial(seed, k)


# ---------------------------------------------------------------------------
# Multi-query verify kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------


def _random_paged_state(rng, B, P, page, npages, Hkv, D, Dv, K1):
    ppos = np.full((P, page), -1, np.int32)
    bt = np.full((B, npages), -1, np.int32)
    q_pos = np.zeros((B, K1), np.int32)
    perm = rng.permutation(P - 1)
    nxt = 0
    for b in range(B):
        ctx = int(rng.integers(K1, npages * page))
        q_pos[b] = ctx - K1 + np.arange(K1)
        used = -(-ctx // page)
        bt[b, :used] = perm[nxt:nxt + used]
        nxt += used
        for t in range(ctx):
            ppos[bt[b, t // page], t % page] = t
    return ppos, bt, q_pos


@pytest.mark.parametrize(
    "B,P,page,npages,Hq,Hkv,D,Dv,K1,window,cap",
    [
        (2, 9, 16, 4, 4, 4, 64, 64, 4, None, None),     # MHA
        (3, 13, 32, 3, 8, 2, 64, 64, 3, None, None),    # GQA 4:1
        (2, 9, 16, 4, 16, 4, 128, 128, 2, 24, None),    # GQA + window
        (2, 9, 16, 4, 4, 2, 64, 64, 5, None, 50.0),     # softcap
        (1, 7, 16, 4, 6, 2, 32, 32, 1, 20, 30.0),       # K1=1 degenerate
    ])
def test_paged_verify_kernel_vs_oracle(rng, B, P, page, npages, Hq, Hkv,
                                       D, Dv, K1, window, cap):
    ppos, bt, q_pos = _random_paged_state(rng, B, P, page, npages, Hkv, D,
                                          Dv, K1)
    kpool = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
    vpool = jnp.asarray(rng.normal(size=(P, page, Hkv, Dv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, K1, Hq, D)), jnp.float32)
    assert DA.paged_verify_shape_supported(q, kpool, jnp.asarray(bt))
    out = DA.paged_verify_attention(
        q, kpool, vpool, jnp.asarray(ppos), jnp.asarray(bt),
        jnp.asarray(q_pos), window=window, scale=D ** -0.5,
        attn_softcap=cap, interpret=True)
    ref = R.paged_verify_attention_ref(
        q, kpool, vpool, jnp.asarray(ppos), jnp.asarray(bt),
        jnp.asarray(q_pos), window=window, scale=D ** -0.5,
        attn_softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "B,P,page,npages,Hq,Hkv,D,K1,window,cap",
    [
        (2, 9, 16, 4, 4, 4, 64, 4, None, None),
        (2, 9, 16, 3, 8, 2, 64, 3, 24, 50.0),
    ])
def test_paged_verify_q8_kernel_vs_oracle(rng, B, P, page, npages, Hq, Hkv,
                                          D, K1, window, cap):
    ppos, bt, q_pos = _random_paged_state(rng, B, P, page, npages, Hkv, D,
                                          D, K1)
    kq, ks = KV.quantize_kv(
        jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32))
    vq, vs = KV.quantize_kv(
        jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32))
    q = jnp.asarray(rng.normal(size=(B, K1, Hq, D)), jnp.float32)
    out = DA.paged_verify_attention_q8(
        q, kq, ks, vq, vs, jnp.asarray(ppos), jnp.asarray(bt),
        jnp.asarray(q_pos), window=window, scale=D ** -0.5,
        attn_softcap=cap, interpret=True)
    ref = R.paged_verify_attention_ref(
        q, kq, vq, jnp.asarray(ppos), jnp.asarray(bt), jnp.asarray(q_pos),
        window=window, scale=D ** -0.5, attn_softcap=cap,
        k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# forward_verify vs sequential decode
# ---------------------------------------------------------------------------


def _paged_two_slots(cfg, params, rng, max_len=64, page_size=8):
    slots = 2
    pages_per_slot = max_len // page_size
    num_pages = slots * pages_per_slot
    cache = T.init_paged_cache(cfg, num_pages=num_pages,
                               page_size=page_size, max_slots=slots,
                               max_len=max_len, dtype=jnp.float32)
    bt = np.full((slots, pages_per_slot), -1, np.int32)
    bt[0] = np.arange(pages_per_slot)
    bt[1] = np.arange(pages_per_slot, 2 * pages_per_slot)
    lens = np.asarray([6, 9], np.int32)
    S = int(lens.max())
    prompt = np.zeros((slots, S), np.int32)
    for b in range(slots):
        prompt[b, :lens[b]] = [2] + list(rng.integers(4, 400,
                                                      size=lens[b] - 1))
    view = KV.slot_view(cache, slots)
    paged = {"block_tables": jnp.asarray(bt),
             "active": jnp.ones((slots,), bool)}
    _, view, _ = T.forward_prefill(params, cfg, jnp.asarray(prompt),
                                jnp.asarray(lens), view, policy=FP32,
                                max_len=max_len, last_only=True,
                                paged=paged)
    cache = KV.slot_merge(cache, view,
                          jnp.asarray(np.arange(slots), np.int32))
    return cache, paged, lens


def test_forward_verify_matches_sequential_decode(rng):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache, paged, lens = _paged_two_slots(cfg, params, rng)
    K1 = 4
    toks = np.asarray(rng.integers(4, 400, size=(2, K1)), np.int32)
    seq = []
    c1 = cache
    for j in range(K1):
        lg, c1, _ = T.forward_decode(
            params, cfg, jnp.asarray(toks[:, j:j + 1]), c1,
            jnp.asarray(lens + j), policy=FP32, max_len=64, paged=paged)
        seq.append(np.asarray(lg[:, 0]))
    seq = np.stack(seq, axis=1)
    vl, c2, _ = T.forward_verify(params, cfg, jnp.asarray(toks), cache,
                              jnp.asarray(lens), policy=FP32, max_len=64,
                              paged=paged)
    np.testing.assert_array_equal(np.asarray(vl), seq)
    # the verify write leaves the same cache positions as the sequence
    for sc1, sc2 in zip(c1["layers"], c2["layers"]):
        for a, b in zip(sc1, sc2):
            np.testing.assert_array_equal(np.asarray(a["ppos"]),
                                          np.asarray(b["ppos"]))


def test_forward_verify_kernel_interpret_matches_fallback():
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache, paged, lens = _paged_two_slots(cfg, params,
                                          np.random.default_rng(5))
    toks = np.asarray(np.random.default_rng(6).integers(4, 400,
                                                        size=(2, 3)),
                      np.int32)
    base, _, _ = T.forward_verify(params, cfg, jnp.asarray(toks), cache,
                               jnp.asarray(lens), policy=FP32, max_len=64,
                               paged=paged)
    with KOPS.kernel_mode_ctx("interpret"):
        cache3, paged3, lens3 = _paged_two_slots(cfg, params,
                                                 np.random.default_rng(5))
        kout, _, _ = T.forward_verify(params, cfg, jnp.asarray(toks), cache3,
                                   jnp.asarray(lens3), policy=FP32,
                                   max_len=64, paged=paged3)
    np.testing.assert_allclose(np.asarray(kout), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def test_forward_verify_rejects_dense_and_recurrent():
    cfg = get_reduced("xlstm-125m")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    cache = T.init_cache(cfg, 1, 32, jnp.float32)
    with pytest.raises(NotImplementedError):
        T.forward_verify(params, cfg, jnp.zeros((1, 3), jnp.int32), cache,
                         jnp.asarray([4], jnp.int32), policy=FP32,
                         max_len=32)


# ---------------------------------------------------------------------------
# End-to-end speculative serving
# ---------------------------------------------------------------------------


def _requests(rng, lens_new):
    return [Request(uid=i,
                    tokens=[2] + list(map(int, rng.integers(4, 400,
                                                            size=ln))),
                    max_new_tokens=mn)
            for i, (ln, mn) in enumerate(lens_new)]


def _reference(cfg, params, reqs, policy=FP32):
    eng = InferenceEngine(cfg, params, policy=policy, max_len=64,
                          max_batch=2)
    out = {}
    for r in reqs:
        g = eng.generate_batch(np.asarray([r.tokens], np.int32),
                               np.asarray([len(r.tokens)], np.int32),
                               r.max_new_tokens)
        row = g[0]
        out[r.uid] = [int(t) for t in row[row >= 0]]
    return out


@pytest.mark.parametrize("drafter,prefix", [
    ("ngram", False), ("ngram", True), ("draft_model", True)])
def test_spec_serving_greedy_parity(rng, drafter, prefix):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(5, 8), (11, 6), (3, 9), (20, 5)])
    ref = _reference(cfg, params, reqs)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    done, m = eng.serve_continuous(
        copy.deepcopy(reqs), page_size=8,
        spec=SPEC.SpecConfig(k=3, drafter=drafter), prefix_cache=prefix)
    for r in done:
        assert r.result == ref[r.uid], f"uid {r.uid}"
    assert m.spec_mode == drafter and m.spec_k == 3
    assert m.drafted_tokens > 0
    if drafter == "draft_model":          # self-draft: greedy is accepted
        assert m.acceptance_rate > 0.5
        assert m.tokens_per_forward > 1.5


def test_spec_serving_int8_parity(rng):
    """Speculative + int8 pools + prefix sharing: bit-identical to the
    non-speculative int8 run (scale pools rewound with the codes)."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefix = list(map(int, np.random.default_rng(7).integers(4, 400,
                                                             size=17)))
    reqs = []
    for i, (ln, mn) in enumerate([(5, 6), (3, 5), (7, 6), (4, 5)]):
        body = list(map(int, rng.integers(4, 400, size=ln)))
        reqs.append(Request(uid=i, tokens=[2] + prefix + body,
                            max_new_tokens=mn))
    eng = InferenceEngine(cfg, params, policy=INT8, max_len=64, max_batch=2)
    base, _ = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   prefix_cache=True)
    eng2 = InferenceEngine(cfg, params, policy=INT8, max_len=64,
                           max_batch=2)
    done, m = eng2.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                    spec=SPEC.SpecConfig(k=3,
                                                         drafter="ngram"),
                                    prefix_cache=True)
    for a, b in zip(base, done):
        assert a.result == b.result, f"uid {a.uid}"
    assert m.kv_dtype == "int8" and m.prefix_matched_tokens > 0


def test_spec_serving_kernel_interpret(rng):
    """The multi-query verify kernel in interpret mode serves the same
    greedy streams as the gather fallback."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(5, 5), (9, 5), (14, 4)])
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=3)
    base, _ = eng.serve_continuous(
        copy.deepcopy(reqs), page_size=8,
        spec=SPEC.SpecConfig(k=2, drafter="draft_model"),
        prefix_cache=False)
    eng2 = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                           max_batch=3)
    with KOPS.kernel_mode_ctx("interpret"):
        done, _ = eng2.serve_continuous(
            copy.deepcopy(reqs), page_size=8,
            spec=SPEC.SpecConfig(k=2, drafter="draft_model"),
            prefix_cache=False)
    for a, b in zip(base, done):
        assert a.result == b.result


def test_spec_serving_budget_edges(rng):
    """max_new of 0/1/2 with speculation: budgets never overshoot."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(5, 0), (5, 1), (5, 2), (6, 7)])
    ref = _reference(cfg, params, reqs)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    done, _ = eng.serve_continuous(
        copy.deepcopy(reqs), page_size=8,
        spec=SPEC.SpecConfig(k=3, drafter="draft_model"))
    for r in done:
        assert r.result == ref[r.uid], f"uid {r.uid}"
        assert len(r.result) <= r.max_new_tokens


def test_spec_serving_eos_in_window(rng, monkeypatch):
    """An accepted drafted EOS retires the request without emitting EOS
    or anything after it."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(5, 8), (9, 8)])
    ref = _reference(cfg, params, reqs)
    import repro.core.engine as E

    class EosDrafter(SPEC.Drafter):
        name = "eos"

        def propose(self, context):
            # propose the model's own continuation with EOS spliced in —
            # the verifier must cut at EOS iff the model agrees
            d = SPEC.DraftModelDrafter(cfg, params, self.k)
            out = d.propose(context)
            out[-1] = EOS
            return out

    monkeypatch.setattr(E, "get_drafter",
                        lambda spec, *a, **k: EosDrafter(spec.k))
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    done, _ = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   spec=SPEC.SpecConfig(k=3))
    for r in done:
        assert r.result == ref[r.uid]
        assert EOS not in r.result


def test_spec_disabled_for_unsupported_families(rng):
    """Windowed attention warns and serves non-speculatively (ring pages
    cannot be rolled back)."""
    cfg = get_reduced("gemma2-2b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(5, 4), (9, 4)])
    ref = _reference(cfg, params, reqs)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    with pytest.warns(UserWarning, match="speculative decoding"):
        done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                       spec=SPEC.SpecConfig(k=3))
    assert m.spec_mode == "off" and m.drafted_tokens == 0
    for r in done:
        assert r.result == ref[r.uid]


def test_spec_sampled_serving_valid(rng):
    """Sampled speculative serving emits valid tokens within budget
    (distribution preservation is tested at the sampler level)."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(5, 6), (9, 6), (3, 6)])
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2,
                          seed=11)
    done, m = eng.serve_continuous(
        reqs, SamplingParams(temperature=1.0, top_k=20, top_p=0.9),
        page_size=8, spec=SPEC.SpecConfig(k=2))
    for r in done:
        assert r.result is not None and len(r.result) <= 6
        assert all(0 <= t < cfg.vocab_size and t != EOS for t in r.result)
    assert m.drafted_tokens > 0


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_serve_metrics_spec_zero_guards():
    m = ServeMetrics()
    assert m.acceptance_rate == 0.0
    assert m.tokens_per_forward == 0.0
    assert m.prefill_pad_frac == 0.0
    assert m.decode_idle_frac == 0.0
    assert m.prefix_hit_rate == 0.0
    assert m.percentile_latency(50) == 0.0
    assert m.spec_mode == "off" and m.spec_k == 0


def test_spec_metrics_accounting(rng):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, [(6, 6), (10, 6)])
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    _, m = eng.serve_continuous(
        copy.deepcopy(reqs), page_size=8,
        spec=SPEC.SpecConfig(k=4, drafter="draft_model"))
    assert m.spec_k == 4
    assert 0 < m.accepted_tokens <= m.drafted_tokens
    assert m.decode_tokens + m.admitted == m.generated_tokens
    # self-draft accepts greedily: strictly more than one token per
    # live slot-forward
    assert m.tokens_per_forward > 1.0
