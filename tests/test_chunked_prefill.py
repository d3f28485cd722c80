"""Unified token-budget scheduler + chunked prefill.

Contracts under test:

  * greedy outputs are bit-identical between the unified scheduler
    (any chunk budget — page-aligned or not) and the bucketed
    whole-prompt engine, including int8 pools, shared prefixes and
    speculative decoding;
  * the per-iteration token budget is never exceeded, decode always
    rides first, and no admitting slot starves (FCFS chunk ordering);
  * the variable-length mixed paged-attention entry matches its oracle
    under ragged per-slot query counts, and padding queries (q_pos -1)
    come back as zeros;
  * TTFT / inter-token-latency percentiles are recorded, zero-guarded
    like the other derived metrics.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core.continuous import (ContinuousScheduler, PageAllocator,
                                   ServeMetrics)
from repro.core.engine import InferenceEngine
from repro.core.precision import FP32
from repro.core.scheduler import Request
from repro.kernels import decode_attention as DA
from repro.kernels import ops as KOPS
from repro.kernels import ref as R
from repro.models import transformer as T

INT8 = dataclasses.replace(FP32, kv_dtype="int8")


def _requests(rng, cfg, lens_new, prefix=None):
    prefix = prefix or []
    return [Request(uid=i,
                    tokens=[2] + prefix + list(map(int, rng.integers(
                        4, min(cfg.vocab_size, 400), size=ln))),
                    max_new_tokens=mn)
            for i, (ln, mn) in enumerate(lens_new)]


def _serve(eng, reqs, **kw):
    done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8, **kw)
    return {r.uid: r.result for r in done}, m


# ---------------------------------------------------------------------------
# Greedy parity: unified scheduler == bucketed whole-prompt engine
# ---------------------------------------------------------------------------


# chunk budgets: tiny (many chunks per prompt), large (one chunk), and
# unaligned-to-page (page_size=8; chunk boundaries fall mid-page)
@pytest.mark.parametrize("budget", [16, 64, 20])
def test_chunked_parity_sweep(rng, budget):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    shapes = [(30, 5), (40, 4), (9, 5), (22, 4), (3, 5)]
    reqs = _requests(rng, cfg, shapes)

    eng_off = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                              max_batch=3)
    base, m_off = _serve(eng_off, reqs, chunked_prefill=False)
    assert m_off.scheduler == "bucketed" and m_off.max_batched_tokens == 0

    eng_on = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                             max_batch=3)
    done, m_on = _serve(eng_on, reqs, max_batched_tokens=budget,
                        chunked_prefill=True)
    for uid, out in done.items():
        assert out == base[uid], f"budget {budget} uid {uid}"
    assert m_on.scheduler == "unified"
    assert m_on.max_batched_tokens == budget
    # every prompt token was either chunk-prefilled exactly once or
    # served from the (default-on) radix prefix cache
    assert m_on.prefill_tokens + m_on.prefix_matched_tokens \
        == sum(r.prompt_len for r in reqs)
    assert m_on.prefill_chunks >= len(reqs)
    if budget == 16:
        # 30- and 40-token prompts cannot fit one 16-token iteration
        assert m_on.prefill_chunks > len(reqs)


def test_chunked_parity_int8_pool(rng):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, cfg, [(26, 5), (11, 4), (33, 5)])
    base, _ = _serve(InferenceEngine(cfg, params, policy=INT8, max_len=64,
                                     max_batch=2),
                     reqs, chunked_prefill=False, prefix_cache=False)
    done, m = _serve(InferenceEngine(cfg, params, policy=INT8, max_len=64,
                                     max_batch=2),
                     reqs, max_batched_tokens=16, chunked_prefill=True,
                     prefix_cache=False)
    assert m.kv_dtype == "int8" and m.scheduler == "unified"
    for uid, out in done.items():
        assert out == base[uid]
        assert out                      # the quantized pool really decoded


def test_chunked_parity_shared_prefix(rng):
    """Chunked + radix sharing: chunks prefill only the unmatched
    suffix, COW still fires, and outputs stay bit-identical to both the
    unchunked run and the sharing-off chunked run."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefix = list(map(int, rng.integers(4, 400, size=21)))
    shapes = [(5, 5), (3, 4), (7, 5), (4, 4), (6, 5)]
    reqs = _requests(rng, cfg, shapes, prefix=prefix)

    base, _ = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2),
                     reqs, chunked_prefill=False, prefix_cache=True)
    unshared, _ = _serve(InferenceEngine(cfg, params, policy=FP32,
                                         max_len=64, max_batch=2),
                         reqs, max_batched_tokens=16, chunked_prefill=True,
                         prefix_cache=False)
    done, m = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2),
                     reqs, max_batched_tokens=16, chunked_prefill=True,
                     prefix_cache=True)
    for uid, out in done.items():
        assert out == base[uid] == unshared[uid]
    assert m.prefix_matched_tokens > 0 and m.pages_shared > 0
    assert m.cow_copies > 0
    # chunks covered exactly the unmatched suffixes
    total_prompt = sum(r.prompt_len for r in reqs)
    assert m.prefill_tokens + m.prefix_matched_tokens == total_prompt


def test_chunked_parity_speculative(rng):
    """Speculation composes with the unified scheduler: decode-only
    iterations run the k+1-token verify step, so the budget floor is
    slots * (k+1) (the largest iteration must fit); iterations carrying
    prefill chunks pause drafting and charge one decode token per
    slot.  Greedy streams stay bit-identical to every other mode."""
    from repro.core.speculative import SpecConfig
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, cfg, [(24, 6), (9, 6), (31, 5)])
    base, _ = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2),
                     reqs, chunked_prefill=False)
    done, m = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2),
                     reqs, max_batched_tokens=16, chunked_prefill=True,
                     spec=SpecConfig(k=3, drafter="ngram"))
    assert m.scheduler == "unified" and m.spec_mode == "ngram"
    assert m.drafted_tokens > 0
    for uid, out in done.items():
        assert out == base[uid]


def test_chunked_kernel_interpret_matches_fallback(rng):
    """The mixed Pallas kernel (interpret mode) must not change greedy
    outputs vs the gather + jnp fallback on the chunked path."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, cfg, [(19, 4), (27, 4)])
    base, _ = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2),
                     reqs, max_batched_tokens=16, chunked_prefill=True)
    with KOPS.kernel_mode_ctx("interpret"):
        done, _ = _serve(InferenceEngine(cfg, params, policy=FP32,
                                         max_len=64, max_batch=2),
                         reqs, max_batched_tokens=16, chunked_prefill=True)
    for uid, out in done.items():
        assert out == base[uid]


def test_chunked_optout_family_falls_back(rng):
    """Forcing chunked prefill on a ring/recurrent-state family warns,
    serves via the bucketed path, and stays exact."""
    cfg = get_reduced("gemma2-2b")            # sliding-window ring
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    reqs = _requests(rng, cfg, [(9, 4), (17, 4)])
    base, _ = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2),
                     reqs, chunked_prefill=False)
    with pytest.warns(UserWarning, match="chunked prefill requested"):
        done, m = _serve(eng, reqs, chunked_prefill=True)
    assert m.scheduler == "bucketed"
    for uid, out in done.items():
        assert out == base[uid]


def test_budget_floor_clamped_with_warning(rng):
    """A budget below one token per slot cannot make decode progress;
    the engine raises it to the floor, loudly, and still serves."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=3)
    reqs = _requests(rng, cfg, [(9, 4), (14, 4)])
    base, _ = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=3),
                     reqs, chunked_prefill=False)
    with pytest.warns(UserWarning, match="raising to"):
        done, m = _serve(eng, reqs, max_batched_tokens=1,
                         chunked_prefill=True)
    assert m.max_batched_tokens == 3          # slots * 1
    for uid, out in done.items():
        assert out == base[uid]


# ---------------------------------------------------------------------------
# Scheduler property tests: budget never exceeded, FCFS, no starvation
# ---------------------------------------------------------------------------


def _scheduler_invariant_trace(seed: int):
    rng = np.random.default_rng(seed)
    slots = int(rng.integers(2, 6))
    budget = int(rng.integers(slots, 40))
    sched = ContinuousScheduler(slots, PageAllocator(64), page_size=8,
                                max_pages_per_slot=16)
    n = int(rng.integers(3, 16))
    for uid in range(n):
        sched.submit(Request(uid=uid,
                             tokens=[1] * int(rng.integers(1, 50)),
                             max_new_tokens=int(rng.integers(1, 6))))
    iters = 0
    while sched.has_work():
        iters += 1
        assert iters < 5000, "scheduler failed to make progress"
        while sched.try_admit() is not None:
            pass
        plan = sched.next_batch(budget)
        # the budget is a hard per-iteration ceiling
        assert plan.total_tokens <= budget
        # decode first: every decoding slot is in the plan
        decoding = sorted(s for s, st in sched.slots.items()
                          if st.prefill_done)
        assert sorted(plan.decode_slots) == decoding
        admitting = [s for s, st in sched.slots.items()
                     if not st.prefill_done]
        if admitting:
            # FCFS, starvation-free: the oldest admitting slot always
            # receives the first (non-empty) chunk of the iteration
            oldest = min(admitting, key=lambda s: sched.slots[s].admit_seq)
            assert plan.chunks and plan.chunks[0].slot == oldest
            assert plan.chunks[0].length >= 1
        seqs = [sched.slots[c.slot].admit_seq for c in plan.chunks]
        assert seqs == sorted(seqs)           # chunks in admission order
        for c in plan.chunks:                 # contiguous, in-bounds
            st = sched.slots[c.slot]
            assert c.start == st.prefill_pos
            assert 1 <= c.length \
                <= st.request.prompt_len - st.prefill_pos
            st.prefill_pos += c.length        # apply the chunk
        for s in plan.decode_slots:           # emulate one decode token
            st = sched.slots[s]
            st.emitted.append(7)
            if len(st.emitted) >= st.request.max_new_tokens:
                sched.retire(s)
    sched.allocator.check()


def test_scheduler_budget_and_fcfs_seeded():
    for seed in range(50):
        _scheduler_invariant_trace(seed)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                           # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=50)
    @given(st.integers(0, 10_000))
    def test_scheduler_budget_and_fcfs_hypothesis(seed):
        _scheduler_invariant_trace(seed)


# ---------------------------------------------------------------------------
# Mixed paged-attention entry: ragged query counts vs oracle
# ---------------------------------------------------------------------------


def test_mixed_attention_ragged_vs_oracle(rng):
    B, P, page, npages, Hq, Hkv, D = 3, 7, 8, 3, 4, 2, 16
    W = 5
    kpool = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
    vpool = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
    ppos = np.full((P, page), -1, np.int32)
    bt = np.full((B, npages), -1, np.int32)
    ctx = [9, 14, 4]                           # stored context per slot
    perm = rng.permutation(P - 1)              # last page is the dump
    nxt_page = 0
    for b in range(B):
        used = -(-(ctx[b] + W) // page)
        bt[b, :used] = perm[nxt_page:nxt_page + used]
        nxt_page += used
        for t in range(ctx[b] + W):            # window K/V already written
            ppos[bt[b, t // page], t % page] = t
    # ragged per-slot query counts: decode row, chunk row, empty row
    n_q = np.asarray([1, W, 0], np.int32)
    q_pos = np.where(np.arange(W)[None, :] < n_q[:, None],
                     np.asarray(ctx)[:, None] + np.arange(W)[None, :],
                     -1).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, W, Hq, D)), jnp.float32)
    assert DA.paged_mixed_shape_supported(q, kpool, jnp.asarray(bt))
    out = DA.paged_mixed_attention(
        q, kpool, vpool, jnp.asarray(ppos), jnp.asarray(bt),
        jnp.asarray(q_pos), window=None, scale=D ** -0.5, interpret=True)
    ref = R.paged_mixed_attention_ref(
        q, kpool, vpool, jnp.asarray(ppos), jnp.asarray(bt),
        jnp.asarray(q_pos), window=None, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # padding queries (q_pos == -1) are exactly zero
    assert not np.asarray(out[0, 1:]).any()
    assert not np.asarray(out[2]).any()


def test_forward_mixed_matches_decode_and_prefill(rng):
    """Model-level: one forward_mixed call carrying a decode row and a
    prefill-chunk row reproduces forward_decode / forward_prefill logits
    for the same tokens."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    page, npages, slots = 8, 8, 2
    toks = [list(map(int, rng.integers(4, 400, size=9))),
            list(map(int, rng.integers(4, 400, size=13)))]

    def fresh():
        return T.init_paged_cache(cfg, num_pages=npages, page_size=page,
                                  max_slots=slots, max_len=48,
                                  dtype=jnp.float32)

    bt = np.full((slots, 6), -1, np.int32)
    bt[0, :3] = [0, 1, 2]
    bt[1, :3] = [3, 4, 5]
    paged = {"block_tables": jnp.asarray(bt)}

    # reference: slot 0 prefilled whole, then one decode step; slot 1
    # prefilled whole (its last-token logits)
    cache = fresh()
    tok0 = jnp.asarray([toks[0] + [0] * 7, toks[1] + [0] * 3], jnp.int32)
    plens = jnp.asarray([9, 13], jnp.int32)
    lg_p, cache, _ = T.forward_prefill(
        params, cfg, tok0, plens, cache, policy=FP32, max_len=48,
        last_only=True, paged={**paged, "active": jnp.ones((2,), bool)})
    nxt0 = int(jnp.argmax(lg_p[0, 0]))
    lg_d, cache, _ = T.forward_decode(
        params, cfg, jnp.asarray([[nxt0], [0]], jnp.int32), cache,
        jnp.asarray([9, 13], jnp.int32), policy=FP32, max_len=48,
        paged={**paged, "active": jnp.asarray([True, False])})

    # mixed: slot 0 already prefilled -> decode row; slot 1 prefills its
    # last 5 tokens as a chunk (first 8 pre-written by a prefix call)
    cache2 = fresh()
    _, cache2, _ = T.forward_prefill(
        params, cfg, tok0, plens, cache2, policy=FP32, max_len=48,
        last_only=True, paged={**paged, "active": jnp.ones((2,), bool)})
    from repro.core import kv_cache as KV
    cache2 = KV.reset_pages_all(cache2, np.asarray(bt[1, :3]))
    _, cache2, _ = T.forward_prefill(
        params, cfg, jnp.asarray([toks[1][:8] + [0] * 5], jnp.int32),
        jnp.asarray([8], jnp.int32),
        KV.slot_view(cache2, 1), policy=FP32, max_len=48,
        last_only=True,
        paged={"block_tables": jnp.asarray(bt[1:2]),
               "active": jnp.ones((1,), bool)})
    W = 5
    mixed_toks = np.zeros((slots, W), np.int32)
    mixed_toks[0, 0] = nxt0
    mixed_toks[1, :5] = toks[1][8:]
    lg_m, _, _ = T.forward_mixed(
        params, cfg, jnp.asarray(mixed_toks), cache2,
        jnp.asarray([9, 8], jnp.int32), jnp.asarray([1, 5], jnp.int32),
        policy=FP32, max_len=48, paged=paged)
    np.testing.assert_allclose(np.asarray(lg_m[0, 0]),
                               np.asarray(lg_d[0, 0]), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg_m[1, 0]),
                               np.asarray(lg_p[1, 0]), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# TTFT / ITL metrics
# ---------------------------------------------------------------------------


def test_ttft_itl_recorded(rng):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    reqs = _requests(rng, cfg, [(9, 5), (21, 5), (5, 5)])
    _, m = _serve(eng, reqs, max_batched_tokens=16, chunked_prefill=True)
    # every request emitted a first token -> one TTFT sample each
    assert len(m.ttft_s) == len(reqs)
    assert all(t >= 0 for t in m.ttft_s)
    assert len(m.itl_s) > 0 and all(g >= 0 for g in m.itl_s)
    assert m.itl_p99 >= m.itl_p50 >= 0
    assert m.ttft_p99 >= m.ttft_p50 > 0


def test_ttft_itl_zero_guards():
    m = ServeMetrics()
    assert m.ttft_p50 == 0.0 and m.ttft_p99 == 0.0
    assert m.itl_p50 == 0.0 and m.itl_p99 == 0.0
