"""Radix prefix cache: cross-request KV page sharing (PR-2 tentpole).

Contracts under test:
  * allocator refcounts never go negative; no leaked pages after a full
    serve (alloc == free + trie-resident);
  * a COW write never mutates a page with refcount > 1 (the writer gets
    a fresh copy of the partial tail page);
  * trie match/insert/evict semantics (LRU, pinning, partial-node
    extension) against a hand-computed oracle;
  * serve_continuous with sharing enabled produces bit-identical sampled
    outputs vs sharing disabled AND vs per-request dense references,
    while measurably skipping prefill work;
  * opted-out layer families (sliding-window, MLA, recurrent, hybrid)
    serve exactly with sharing silently disabled.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    # when hypothesis is installed (CI installs it), the invariant
    # harness below also runs as a generative property test
    from hypothesis import given, settings, strategies as st
    settings.register_profile("prefix", deadline=None, max_examples=20)
    settings.load_profile("prefix")
    HAVE_HYPOTHESIS = True
except ImportError:                    # seeded fallback still runs
    HAVE_HYPOTHESIS = False

from repro.configs.registry import get_reduced
from repro.core import kv_cache as KV
from repro.core.continuous import ContinuousScheduler, PageAllocator
from repro.core.engine import InferenceEngine
from repro.core.precision import FP32
from repro.core.prefix_cache import RadixPrefixCache, shareable
from repro.core.scheduler import Request
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# Allocator refcounts
# ---------------------------------------------------------------------------


def test_allocator_refcount_lifecycle():
    al = PageAllocator(4)
    pages = al.alloc(2)
    assert al.refcount(pages[0]) == 1
    al.incref(pages[0])
    assert al.refcount(pages[0]) == 2
    al.decref(pages[0])
    assert al.free_count == 2               # still held once
    al.decref(pages[0])
    assert al.free_count == 3               # now back in the pool
    with pytest.raises(ValueError):
        al.decref(pages[0])                 # would go negative
    with pytest.raises(ValueError):
        al.incref(pages[0])                 # incref of a free page
    al.decref(pages[1])
    al.check()
    assert al.free_count == 4 and al.allocated_count == 0


def test_allocator_check_detects_leak():
    al = PageAllocator(3)
    al.alloc(1)
    al.check()                              # 1 resident + 2 free = 3: fine
    al._free.append(99)                     # corrupt on purpose
    with pytest.raises(AssertionError):
        al.check()


# ---------------------------------------------------------------------------
# Radix trie
# ---------------------------------------------------------------------------


def _trie(num_pages=16, ps=4):
    al = PageAllocator(num_pages)
    return RadixPrefixCache(al, ps), al


def test_trie_match_insert_basic():
    trie, al = _trie()
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]          # 2.5 pages at ps=4
    pages = al.alloc(3)
    kept = trie.insert(toks, pages, len(toks))
    assert kept == 3
    for p in pages:
        al.decref(p)                                 # trie now sole owner
    assert sorted(trie.resident_pages) == sorted(pages)

    # full match: 2 full pages + partial tail (10 tokens)
    m, mp = trie.match(toks)
    assert m == 10 and mp == pages
    # prefix-of-cached match stops inside the second page
    m, mp = trie.match([1, 2, 3, 4, 5, 6])
    assert m == 6 and mp == pages[:2]
    # divergence after one page
    m, mp = trie.match([1, 2, 3, 4, 99, 98])
    assert m == 4 and mp == pages[:1]
    # no match at all
    m, mp = trie.match([7, 7, 7])
    assert (m, mp) == (0, [])


def test_trie_divergent_siblings_coexist():
    trie, al = _trie()
    a = al.alloc(2)
    b = al.alloc(2)
    trie.insert([1, 2, 3, 4, 5, 5, 5, 5], a, 8)
    trie.insert([1, 2, 3, 4, 6, 6, 6, 6], b, 8)
    # first page deduped (a[0] kept), second spans diverge into siblings
    assert trie.num_nodes == 3
    m, mp = trie.match([1, 2, 3, 4, 6, 6, 6, 6])
    assert m == 8 and mp == [a[0], b[1]]
    for p in a + b:
        al.decref(p)
    assert al.allocated_count == 3                   # b[0] was never kept


def test_trie_partial_node_extension_swaps_page():
    trie, al = _trie()
    short = al.alloc(1)
    trie.insert([1, 2], short, 2)                    # partial tail node
    for p in short:
        al.decref(p)
    longer = al.alloc(1)
    trie.insert([1, 2, 3, 4], longer, 4)             # extends in place
    for p in longer:
        al.decref(p)
    assert trie.num_nodes == 1
    assert trie.resident_pages == [longer[0]]        # page swapped
    assert al.refcount(short[0]) == 0                # old page released
    m, mp = trie.match([1, 2, 3, 4, 9])
    assert m == 4 and mp == [longer[0]]


def test_trie_lru_eviction_and_pinning():
    trie, al = _trie(num_pages=4)
    a = al.alloc(1)
    b = al.alloc(1)
    c = al.alloc(1)
    trie.insert([1, 1, 1, 1], a, 4, pin=True)
    trie.insert([2, 2, 2, 2], b, 4)
    trie.insert([3, 3, 3, 3], c, 4)
    for p in a + b + c:
        al.decref(p)
    trie.match([3, 3, 3, 3])                         # c most recently used
    assert trie.evict(1) == 1                        # LRU unpinned: b
    assert sorted(trie.resident_pages) == sorted(a + c)
    assert trie.evict(5) == 1                        # c evictable, a pinned
    assert trie.resident_pages == a
    trie.unpin_all()
    assert trie.evict(1) == 1
    assert trie.num_nodes == 0
    al.check()
    assert al.free_count == 4


def test_trie_never_evicts_actively_referenced():
    trie, al = _trie(num_pages=4)
    a = al.alloc(1)
    trie.insert([1, 1, 1, 1], a, 4)                  # refcount 2: us + trie
    assert trie.evict(1) == 0                        # we still hold it
    al.decref(a[0])
    assert trie.evict(1) == 1


def test_trie_evict_leaf_before_parent():
    trie, al = _trie(num_pages=4)
    pages = al.alloc(2)
    trie.insert([1, 2, 3, 4, 5, 6, 7, 8], pages, 8)
    for p in pages:
        al.decref(p)
    trie.evict(2)
    al.check()
    assert trie.num_nodes == 0 and al.free_count == 4


# ---------------------------------------------------------------------------
# COW page copy (device op)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantized", [False, True])
def test_copy_pages_keeps_prefix_masks_tail(rng, quantized):
    P, page, H, D = 4, 4, 2, 8
    pool = {"pk": jnp.asarray(rng.normal(size=(P, page, H, D)), jnp.float32),
            "pv": jnp.asarray(rng.normal(size=(P, page, H, D)), jnp.float32),
            "ppos": jnp.asarray([[4, 5, 6, 7], [-1] * 4, [-1] * 4,
                                 [-1] * 4], jnp.int32)}
    if quantized:
        # int8 pool layout: codes + per-entry scale pools travel together
        for kk in ("pk", "pv"):
            q, s = KV.quantize_kv(pool[kk])
            pool[kk] = q
            pool[kk + "_scale"] = s
    out = KV.copy_pages(pool, jnp.asarray([0]), jnp.asarray([2]),
                        jnp.asarray([6]))
    # entries at positions 4,5 kept; 6,7 beyond the match masked
    np.testing.assert_array_equal(np.asarray(out["ppos"][2]),
                                  [4, 5, -1, -1])
    data_keys = [k for k in KV.PAGED_DATA_KEYS if k in pool]
    for kk in data_keys:
        np.testing.assert_allclose(np.asarray(out[kk][2]),
                                   np.asarray(pool[kk][0]))
    # the source page is bit-untouched (copy, not move)
    np.testing.assert_array_equal(np.asarray(out["ppos"][0]),
                                  np.asarray(pool["ppos"][0]))
    for kk in data_keys:
        np.testing.assert_allclose(np.asarray(out[kk][0]),
                                   np.asarray(pool[kk][0]))


def test_copy_pages_dump_row_noop():
    P, page = 3, 4
    pool = {"pk": jnp.zeros((P, page, 1, 2)), "pv": jnp.zeros((P, page, 1, 2)),
            "ppos": jnp.full((P, page), -1, jnp.int32)}
    out = KV.copy_pages(pool, jnp.asarray([P - 1]), jnp.asarray([P - 1]),
                        jnp.asarray([0]))
    assert int(out["ppos"][P - 1].max()) == -1


# ---------------------------------------------------------------------------
# Scheduler + trie: pool invariants under random traffic (host-only)
# ---------------------------------------------------------------------------


def _pool_invariant_trace(trace, num_pages):
    """Drive admission/retire bookkeeping (no device work) with random
    shared-prefix traffic: refcounts stay positive, COW targets are
    always private, and after the last retire every allocated page is
    exactly the trie's residency (alloc == free + resident)."""
    ps = 4
    al = PageAllocator(num_pages)
    trie = RadixPrefixCache(al, ps)
    sched = ContinuousScheduler(2, al, ps, max_pages_per_slot=16,
                                prefix_cache=trie)
    prefixes = {g: [100 + g] * (3 + 2 * g) for g in range(4)}
    for uid, (g, extra, mn) in enumerate(trace):
        toks = prefixes[g] + [uid % 7 + 1] * extra
        sched.submit(Request(uid=uid, tokens=toks, max_new_tokens=mn))
    while sched.has_work():
        progressed = False
        while True:
            adm = sched.try_admit()
            if adm is None:
                break
            progressed = True
            _, stt = adm
            # COW invariant: every page the admission prefill writes
            # (the fresh ones) is private to this request
            for p in stt.fresh_pages:
                assert al.refcount(p) == 1
            if stt.cow_src >= 0:
                assert al.refcount(stt.cow_src) >= 2   # pinned for copy
            sched.release_cow_source(stt)
            plen = stt.request.prompt_len
            sched.insert_prefix(stt, (plen // ps) * ps)
        # emulate decode-to-completion for one occupied slot
        if sched.slots:
            slot = next(iter(sched.slots))
            stt = sched.slots[slot]
            budget = min(stt.request.max_new_tokens, 3)
            stt.emitted = [5] * budget
            sched.retire(slot)
        elif not progressed:
            # head can never fit this pool even after eviction: drop it
            sched.waiting.pop(0)
    al.check()
    resident = trie.resident_pages
    assert len(resident) == len(set(resident))
    assert al.allocated_count == len(resident)
    assert all(al.refcount(p) == 1 for p in resident)


def test_pool_invariants_seeded_traffic():
    """Deterministic sweep of the invariant harness (always runs; the
    hypothesis variant below widens the search when available)."""
    for seed in range(40):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 24))
        trace = [(int(rng.integers(0, 4)), int(rng.integers(1, 30)),
                  int(rng.integers(1, 12))) for _ in range(n)]
        _pool_invariant_trace(trace, int(rng.integers(6, 40)))


if HAVE_HYPOTHESIS:
    @given(st.lists(st.tuples(st.integers(0, 3),      # prefix group
                              st.integers(1, 30),     # extra suffix tokens
                              st.integers(1, 12)),    # max_new
                    min_size=1, max_size=24),
           st.integers(6, 40))
    def test_pool_invariants_random_traffic(trace, num_pages):
        _pool_invariant_trace(trace, num_pages)


# ---------------------------------------------------------------------------
# Engine-level: exactness + savings
# ---------------------------------------------------------------------------


def _requests(rng, cfg, shapes, prefix=None):
    out = []
    for i, (ln, mn) in enumerate(shapes):
        body = list(map(int, rng.integers(4, min(cfg.vocab_size, 400),
                                          size=ln)))
        out.append(Request(uid=i, tokens=([2] + (prefix or []) + body),
                           max_new_tokens=mn))
    return out


def _reference(eng, reqs):
    out = {}
    for r in reqs:
        g = eng.generate_batch(np.asarray([r.tokens], np.int32),
                               np.asarray([len(r.tokens)], np.int32),
                               r.max_new_tokens)
        row = g[0]
        out[r.uid] = [int(t) for t in row[row >= 0]]
    return out


def test_shareable_gate():
    assert shareable(get_reduced("qwen3-4b"), 64) is None
    assert shareable(get_reduced("unimo-text"), 64) is None
    assert shareable(get_reduced("gemma2-2b"), 64) is not None   # window
    assert shareable(get_reduced("deepseek-v3-671b"), 64) is not None  # MLA
    assert shareable(get_reduced("xlstm-125m"), 64) is not None  # recurrent
    assert shareable(get_reduced("hymba-1.5b"), 64) is not None  # hybrid


def test_prefix_sharing_exact_and_saves_prefill(rng):
    """Shared-prefix trace: results must be bit-identical to both the
    dense per-request reference and the sharing-off run, while the
    prefill token count provably drops."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefix = list(map(int, rng.integers(4, 400, size=21)))
    shapes = [(5, 5), (3, 4), (7, 5), (4, 4), (6, 5)]

    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    reqs = _requests(rng, cfg, shapes, prefix=prefix)
    ref = _reference(eng, reqs)

    eng_off = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                              max_batch=2)
    off, m_off = eng_off.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                          steps_per_sync=3,
                                          prefix_cache=False)
    eng_on = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                             max_batch=2)
    on, m_on = eng_on.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                       steps_per_sync=3, prefix_cache=True)
    for a, b in zip(off, on):
        assert a.result == ref[a.uid]
        assert b.result == ref[b.uid]
    assert m_on.prefix_matched_tokens > 0
    assert m_on.pages_shared > 0
    assert m_on.prefix_hits >= len(reqs) - 2     # first-in-slot pair misses
    assert m_off.prefix_matched_tokens == 0
    # every prompt token is either computed or served from the cache
    total_prompt = sum(r.prompt_len for r in reqs)
    assert m_on.prefill_tokens + m_on.prefix_matched_tokens == total_prompt
    assert m_on.prefill_tokens < m_off.prefill_tokens
    assert 0.0 < m_on.prefix_hit_rate < 1.0
    # per-request observability
    assert sum(r.prefix_tokens_matched for r in on) \
        == m_on.prefix_matched_tokens


def test_identical_prompt_resubmission_hits_cache(rng):
    """The same prompt served twice: the second run matches everything
    but the final token and emits identical output."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    reqs = _requests(rng, cfg, [(14, 5)])
    ref = _reference(eng, reqs)
    r1, m1 = eng.serve_continuous(copy.deepcopy(reqs), page_size=8)
    r2, m2 = eng.serve_continuous(copy.deepcopy(reqs), page_size=8)
    assert r1[0].result == ref[0] and r2[0].result == ref[0]
    # second pass: everything except the last prompt token may be served
    # from cache (the cache also holds the generated continuation)
    assert m2.prefix_matched_tokens == r2[0].prompt_len - 1
    assert m2.prefill_tokens == 1
    assert m1.prefix_matched_tokens == 0


@pytest.mark.parametrize("arch", ["gemma2-2b", "deepseek-v3-671b",
                                  "xlstm-125m", "hymba-1.5b"])
def test_optout_families_serve_exactly(arch, rng):
    """Window/MLA/recurrent/hybrid layers opt out of sharing; forcing
    prefix_cache=True must warn, disable itself, and still serve every
    request bit-exactly."""
    import dataclasses
    cfg = get_reduced(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    reqs = _requests(rng, cfg, [(5, 4), (5, 4), (9, 4)],
                     prefix=[7, 8, 9, 10, 11, 12, 13, 14])
    ref = _reference(eng, reqs)
    with pytest.warns(UserWarning, match="disabled"):
        done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                       prefix_cache=True)
    for r in done:
        assert r.result == ref[r.uid], f"{arch} uid {r.uid}"
    assert m.prefix_matched_tokens == 0 and m.pages_shared == 0


def test_set_prefix_seeds_first_wave(rng):
    """engine.set_prefix on the paged path: requests in the very first
    admission wave already skip the seeded prefix."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    sys_prompt = [2] + list(map(int, rng.integers(4, 400, size=23)))
    shapes = [(4, 5), (6, 5), (3, 4)]

    eng_ref = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                              max_batch=2)
    reqs = _requests(rng, cfg, shapes)
    for r in reqs:                       # prepend the system prompt
        r.tokens = sys_prompt + r.tokens
    ref = _reference(eng_ref, reqs)

    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    eng.set_prefix(sys_prompt, page_size=8)
    done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8)
    for r in done:
        assert r.result == ref[r.uid]
    assert m.prefix_hits == len(reqs)            # every admission hit
    assert m.prefix_matched_tokens >= len(reqs) * (len(sys_prompt) // 8) * 8
    eng.clear_prefix()                           # unpins; still correct
    done2, _ = eng.serve_continuous(copy.deepcopy(reqs), page_size=8)
    for r in done2:
        assert r.result == ref[r.uid]


def test_set_prefix_optout_warns_noop():
    cfg = get_reduced("xlstm-125m")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    with pytest.warns(UserWarning, match="sharing disabled"):
        eng.set_prefix([2, 3, 4, 5])
    assert eng._paged_ctx is None


@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-v3-671b"])
def test_dense_resume_prefill_matches_full(arch, rng):
    """Model-level contract kept from the dense prefix era: a prefill
    resumed from a pre-filled cache (``start > 0``, attend-cache) equals
    one uninterrupted prefill — incl. the MLA latent path."""
    cfg = get_reduced(arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    B, S, cut = 2, 12, 5
    toks = jnp.asarray(rng.integers(4, min(cfg.vocab_size, 400),
                                    size=(B, S)), jnp.int32)
    c_full = T.init_cache(cfg, B, 32, jnp.float32)
    lg_full, _, _ = T.forward_prefill(params, cfg, toks,
                                   jnp.full((B,), S, jnp.int32), c_full,
                                   policy=FP32)
    c = T.init_cache(cfg, B, 32, jnp.float32)
    _, c, _ = T.forward_prefill(params, cfg, toks[:, :cut],
                             jnp.full((B,), cut, jnp.int32), c, policy=FP32)
    lg2, _, _ = T.forward_prefill(params, cfg, toks[:, cut:],
                               jnp.full((B,), S - cut, jnp.int32), c,
                               policy=FP32, start=cut)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg_full[:, cut:]),
                               rtol=3e-4, atol=3e-4)


def test_eviction_under_pool_pressure_stays_exact(rng):
    """A pool too small to cache every distinct prefix forces LRU
    eviction mid-run; serving stays exact and the books balance."""
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    pfx = [list(map(int, rng.integers(4, 400, size=17))) for _ in range(3)]
    reqs = []
    for i in range(9):
        body = list(map(int, rng.integers(4, 400, size=3 + i % 3)))
        reqs.append(Request(uid=i, tokens=[2] + pfx[i % 3] + body,
                            max_new_tokens=4))
    ref = _reference(eng, reqs)
    eng2 = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    # 14 pages of 8: two slots need up to 2*ceil((21+4)/8)=8 live pages,
    # while 3 distinct prefixes want 3*3=9 cached -> pressure
    done, m = eng2.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                    num_pages=14)
    for r in done:
        assert r.result == ref[r.uid]
    assert m.prefix_matched_tokens > 0
    ctx = eng2._paged_ctx
    ctx["alloc"].check()
    assert ctx["alloc"].allocated_count == len(ctx["trie"].resident_pages)
