"""Token-packed ragged execution: one (1, T) dispatch per iteration.

Contracts under test:

  * the packed paged-attention kernel matches its oracle on ragged
    multi-slot streams (decode lanes + prefill chunks in one (1, T)
    dispatch), including int8 pools, sliding windows and logit
    softcaps; padding lanes (q_pos == -1) come back exactly zero;
  * ``forward_packed`` reproduces ``forward_decode`` /
    ``forward_prefill`` logits for the same tokens — the whole
    iteration flattens without changing any segment's math;
  * ``pack_batch`` preserves the plan verbatim (budget ceiling,
    decode-first layout, FCFS chunk order, contiguous per-segment
    positions) — property-tested over random scheduler traces;
  * greedy serving outputs are bit-identical between packed and
    bucketed execution across plain, shared-prefix, int8, speculative
    and preemption/resume serving, while packed runs make exactly ONE
    device dispatch per mixed iteration;
  * an engine builds each step program once: a second serve call at
    the same geometry and stream widths traces, lowers and compiles
    nothing, and the benchmark's warm-up recipe compiles exactly its
    width ladder plus the fused decode program;
  * the new ServeMetrics derivations (host/device split, dispatches
    per iteration, padded-token fraction) are zero-guarded.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_reduced
from repro.core import kv_cache as KV
from repro.core.continuous import (ContinuousScheduler, PageAllocator,
                                   ServeMetrics)
from repro.core.engine import InferenceEngine, packed_width_buckets
from repro.core.precision import FP32
from repro.core.sampling import SamplingParams
from repro.core.scheduler import Request
from repro.core.trace import ServeTracer
from repro.kernels import decode_attention as DA
from repro.kernels import ops as KOPS
from repro.kernels import ref as R
from repro.models import transformer as T

INT8 = dataclasses.replace(FP32, kv_dtype="int8")


# ---------------------------------------------------------------------------
# Packed paged-attention kernel vs oracle
# ---------------------------------------------------------------------------


def _packed_setup(rng, *, q8=False):
    """Three slots' paged pools plus one packed stream over them:
    a decode lane for slot 0, a 5-token chunk for slot 1 and a 6-token
    chunk for slot 2, padded to T=16."""
    B, P, page, npages, Hq, Hkv, D = 3, 10, 8, 3, 4, 2, 16
    T_ = 16
    ctx = [9, 14, 4]                   # already-stored context per slot
    seg_len = [1, 5, 6]                # decode, chunk, chunk
    if q8:
        kpool = jnp.asarray(rng.integers(-127, 128, size=(P, page, Hkv, D)),
                            jnp.int8)
        vpool = jnp.asarray(rng.integers(-127, 128, size=(P, page, Hkv, D)),
                            jnp.int8)
        k_scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(P, page, Hkv)),
                              jnp.float32)
        v_scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(P, page, Hkv)),
                              jnp.float32)
    else:
        kpool = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
        vpool = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
        k_scale = v_scale = None
    ppos = np.full((P, page), -1, np.int32)
    bt = np.full((B, npages), -1, np.int32)
    perm = rng.permutation(P - 1)      # last page is the dump
    nxt = 0
    for b in range(B):
        used = -(-(ctx[b] + seg_len[b]) // page)
        bt[b, :used] = perm[nxt:nxt + used]
        nxt += used
        for t in range(ctx[b] + seg_len[b]):   # window K/V already written
            ppos[bt[b, t // page], t % page] = t
    slot_ids = np.full(T_, -1, np.int32)
    q_pos = np.full(T_, -1, np.int32)
    seg_start, t = [], 0
    for b in range(B):
        seg_start.append(t)
        slot_ids[t:t + seg_len[b]] = b
        q_pos[t:t + seg_len[b]] = ctx[b] + np.arange(seg_len[b])
        t += seg_len[b]
    meta = DA.packed_meta_table(np.asarray(seg_start, np.int32),
                                np.asarray(seg_len, np.int32),
                                np.arange(B, dtype=np.int32), T_,
                                T_ // DA.PACKED_BLOCK_Q + B)
    q = jnp.asarray(rng.normal(size=(1, T_, Hq, D)), jnp.float32)
    return (q, kpool, vpool, jnp.asarray(ppos), jnp.asarray(bt),
            jnp.asarray(q_pos[None, :]), jnp.asarray(slot_ids),
            jnp.asarray(meta), k_scale, v_scale, D, t)


@pytest.mark.parametrize("window,softcap", [(None, None), (None, 30.0),
                                            (4, None)])
def test_packed_kernel_vs_oracle(rng, window, softcap):
    (q, kpool, vpool, ppos, bt, q_pos, slot_ids, meta,
     _, _, D, n_real) = _packed_setup(rng)
    assert DA.paged_packed_shape_supported(q, kpool, bt)
    out = DA.paged_packed_attention(q, kpool, vpool, ppos, bt, q_pos, meta,
                                    window=window, scale=D ** -0.5,
                                    attn_softcap=softcap, interpret=True)
    ref = R.paged_packed_attention_ref(q, kpool, vpool, ppos, bt, q_pos,
                                       slot_ids, window=window,
                                       scale=D ** -0.5, attn_softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # padding lanes are exactly zero
    assert not np.asarray(out[0, n_real:]).any()


def test_packed_kernel_q8_vs_oracle(rng):
    (q, kpool, vpool, ppos, bt, q_pos, slot_ids, meta,
     k_scale, v_scale, D, n_real) = _packed_setup(rng, q8=True)
    out = DA.paged_packed_attention_q8(q, kpool, k_scale, vpool, v_scale,
                                       ppos, bt, q_pos, meta, window=None,
                                       scale=D ** -0.5, interpret=True)
    ref = R.paged_packed_attention_ref(q, kpool, vpool, ppos, bt, q_pos,
                                       slot_ids, window=None,
                                       scale=D ** -0.5, k_scale=k_scale,
                                       v_scale=v_scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out[0, n_real:]).any()


@pytest.mark.parametrize("pool", ["fp", "q8"])
def test_packed_kernel_live_bound_vs_oracle(rng, pool):
    """The live-page bound at its edges in one packed stream: decode
    lanes at contexts of 1, 15, 16, 17 and max_len tokens, a 10-token
    chunk from position 12 (two query windows, across a page edge), a
    slot whose allocated pages past the bound hold stale entries beyond
    its query, an empty slot in the block table, and unused (slot -1)
    work rows."""
    P, page, npages, Hq, Hkv, D, T_ = 24, 16, 4, 4, 2, 16, 24
    max_len = page * npages
    ppos = np.full((P, page), -1, np.int32)
    free = iter(rng.permutation(P - 1))      # last page is the dump
    # (stored context incl. the stream's own tokens, segment length)
    slots = [(1, 1), (15, 1), (16, 1), (17, 1), (max_len, 1), (22, 10),
             (20, 1), (0, 0)]
    bt = np.full((len(slots), npages), -1, np.int32)
    slot_ids = np.full(T_, -1, np.int32)
    q_pos = np.full(T_, -1, np.int32)
    seg_start, seg_len, seg_slot, t = [], [], [], 0
    for b, (ctx, n) in enumerate(slots):
        used = npages if b == 6 else -(-ctx // page)
        bt[b, :used] = [next(free) for _ in range(used)]
        for p in range(ctx):
            ppos[bt[b, p // page], p % page] = p
        if n:
            seg_start.append(t)
            seg_len.append(n)
            seg_slot.append(b)
            slot_ids[t:t + n] = b
            q_pos[t:t + n] = np.arange(ctx - n, ctx)
            t += n
    ppos[bt[6, 1], 4:] = np.arange(28, 40)   # stale, beyond position 19
    ppos[bt[6, 2]] = np.arange(40, 56)
    ppos[bt[6, 3]] = np.arange(56, 72)
    n_work = T_ // DA.PACKED_BLOCK_Q + len(slots)
    meta = DA.packed_meta_table(seg_start, seg_len, seg_slot, T_, n_work)
    assert (meta[:, 0] < 0).any()
    k = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, T_, Hq, D)), jnp.float32)
    args = (jnp.asarray(ppos), jnp.asarray(bt), jnp.asarray(q_pos[None]))
    if pool == "q8":
        (kq, ks), (vq, vs) = KV.quantize_kv(k), KV.quantize_kv(v)
        out = DA.paged_packed_attention_q8(
            q, kq, ks, vq, vs, *args, jnp.asarray(meta), window=None,
            scale=D ** -0.5, interpret=True)
        ref = R.paged_packed_attention_ref(
            q, kq, vq, *args, jnp.asarray(slot_ids), window=None,
            scale=D ** -0.5, k_scale=ks, v_scale=vs)
    else:
        out = DA.paged_packed_attention(q, k, v, *args, jnp.asarray(meta),
                                        window=None, scale=D ** -0.5,
                                        interpret=True)
        ref = R.paged_packed_attention_ref(q, k, v, *args,
                                           jnp.asarray(slot_ids),
                                           window=None, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out[0, t:]).any()


def _block_edge_stream(rng, *, layers, layer):
    """A packed stream whose work rows sit at the kernel's block edges:
    npages 6 and pages small enough that a block holds BP = 4 pages
    (asserted by the caller).  Decode lanes of 1, BP - 1, BP, BP + 1 and
    npages live pages; a 12-token chunk (two consecutive rows of one
    slot); a lane whose live range holds an unallocated entry; a lane
    whose pages hold stale positions past its query, inside the bound
    and beyond it; unused rows (0 live pages) between used ones.  Pages
    are spread over a pool of 136 (> POS_LANES), so some take their
    positions by copy and some from the resident last column block."""
    P, page, npages, Hq, Hkv, D = 136, 8, 6, 4, 2, 16
    # (slot, query positions); slot 6 misses logical page 2, slot 7
    # holds stale entries in its pages
    segs = [(0, [0]), (1, [23]), (2, [31]), (3, [32]), (4, [47]),
            (5, list(range(20, 32))), (6, [39]), (7, [19])]
    ppos = np.full((layers, P, page), -1, np.int32)
    bt = np.full((len(segs), npages), -1, np.int32)
    free = iter(rng.permutation(P - 1))
    for slot, qs in segs:
        ctx = npages * page if slot == 7 else qs[-1] + 1
        for j in range(-(-ctx // page)):
            if slot == 6 and j == 2:
                continue
            bt[slot, j] = next(free)
            for t in range(page):
                if j * page + t < ctx:
                    ppos[layer, bt[slot, j], t] = j * page + t
    # slot 7's query is at 19: its page 2 holds 16..23, pages 3..5
    # stale entries beyond it
    T_ = 24
    slot_ids = np.full(T_, -1, np.int32)
    q_pos = np.full(T_, -1, np.int32)
    starts, lens, t = [], [], 0
    for slot, qs in segs:
        starts.append(t)
        lens.append(len(qs))
        slot_ids[t:t + len(qs)] = slot
        q_pos[t:t + len(qs)] = qs
        t += len(qs)
    meta = DA.packed_meta_table(starts, lens, [g[0] for g in segs], T_,
                                T_ // DA.PACKED_BLOCK_Q + len(segs))
    used = meta[meta[:, 0] >= 0]
    unused = np.asarray([[-1, 0, 0, 0]], np.int32)
    # unused rows between used ones: after the first row, after the
    # chunk's two rows, and at the end
    meta = np.concatenate([used[:1], unused, used[1:7], unused, unused,
                           used[7:], unused], axis=0)
    k = rng.normal(size=(layers, P, page, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(layers, P, page, Hkv, D)).astype(np.float32)
    q = rng.normal(size=(1, T_, Hq, D)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(ppos), jnp.asarray(bt), jnp.asarray(q_pos[None]),
            jnp.asarray(slot_ids), jnp.asarray(meta), D, t)


@pytest.mark.parametrize("pool", ["fp", "q8"])
@pytest.mark.parametrize("window,softcap,layer", [
    (None, None, None), (12, None, None), (None, 30.0, None),
    (None, None, 1)])
def test_packed_kernel_block_edges_vs_oracle(rng, pool, window, softcap,
                                             layer):
    """The packed kernel's blocks at their edges against the oracle:
    rows of 0, 1, BP - 1, BP, BP + 1 and npages live pages, consecutive
    rows of one slot and of different slots (the next row's first block
    is fetched during this row's last), unused rows between them, an
    unallocated entry in a live range, stale positions inside and past
    the bound; under a window, a softcap, and at layer 1 of a stack of
    3.  Padding lanes come back zero."""
    layers = 1 if layer is None else 3
    (q, k, v, ppos, bt, q_pos, slot_ids, meta, D,
     n_real) = _block_edge_stream(rng, layers=layers, layer=layer or 0)
    if layer is None:
        k, v, ppos = k[0], v[0], ppos[0]
    live = DA.packed_live_pages(meta, q_pos, 8, 6)
    bp = DA.packed_block_pages(k, v, None, 6)
    assert bp == 4
    assert {0, 1, bp - 1, bp, bp + 1, 6} <= set(np.asarray(live).tolist())
    kw = dict(window=window, scale=D ** -0.5, attn_softcap=softcap)
    if pool == "q8":
        (kq, ks), (vq, vs) = KV.quantize_kv(k), KV.quantize_kv(v)
        out = DA.paged_packed_attention_q8(
            q, kq, ks, vq, vs, ppos, bt, q_pos, meta, layer, **kw,
            interpret=True)
        ref = R.paged_packed_attention_ref(
            q, kq, vq, ppos, bt, q_pos, slot_ids, k_scale=ks, v_scale=vs,
            layer=layer, **kw)
    else:
        out = DA.paged_packed_attention(q, k, v, ppos, bt, q_pos, meta,
                                        layer, **kw, interpret=True)
        ref = R.paged_packed_attention_ref(q, k, v, ppos, bt, q_pos,
                                           slot_ids, layer=layer, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out[0, n_real:]).any()


def test_packed_meta_table_tiles_segments():
    """Every real lane lands in exactly one query window; unused rows
    are slot -1; tile starts clamp into the padded stream."""
    seg_start = np.asarray([0, 1, 6], np.int32)
    seg_len = np.asarray([1, 5, 6], np.int32)
    seg_slot = np.asarray([0, 1, 2], np.int32)
    T_, bq = 16, DA.PACKED_BLOCK_Q
    meta = DA.packed_meta_table(seg_start, seg_len, seg_slot, T_,
                                T_ // bq + 3)
    covered = np.zeros(T_, int)
    for slot, tile, ws, we in meta:
        if slot < 0:
            assert (tile, ws, we) == (0, 0, 0)
            continue
        assert 0 <= tile <= T_ - bq                 # tile fits the stream
        assert tile <= ws and we <= tile + bq       # window inside tile
        covered[ws:we] += 1
    assert (covered[:12] == 1).all()                # real lanes once each
    assert (covered[12:] == 0).all()                # padding never covered


# ---------------------------------------------------------------------------
# forward_packed vs forward_decode / forward_prefill logits
# ---------------------------------------------------------------------------


def test_forward_packed_matches_decode_and_prefill(rng):
    """One forward_packed stream carrying a decode lane (slot 0) and a
    prefill-chunk segment (slot 1) reproduces forward_decode /
    forward_prefill logits for the same tokens."""
    from repro.core import kv_cache as KV
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

    # reference: slot 0 prefilled whole then one decode step; slot 1's
    # whole-prompt last-token logits
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

    # packed: slot 0 decode lane + slot 1's final 5-token chunk, one
    # (1, 8) stream (first 8 of slot 1 pre-written by a prefix call)
    cache2 = fresh()
    _, cache2, _ = T.forward_prefill(
        params, cfg, tok0, plens, cache2, policy=FP32, max_len=48,
        last_only=True, paged={**paged, "active": jnp.ones((2,), bool)})
    cache2 = KV.reset_pages_all(cache2, np.asarray(bt[1, :3]))
    _, cache2, _ = T.forward_prefill(
        params, cfg, jnp.asarray([toks[1][:8] + [0] * 5], jnp.int32),
        jnp.asarray([8], jnp.int32), KV.slot_view(cache2, 1), policy=FP32,
        max_len=48, last_only=True,
        paged={"block_tables": jnp.asarray(bt[1:2]),
               "active": jnp.ones((1,), bool)})
    stream = np.zeros(8, np.int32)
    stream[0] = nxt0
    stream[1:6] = toks[1][8:]
    slot_ids = np.asarray([0, 1, 1, 1, 1, 1, -1, -1], np.int32)
    positions = np.asarray([9, 8, 9, 10, 11, 12, -1, -1], np.int32)
    seg_last = np.asarray([0, 5], np.int32)
    lg_pk, _, _ = T.forward_packed(
        params, cfg, jnp.asarray(stream[None, :]), cache2,
        jnp.asarray(slot_ids), jnp.asarray(positions),
        jnp.asarray(seg_last), policy=FP32, max_len=48, paged=paged)
    np.testing.assert_allclose(np.asarray(lg_pk[0, 0]),
                               np.asarray(lg_d[0, 0]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg_pk[0, 1]),
                               np.asarray(lg_p[1, 0]), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The pool updated in place vs the slice-per-layer formulation
# ---------------------------------------------------------------------------


def _slice_per_layer_run_stack(cfg, stack, stack_p, stack_c, x, *,
                               positions, cache_pos, mode, max_len, remat,
                               attend_cache=False, paged=None):
    """The layer scan as it ran before the paged pools rode in its
    carry: each layer's cache, pools included, goes in as the scan's xs
    (a slice of every stack) and comes back as its ys (dense models
    only: no aux loss, no expert counters)."""
    from repro.models import moe as MOE

    def body(xx, xs):
        p_r, c_r = xs
        new_cs = []
        for pi, spec in enumerate(stack.pattern):
            xx, nc, _, _ = T.layer_apply(
                cfg, spec, p_r[pi], xx, positions=positions,
                cache_pos=cache_pos, cache=c_r[pi], mode=mode,
                max_len=max_len, attend_cache=attend_cache, paged=paged)
            new_cs.append(nc)
        return xx, tuple(new_cs)

    x, new_cache = jax.lax.scan(body, x, (stack_p, stack_c))
    return x, new_cache, jnp.zeros((), jnp.float32), MOE.no_counts()


def _reset_and_copy_whole_stacks(cache, pages, src, dst, keep):
    """Page resets and copy-on-write tails as the slice-per-layer
    programs made them: scatters of whole page rows across every layer
    of each stack, before the forward."""
    def one(c):
        c = dict(c)
        c["ppos"] = c["ppos"].at[:, pages].set(-1)
        pos = c["ppos"][:, src]
        kept = (pos >= 0) & (pos < keep[None, :, None])
        c["ppos"] = c["ppos"].at[:, dst].set(jnp.where(kept, pos, -1))
        for k in KV.PAGED_DATA_KEYS:
            if k in c:
                c[k] = c[k].at[:, dst].set(c[k][:, src])
        return c
    return {"layers": tuple(tuple(one(c) for c in st)
                            for st in cache["layers"])}


@pytest.mark.parametrize("kernels", ["off", "interpret"])
@pytest.mark.parametrize("policy", ["fp32", "int8"], ids=["fp", "q8"])
def test_pool_in_place_matches_slice_per_layer(rng, monkeypatch, policy,
                                               kernels):
    """A packed forward (two decode lanes and an admitting slot's chunk,
    its fresh pages reset and its partial prefix page copied on write)
    and then a fused two-step decode, on a 3-layer stacked pool: the
    in-place programs leave the pool the slice-per-layer formulation
    leaves, bit for bit, with the same logits."""
    pol = INT8 if policy == "int8" else FP32
    base = get_reduced("unimo-text")
    cfg = base.replace(stacks=(dataclasses.replace(base.stacks[0],
                                                   repeats=3),))
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    page, num_pages, slots, max_len = 8, 15, 3, 48
    cache0 = T.init_paged_cache(cfg, num_pages=num_pages, page_size=page,
                                max_slots=slots, max_len=max_len,
                                dtype=jnp.float32, kv_dtype=pol.kv_dtype)
    # slots 0 and 1 hold 13 and 11 tokens; pages 6-8 hold a retired
    # request's stale entries, which slot 2 gets as its fresh pages
    bt = np.full((slots, 6), -1, np.int32)
    bt[0, :2], bt[1, :2], bt[2, :2] = [0, 1], [2, 3], [6, 7]
    toks = rng.integers(4, 400, size=(slots, 16)).astype(np.int32)
    _, cache0, _ = T.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray([13, 11, 16]),
        cache0, policy=pol, max_len=max_len, last_only=True,
        paged={"block_tables": jnp.asarray(bt),
               "active": jnp.ones((slots,), bool)})
    # slot 2 now shares slot 1's first 10 tokens: page 2 whole, page 3
    # up to position 10 copied into its fresh page 7, then its 5-token
    # chunk from position 10; its fresh page 6 holds stale positions
    # until reset (reset pages padded with the dump page)
    bt[2, :3] = [2, 7, 6]
    fresh = np.full((slots, 6), num_pages, np.int32)
    fresh[2, :2] = [7, 6]
    cow = (jnp.asarray([num_pages, num_pages, 3]),
           jnp.asarray([num_pages, num_pages, 7]), jnp.asarray([0, 0, 10]))
    T_ = 8
    stream = np.zeros(T_, np.int32)
    stream[:2] = [5, 6]
    stream[2:7] = toks[2, 10:15]
    slot_ids = np.asarray([0, 1, 2, 2, 2, 2, 2, -1], np.int32)
    positions = np.asarray([13, 11, 10, 11, 12, 13, 14, -1], np.int32)
    seg_last = np.asarray([0, 1, 6], np.int32)
    meta = DA.packed_meta_table(np.asarray([0, 1, 2]), np.asarray([1, 1, 5]),
                                np.arange(3, dtype=np.int32), T_,
                                T_ // DA.PACKED_BLOCK_Q + slots)
    paged = {"block_tables": jnp.asarray(bt), "packed_meta": jnp.asarray(meta)}

    def packed(cache, in_place):
        if in_place:
            cache = KV.reset_pages_all(cache, jnp.asarray(fresh))
            pg = {**paged, "cow": cow}
        else:
            cache = _reset_and_copy_whole_stacks(
                cache, jnp.asarray(fresh), *cow)
            pg = paged
        return T.forward_packed(
            params, cfg, jnp.asarray(stream[None]), cache,
            jnp.asarray(slot_ids), jnp.asarray(positions),
            jnp.asarray(seg_last), policy=pol, max_len=max_len,
            paged=pg)[:2]

    def decode(cache):
        def step(carry, _):
            tok, lens, cache = carry
            logits, cache, _ = T.forward_decode(
                params, cfg, tok[:, None], cache, lens, policy=pol,
                max_len=max_len,
                paged={"block_tables": jnp.asarray(bt),
                       "active": jnp.ones((slots,), bool)})
            nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            return (nxt, lens + 1, cache), logits
        (_, _, cache), logits = jax.lax.scan(
            step, (jnp.asarray([7, 8, 9], jnp.int32),
                   jnp.asarray([14, 12, 15], jnp.int32), cache), None,
            length=2)
        return logits, cache

    runs = {}
    with KOPS.kernel_mode_ctx(kernels):
        for in_place in (True, False):
            with monkeypatch.context() as m:
                if not in_place:
                    m.setattr(T, "_run_stack", _slice_per_layer_run_stack)
                lg_p, cache = jax.jit(lambda c: packed(c, in_place))(cache0)
                lg_d, cache = jax.jit(decode)(cache)
            runs[in_place] = (lg_p, lg_d, cache)
    for a, b in zip(jax.tree.leaves(runs[True]), jax.tree.leaves(runs[False])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the copy-on-write page holds slot 1's first 10 positions, in layers
    # the formulations share
    assert np.asarray(runs[True][2]["layers"][0][0]["ppos"])[:, 7, :2] \
        .tolist() == [[8, 9]] * 3


# ---------------------------------------------------------------------------
# pack_batch property tests: packing preserves the plan verbatim
# ---------------------------------------------------------------------------


def _pack_invariant_trace(seed: int):
    rng = np.random.default_rng(seed)
    slots = int(rng.integers(2, 6))
    budget = int(rng.integers(slots, 40))
    sched = ContinuousScheduler(slots, PageAllocator(64), page_size=8,
                                max_pages_per_slot=16)
    for uid in range(int(rng.integers(3, 12))):
        sched.submit(Request(uid=uid,
                             tokens=list(map(int, rng.integers(
                                 1, 900, size=int(rng.integers(1, 50))))),
                             max_new_tokens=int(rng.integers(1, 6))))
    pending = rng.integers(1, 900, size=slots).astype(np.int32)
    lengths = rng.integers(1, 64, size=slots).astype(np.int32)
    iters = 0
    while sched.has_work():
        iters += 1
        assert iters < 5000
        while sched.try_admit() is not None:
            pass
        plan = sched.next_batch(budget)
        width = max(8, 1 << (plan.total_tokens - 1).bit_length())
        pb = sched.pack_batch(plan, pending, lengths, width)
        # stream totals track the plan: budget is a hard ceiling
        assert pb.n_tokens == plan.total_tokens <= budget
        assert pb.n_segments == len(plan.decode_slots) + len(plan.chunks)
        assert pb.n_decode == len(plan.decode_slots)
        # decode lanes first, in plan order, one token each
        for i, s in enumerate(plan.decode_slots):
            assert pb.seg_slots[i] == s and pb.seg_len[i] == 1
            assert pb.slot_ids[pb.seg_start[i]] == s
            assert pb.tokens[pb.seg_start[i]] == pending[s]
            assert pb.positions[pb.seg_start[i]] == lengths[s]
        # then FCFS chunks, contiguous positions, prompt tokens verbatim
        for j, c in enumerate(plan.chunks):
            i = pb.n_decode + j
            st = sched.slots[c.slot]
            s0, ln = pb.seg_start[i], pb.seg_len[i]
            assert pb.seg_slots[i] == c.slot and ln == c.length
            assert pb.last_idx[i] == s0 + ln - 1
            assert (pb.slot_ids[s0:s0 + ln] == c.slot).all()
            np.testing.assert_array_equal(
                pb.positions[s0:s0 + ln],
                np.arange(c.start, c.start + c.length))
            np.testing.assert_array_equal(
                pb.tokens[s0:s0 + ln],
                st.ctx[c.start:c.start + c.length])
        # chunk segments keep admission (FCFS) order
        seqs = [sched.slots[c.slot].admit_seq for c in plan.chunks]
        assert seqs == sorted(seqs)
        # segments are contiguous and padding lanes are inert
        starts = [pb.seg_start[i] for i in range(pb.n_segments)]
        assert starts == sorted(starts)
        assert (pb.slot_ids[pb.n_tokens:] == -1).all()
        assert (pb.positions[pb.n_tokens:] == -1).all()
        for c in plan.chunks:                 # apply the plan
            sched.slots[c.slot].prefill_pos += c.length
        for s in plan.decode_slots:
            st = sched.slots[s]
            st.emitted.append(7)
            if len(st.emitted) >= st.request.max_new_tokens:
                sched.retire(s)
    sched.allocator.check()


def test_pack_batch_invariants_seeded():
    for seed in range(50):
        _pack_invariant_trace(seed)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                           # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=50)
    @given(st.integers(0, 10_000))
    def test_pack_batch_invariants_hypothesis(seed):
        _pack_invariant_trace(seed)


# ---------------------------------------------------------------------------
# Greedy parity sweep: packed == bucketed execution, one dispatch/iter
# ---------------------------------------------------------------------------


def _requests(rng, cfg, lens_new, prefix=None):
    prefix = prefix or []
    return [Request(uid=i,
                    tokens=[2] + prefix + list(map(int, rng.integers(
                        4, min(cfg.vocab_size, 400), size=ln))),
                    max_new_tokens=mn)
            for i, (ln, mn) in enumerate(lens_new)]


def _serve(eng, reqs, **kw):
    done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   max_batched_tokens=16,
                                   chunked_prefill=True, **kw)
    return {r.uid: r.result for r in done}, m


@pytest.mark.parametrize("mode", ["plain", "prefix", "int8", "spec",
                                  "preempt"])
def test_packed_vs_bucketed_bit_identical(rng, mode):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    policy = INT8 if mode == "int8" else FP32
    prefix = list(map(int, rng.integers(4, 400, size=16))) \
        if mode == "prefix" else None
    shapes = [(14, 6), (25, 6), (9, 5), (19, 6)] if prefix \
        else [(30, 6), (28, 6), (26, 5), (22, 6), (9, 5)]
    reqs = _requests(rng, cfg, shapes, prefix=prefix)
    kw = {}
    if mode == "spec":
        from repro.core.speculative import SpecConfig
        kw["spec"] = SpecConfig(k=3, drafter="ngram")
    if mode == "preempt":
        kw.update(num_pages=11, preemption="lru", host_kv_bytes=1 << 30,
                  debug_audit=True)

    def eng():
        return InferenceEngine(cfg, params, policy=policy, max_len=64,
                               max_batch=3)

    base, mb = _serve(eng(), reqs, packed=False, **kw)
    done, mp = _serve(eng(), reqs, packed=True, **kw)
    for uid, out in done.items():
        assert out == base[uid], f"packed diverged ({mode}, uid {uid})"
    # packed: exactly ONE device dispatch per mixed iteration; bucketed:
    # one per chunk plus the decode micro-step
    assert mp.mixed_iters > 0
    assert mp.dispatches_per_iter == 1.0
    assert mb.dispatches_per_iter > 1.0
    assert 0.0 <= mp.padded_token_frac < 1.0
    assert mp.packed_tokens_real > 0
    assert mb.packed_tokens_real == 0      # bucketed leg never packs
    if mode == "preempt":
        assert mp.preemptions >= 1


def test_packed_kernel_interpret_matches_fallback(rng):
    """The packed Pallas kernel (interpret mode) must not change greedy
    outputs vs the per-lane gather + jnp fallback."""
    from repro.kernels import ops as KOPS
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, cfg, [(19, 4), (27, 4)])
    base, _ = _serve(InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                     max_batch=2), reqs)
    with KOPS.kernel_mode_ctx("interpret"):
        done, _ = _serve(InferenceEngine(cfg, params, policy=FP32,
                                         max_len=64, max_batch=2), reqs)
    for uid, out in done.items():
        assert out == base[uid]


def test_packed_optout_without_chunking_warns(rng):
    """packed=True on a family without chunked-prefill support warns
    and serves bucketed, exactly."""
    cfg = get_reduced("gemma2-2b")            # sliding-window ring
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _requests(rng, cfg, [(9, 4), (17, 4)])
    base, _ = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                              max_batch=2).serve_continuous(
        copy.deepcopy(reqs), page_size=8, chunked_prefill=False)
    base = {r.uid: r.result for r in base}
    with pytest.warns(UserWarning, match="packed execution requested"):
        done, m = InferenceEngine(cfg, params, policy=FP32, max_len=64,
                                  max_batch=2).serve_continuous(
            copy.deepcopy(reqs), page_size=8, packed=True)
    assert m.scheduler == "bucketed" and m.packed_tokens_padded == 0
    for r in done:
        assert r.result == base[r.uid]


# ---------------------------------------------------------------------------
# Program reuse: each step program is built and compiled once per engine
# ---------------------------------------------------------------------------


class _CompileEvents:
    """Counts JAX's trace, lowering and backend-compile events while
    entered (the events the benchmark's set-up log sums)."""

    NAMES = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.counts = dict.fromkeys(self.NAMES, 0)

    def _on(self, event, _seconds, **_):
        if event in self.counts:
            self.counts[event] += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


@pytest.mark.parametrize("mode", ["plain", "int8", "moe"])
def test_second_serve_call_builds_nothing(rng, mode):
    """A second serve call at the same geometry, whose packed widths the
    first call already used, reuses every jitted program and the paged
    context: nothing is traced, lowered or compiled."""
    arch = "qwen3-30b-a3b" if mode == "moe" else "qwen3-4b"
    cfg = get_reduced(arch)
    policy = dataclasses.replace(FP32, weights_dtype="int8",
                                 kv_dtype="int8") if mode == "int8" else FP32
    eng = InferenceEngine(cfg, T.init_params(jax.random.PRNGKey(0), cfg),
                          policy=policy, max_len=64, max_batch=3)
    shapes = [(30, 5), (22, 6), (9, 4)]

    def serve():
        tr = ServeTracer()
        _serve(eng, _requests(rng, cfg, shapes), trace=tr)
        return {e["width_bucket"] for e in tr.iter_events("iteration")
                if e["chunk_segments"]}

    widths = serve()
    fns, ctx = dict(eng._cont_cache), eng._paged_ctx
    packed = fns[("packed", SamplingParams())]
    step = fns[(SamplingParams(), 4)][2]
    n_packed = packed._cache_size()
    with _CompileEvents() as ev:
        again = serve()
    assert again <= widths
    assert eng._cont_cache.keys() == fns.keys()
    assert all(eng._cont_cache[k] is v for k, v in fns.items())
    assert eng._paged_ctx is ctx
    assert step._cache_size() == 1
    assert packed._cache_size() == n_packed
    assert ev.counts == dict.fromkeys(_CompileEvents.NAMES, 0), ev.counts


def test_warm_up_recipe_compiles_exactly_the_ladder(rng):
    """The benchmark's warm-up, one lone prompt per stream width of the
    budget's ladder with two tokens to generate, leaves one packed
    program per width, one fused decode program, and no admission or
    verify program."""
    cfg = get_reduced("qwen3-4b")
    eng = InferenceEngine(cfg, T.init_params(jax.random.PRNGKey(0), cfg),
                          policy=FP32, max_len=64, max_batch=3)
    budget = 32
    widths = packed_width_buckets(budget)
    for w in widths:
        req = Request(uid=0, tokens=[int(t) for t in rng.integers(4, 400, w)],
                      max_new_tokens=2)
        eng.serve_continuous([req], page_size=8, steps_per_sync=4,
                             max_batched_tokens=budget)
    sp = SamplingParams()
    fns = eng._cont_cache
    admit, admit_prefix, step = fns[(sp, 4)]
    assert fns[("packed", sp)]._cache_size() == len(widths)
    assert step._cache_size() == 1
    assert admit._cache_size() == admit_prefix._cache_size() == 0
    assert set(fns) == {("packed", sp), (sp, 4)}


# ---------------------------------------------------------------------------
# Metrics: host/device split + packed derivations, zero-guarded
# ---------------------------------------------------------------------------


def test_packed_metrics_zero_guards():
    m = ServeMetrics()
    assert m.host_frac == 0.0
    assert m.dispatches_per_iter == 0.0
    assert m.padded_token_frac == 0.0


def test_packed_metrics_derivations():
    m = ServeMetrics(host_s=1.0, device_s=3.0, mixed_iters=4,
                     mixed_dispatches=4, packed_tokens_real=90,
                     packed_tokens_padded=100)
    assert m.host_frac == pytest.approx(0.25)
    assert m.dispatches_per_iter == 1.0
    assert m.padded_token_frac == pytest.approx(0.1)


def test_host_device_split_recorded(rng):
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(cfg, params, policy=FP32, max_len=64, max_batch=2)
    reqs = _requests(rng, cfg, [(9, 5), (21, 5)])
    _, m = _serve(eng, reqs)
    assert m.device_s > 0.0 and m.host_s >= 0.0
    assert 0.0 <= m.host_frac < 1.0
