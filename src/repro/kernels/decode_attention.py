"""Decode attention Pallas TPU kernel: one query token vs a long KV cache.

This is the kernel form of the paper's K-V-cache pillar (P1): at each
decode step only the new token's attention is computed, streaming cache
blocks HBM->VMEM.  Unlike the prefill kernel, all query heads of a batch
element are processed together (the single query row would waste the MXU
otherwise):

  grid = (B, num_k_blocks)   (k innermost, sequential)

Per step: q tile (Hq, D) stays resident; one (block_k, Hkv, D) cache tile
streams in; GQA grouping is a reshape of the q rows (Hkv, g, D) batched
against the tile.  Running softmax state (m, l, acc) lives in VMEM scratch.

The quantized-pool variant (``paged_decode_attention_q8``) streams int8
K/V pages plus their per-entry fp32 scale rows and dequantizes
*in-register* to fp32 right before QK^T / PV — halving the HBM bytes per
decode step versus bf16 pages while the matmuls still accumulate in fp32.

The paged kernels walk a block table, and each item streams and
computes only its ``n_live`` live pages (:func:`live_pages`, from the
item's largest query position, passed by scalar prefetch).  The decode
and mixed kernels' grid is (items, npages) with ``npages =
block_tables.shape[1]``: on the dead steps past the live pages the index
maps repeat the last live page, so the pipeline fetches nothing, and the
body is skipped; ``_init`` and ``_finish`` still run at the first and
last step, so the grid and the output contract are those of a full
walk.  The packed kernel's grid is its work rows alone: each row copies
its live pages from HBM itself, in blocks of :func:`packed_block_pages`
pages, double-buffered across blocks and rows.  A skipped page lies
wholly beyond the item's queries, and a fully masked page leaves the
online softmax state exactly as it was, so the outputs are the full
walk's.

The paged kernels read the pool in place from the whole layer stack a
serving layer scan carries: pools ``(L, P, page, Hkv, D)``, positions
``(L, P, page)`` and scales ``(L, P, page, Hkv)``, with the layer to read
passed as one more scalar-prefetch operand, so no layer's slice of the
stack is copied out for the kernel.  Given ``layer=None`` they take one
layer's pools, as a stack of one.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 256


def _attend_block_mq(qg, k, v, mask, m_scr, l_scr, acc_scr, *, scale,
                     attn_softcap):
    """One online-softmax accumulation step shared by every decode /
    verify kernel: ``nq`` query rows per kv head against one fp32 K/V
    tile, each query under its own key mask (causal masking *inside* a
    speculation window is per-query).

    qg: (Hkv, nq, g, D); k/v: (bk, Hkv, D[v]); mask: (nq, bk) bool.
    Scratch state is flattened over (nq, g): m/l (Hkv, nq*g) and acc
    (Hkv, nq*g, Dv) — the single-query kernels are the nq == 1 case.
    """
    Hkv, nq, g, D = qg.shape
    bk = k.shape[0]
    q2 = qg.reshape(Hkv, nq * g, D)
    # (Hkv, nq*g, D) x (bk, Hkv, D) -> (Hkv, nq*g, bk)
    logits = jax.lax.dot_general(
        q2, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    if attn_softcap is not None:
        logits = jnp.tanh(logits / attn_softcap) * attn_softcap
    mask4 = jnp.broadcast_to(mask[None, :, None, :], (Hkv, nq, g, bk)) \
        .reshape(Hkv, nq * g, bk)
    logits = jnp.where(mask4, logits, -jnp.inf)

    m_prev = m_scr[...]                                    # (Hkv, nq*g)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(mask4, p, 0.0)

    # (Hkv, nq*g, bk) x (bk, Hkv, Dv) -> (Hkv, nq*g, Dv)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    l_scr[...] = l_scr[...] * alpha + p.sum(-1)
    m_scr[...] = m_new


def _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, *, scale,
                  attn_softcap, g):
    """Single-query case: q (Hq, D) under one (1, bk) key mask."""
    Hq, D = q.shape
    Hkv = k.shape[1]
    _attend_block_mq(q.reshape(Hkv, 1, g, D), k, v, mask,
                     m_scr, l_scr, acc_scr, scale=scale,
                     attn_softcap=attn_softcap)


# pages whose stored positions one streamed block holds: a kernel reads
# the positions transposed, (L, page, P), as the pool keeps them (pages
# minor, its TPU default layout), and picks its page's column out
POS_LANES = 128


def _stack(layer, pools):
    """The layer to read as a (1,) int32 scalar-prefetch operand, and
    the pools as whole layer stacks: a single layer's pools (``layer``
    None) become a stack of one."""
    if layer is None:
        return jnp.zeros((1,), jnp.int32), [p[None] for p in pools]
    return jnp.reshape(layer, (1,)).astype(jnp.int32), list(pools)


def _pool_specs(kpool, vpool, k_scale, page_of):
    """BlockSpecs of the stacked pool operands in the kernels' order: k,
    [k scale,] v, [v scale,] positions (transposed, :func:`_positions`).
    Each streams page ``page_of(i, j, *prefetch)`` of layer ``ly[0]``;
    the positions come as the (page, POS_LANES) block that holds that
    page's column."""
    page, Hkv, D = kpool.shape[-3:]
    Dv = vpool.shape[-1]

    def spec(*block):
        return pl.BlockSpec(
            (None, 1) + block,
            lambda i, j, ly, *pf: (ly[0], page_of(i, j, *pf))
            + (0,) * len(block))

    scale = [spec(page, k_scale.shape[-1])] if k_scale is not None else []
    pos = pl.BlockSpec(
        (None, page, POS_LANES),
        lambda i, j, ly, *pf: (ly[0], 0, page_of(i, j, *pf) // POS_LANES))
    return [spec(page, Hkv, D)] + scale + [spec(page, Hkv, Dv)] + scale \
        + [pos]


def _positions(ppos):
    """(L, P, page) -> (L, page, P): the positions operand, in the
    layout a TPU keeps ``ppos`` in (pages minor), so this transpose is
    free."""
    return jnp.swapaxes(ppos, -1, -2)


def _page_pos(kp_ref, bt_ref, row, j, n_live, dump):
    """(1, page) stored positions of the page streamed at step j: its
    column of the (page, POS_LANES) block, as a row."""
    c = _live_page_id(bt_ref, row, j, n_live, dump) % POS_LANES
    cols = kp_ref[...].T                                   # (lanes, page)
    pick = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 0) == c
    return jnp.sum(jnp.where(pick, cols, 0), axis=0, keepdims=True)


def _page_kv(q_ref, k_ref, v_ref, ks_ref=None, vs_ref=None):
    """The streamed page's K and V as float32 (dequantized with their
    scale rows for an int8 pool).  A pool widened to whole lanes
    (``kv_cache.paged_layer_cache_shape``) holds zeros past each row's
    head dim and heads: K is cut to the query's head dim and the scale
    rows to K's heads; V keeps the pool's width, and the caller cuts the
    output."""
    d, hkv = q_ref.shape[-1], k_ref.shape[-2]
    k = k_ref[0, :, :, :d].astype(jnp.float32)             # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32)                       # (page, Hkv, Dv)
    if ks_ref is not None:
        k = k * ks_ref[0, :, :hkv].astype(jnp.float32)[..., None]
        v = v * vs_ref[0, :, :hkv].astype(jnp.float32)[..., None]
    return k, v


def live_pages(max_qpos, page: int, npages: int):
    """The live-page bound of each grid item: how many logical pages can
    hold a key its queries attend.  Logical page j holds positions
    ``j*page .. j*page+page-1`` (modulo a ring layer's ring, whose every
    page is live once it has wrapped), so a query at position p sees
    pages ``0 .. p // page``.  max_qpos: (items,) int the item's largest
    query position, < 0 where it has no query (0 live pages)."""
    return jnp.where(max_qpos >= 0,
                     jnp.minimum(max_qpos // page + 1, npages),
                     0).astype(jnp.int32)


def _live_page_id(bt, row, j, n_live, dump):
    """Physical page an index map streams at page step j of an item with
    ``n_live`` live pages on block-table row ``row``: page j while it is
    live, then the last live page again — a block index equal to the
    previous step's is not fetched again, so dead steps move no bytes.
    The dump page where the item has no live page or the entry is
    unallocated."""
    pid = bt[row, jnp.minimum(j, jnp.maximum(n_live - 1, 0))]
    return jnp.where((n_live == 0) | (pid < 0), dump, pid)


def _row_live_pages(q_pos, block_tables, page: int):
    """:func:`live_pages` of each block-table row's query window (the
    decode and mixed kernels): its largest query position.  A row whose
    first page is unallocated holds no context (tables fill from logical
    page 0), so it has no live page: an empty slot's lane streams
    nothing."""
    B, npages = block_tables.shape
    max_qpos = jnp.where(block_tables[:, 0] >= 0,
                         q_pos.reshape(B, -1).max(axis=1), -1)
    return live_pages(max_qpos, page, npages)


def _on_live_page(j, n_live):
    """Guard of a page step's body (dequant, matmuls, softmax update):
    it runs only while j < n_live; dead steps leave the state as is."""
    return pl.when(j < n_live)


def shape_supported(q, k, block_k: int = DEFAULT_BLOCK_K) -> bool:
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    return (Sq == 1 and Hq % Hkv == 0 and D % 8 == 0
            and k.shape[3] % 8 == 0 and Sk % min(block_k, Sk) == 0)


def _kernel(q_ref, k_ref, v_ref, kp_ref, qp_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, attn_softcap, window, nk, g):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (Hq, D)
    k = k_ref[0].astype(jnp.float32)                       # (bk, Hkv, D)
    v = v_ref[0].astype(jnp.float32)                       # (bk, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, bk)
    qp = qp_ref[0]                                         # (1, 1)

    mask = _mq_mask(kp, qp, True, window)                  # (1, bk)
    _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                  attn_softcap=attn_softcap, g=g)

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom).reshape(q.shape[0], acc_scr.shape[-1])
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_shape_supported(q, kpool, block_tables) -> bool:
    B, Sq, Hq, D = q.shape
    page, Hkv = kpool.shape[-3], kpool.shape[-2]
    return (Sq == 1 and Hq % Hkv == 0 and D % 8 == 0
            and kpool.shape[-1] % 8 == 0 and page % 8 == 0
            and block_tables.shape[0] == B)


def _paged_kernel(ly_ref, nl_ref, bt_ref, q_ref, k_ref, v_ref, kp_ref,
                  qp_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                  attn_softcap, window, npages, dump, g):
    """Same online-softmax scheme as _kernel, but the grid walks the
    slot's block table: page j streams physical page bt[b, j] from the
    pool (the BlockSpec index_map does the indirection; bt itself arrives
    via scalar prefetch).  Unallocated entries resolve to the dump page,
    whose positions are always -1, so masking alone keeps them out.
    Only the slot's nl[b] live pages are streamed and attended."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @_on_live_page(j, nl_ref[b])
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                # (Hq, D)
        k, v = _page_kv(q_ref, k_ref, v_ref)             # (page, Hkv, D[v])
        kp = _page_pos(kp_ref, bt_ref, b, j, nl_ref[b], dump)  # (1, page)
        qp = qp_ref[0]                                     # (1, 1)
        mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)  # (1, page)
        _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                      attn_softcap=attn_softcap, g=g)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom).reshape(q_ref.shape[2],
                                             acc_scr.shape[-1])
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_decode_attention(q, kpool, vpool, ppos, block_tables, q_pos,
                           layer=None, *, window: Optional[int],
                           scale: float,
                           attn_softcap: Optional[float] = None,
                           interpret: bool = False):
    """Decode attention over a paged KV pool.

    q: (B,1,Hq,D); kpool/vpool: (L,P,page,Hkv,D[v]); ppos: (L,P,page)
    absolute positions (-1 empty), read at layer ``layer`` (or one
    layer's (P,page,...) pools with ``layer`` None); block_tables:
    (B,npages) physical page ids with -1 = unallocated; q_pos: (B,1).
    Page P-1 is the dump page.

    The grid spans (B, npages), but slot b streams and attends only its
    ``n_live`` live pages (``q_pos[b] // page + 1``; none for a row with
    no first page); later steps fetch nothing and compute nothing.
    """
    B, _, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape[-4:]
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1
    ly, (kpool, vpool, ppos) = _stack(layer, (kpool, vpool, ppos))

    n_live = _row_live_pages(q_pos, block_tables, page)

    # index maps take the scalar-prefetch refs (n_live, block tables)
    def page_of(b, j, nl, bt):
        return _live_page_id(bt, b, j, nl[b], dump)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, dump=dump, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, 1, Hq, D), lambda b, j, *pf: (b, 0, 0, 0)),
            *_pool_specs(kpool, vpool, None, page_of),
            pl.BlockSpec((1, 1, 1), lambda b, j, *pf: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hq, Dv), lambda b, j, *pf: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, Hq, Dv), q.dtype),
        interpret=interpret,
    )(ly, n_live, block_tables, q, kpool, vpool, _positions(ppos),
      q_pos.reshape(B, -1, 1))
    return out


def _paged_kernel_q8(ly_ref, nl_ref, bt_ref, q_ref, k_ref, ks_ref, v_ref,
                     vs_ref, kp_ref, qp_ref, o_ref, m_scr, l_scr, acc_scr, *,
                     scale, attn_softcap, window, npages, dump, g):
    """Quantized-pool variant of _paged_kernel: the page tile arrives as
    int8 codes plus a per-entry (page, Hkv) fp32 scale row, and the
    dequantize (code * scale) happens in-register before QK^T / PV — the
    HBM stream is half the bf16 bytes, the math is still fp32."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @_on_live_page(j, nl_ref[b])
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                # (Hq, D)
        k, v = _page_kv(q_ref, k_ref, v_ref, ks_ref, vs_ref)
        kp = _page_pos(kp_ref, bt_ref, b, j, nl_ref[b], dump)  # (1, page)
        qp = qp_ref[0]                                     # (1, 1)
        mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)  # (1, page)
        _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                      attn_softcap=attn_softcap, g=g)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom).reshape(q_ref.shape[2],
                                             acc_scr.shape[-1])
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_decode_attention_q8(q, kpool, k_scale, vpool, v_scale, ppos,
                              block_tables, q_pos, layer=None, *,
                              window: Optional[int], scale: float,
                              attn_softcap: Optional[float] = None,
                              interpret: bool = False):
    """Decode attention over an int8-quantized paged KV pool.

    Same contract as :func:`paged_decode_attention` plus the parallel
    scale pools: kpool/vpool are (L,P,page,Hkv,D[v]) int8 codes and
    k_scale/v_scale are (L,P,page,Hkv) fp32 per-entry absmax scales.
    Dequantization is fused into the page stream (in-register, before
    the matmuls).  Like the fp entry, each slot streams and dequantizes
    only its live pages of the (B, npages) grid."""
    B, _, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape[-4:]
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1
    ly, (kpool, k_scale, vpool, v_scale, ppos) = _stack(
        layer, (kpool, k_scale, vpool, v_scale, ppos))

    n_live = _row_live_pages(q_pos, block_tables, page)

    # index maps take the scalar-prefetch refs (n_live, block tables)
    def page_of(b, j, nl, bt):
        return _live_page_id(bt, b, j, nl[b], dump)

    kernel = functools.partial(_paged_kernel_q8, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, dump=dump, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, 1, Hq, D), lambda b, j, *pf: (b, 0, 0, 0)),
            *_pool_specs(kpool, vpool, k_scale, page_of),
            pl.BlockSpec((1, 1, 1), lambda b, j, *pf: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hq, Dv), lambda b, j, *pf: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, Hq, Dv), q.dtype),
        interpret=interpret,
    )(ly, n_live, block_tables, q, kpool, k_scale, vpool, v_scale,
      _positions(ppos), q_pos.reshape(B, -1, 1))
    return out


def paged_mixed_shape_supported(q, kpool, block_tables) -> bool:
    B, Sq, Hq, D = q.shape
    page, Hkv = kpool.shape[-3], kpool.shape[-2]
    return (Sq >= 1 and Hq % Hkv == 0 and D % 8 == 0
            and kpool.shape[-1] % 8 == 0 and page % 8 == 0
            and block_tables.shape[0] == B)


# verify is the all-rows-full special case of the mixed entry below
paged_verify_shape_supported = paged_mixed_shape_supported


def _mq_mask(kp, qp, allocated, window):
    """(W, page) per-query key mask for one streamed page tile, from the
    tile's stored positions kp (1, page) on the lane axis and the query
    positions qp (W, 1) on the sublane axis: causal
    against the stored absolute positions — which the mixed/verify
    forward has just written for the window's own tokens too, so query j
    attends tokens 1..j-1 of its window (causality *inside* a prefill
    chunk or speculation window) for free.  A padding query carries
    qp == -1: no key satisfies ``kp <= -1 & kp >= 0``, so its row is
    fully masked and the kernel's zero-denominator guard emits zeros."""
    mask = (kp <= qp) & (kp >= 0) & allocated
    if window is not None:
        mask &= kp > (qp - window)
    return mask


def _paged_mixed_kernel(ly_ref, nl_ref, bt_ref, q_ref, k_ref, v_ref, kp_ref,
                        qp_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                        attn_softcap, window, npages, dump, g):
    """Multi-query-per-slot variant of _paged_kernel: all W query
    positions of a slot's window (prefill chunk, speculation window, or
    a single decode token plus padding) stream the slot's pages ONCE
    (the block-table indirection and online-softmax scheme are
    identical; scratch carries an extra query dim folded into g)."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _, K1, Hq, D = q_ref.shape
    Hkv = k_ref.shape[2]

    @_on_live_page(j, nl_ref[b])
    def _step():
        q = q_ref[0].astype(jnp.float32)                   # (K1, Hq, D)
        k, v = _page_kv(q_ref, k_ref, v_ref)             # (page, Hkv, D[v])
        kp = _page_pos(kp_ref, bt_ref, b, j, nl_ref[b], dump)  # (1, page)
        qp = qp_ref[0]                                     # (K1, 1)
        mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)
        qg = q.reshape(K1, Hkv, g, D).transpose(1, 0, 2, 3)  # (Hkv,K1,g,D)
        _attend_block_mq(qg, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                         attn_softcap=attn_softcap)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom) \
            .reshape(Hkv, K1, g, acc_scr.shape[-1]) \
            .transpose(1, 0, 2, 3).reshape(K1, Hq, acc_scr.shape[-1])
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_mixed_attention(q, kpool, vpool, ppos, block_tables, q_pos,
                          layer=None, *, window: Optional[int], scale: float,
                          attn_softcap: Optional[float] = None,
                          interpret: bool = False):
    """Variable-length mixed-batch attention over a paged KV pool: up to
    W query positions per slot in one kernel pass, with *per-slot query
    counts* — 1 real query for decode rows, chunk-length queries for
    chunked-prefill rows, K+1 for speculative verify windows.

    Same contract as :func:`paged_decode_attention` with the query dim
    widened: q (B, W, Hq, D), q_pos (B, W) absolute positions where
    **-1 marks a padding query** (its output lane is zeros; callers
    discard it).  The window's own K/V must already be in the pool
    (written by ``kv_cache.paged_write_decode_multi``); stored positions
    make the per-query causal mask exact inside the window, so any
    chunk boundary is legal.  The grid spans (B, npages), but row b
    streams and attends only the live pages of its largest query
    position (:func:`live_pages`).  Pools are stacked and read at
    ``layer`` as in :func:`paged_decode_attention`.
    """
    B, K1, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape[-4:]
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1
    ly, (kpool, vpool, ppos) = _stack(layer, (kpool, vpool, ppos))

    n_live = _row_live_pages(q_pos, block_tables, page)

    # index maps take the scalar-prefetch refs (n_live, block tables)
    def page_of(b, j, nl, bt):
        return _live_page_id(bt, b, j, nl[b], dump)

    kernel = functools.partial(_paged_mixed_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, dump=dump, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, K1, Hq, D), lambda b, j, *pf: (b, 0, 0, 0)),
            *_pool_specs(kpool, vpool, None, page_of),
            pl.BlockSpec((1, K1, 1), lambda b, j, *pf: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K1, Hq, Dv),
                               lambda b, j, *pf: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, K1 * g), jnp.float32),
            pltpu.VMEM((Hkv, K1 * g), jnp.float32),
            pltpu.VMEM((Hkv, K1 * g, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K1, Hq, Dv), q.dtype),
        interpret=interpret,
    )(ly, n_live, block_tables, q, kpool, vpool, _positions(ppos),
      q_pos.reshape(B, -1, 1))
    return out


# speculative verify = the mixed entry with every row's window full
paged_verify_attention = paged_mixed_attention


def _paged_mixed_kernel_q8(ly_ref, nl_ref, bt_ref, q_ref, k_ref, ks_ref,
                           v_ref, vs_ref, kp_ref, qp_ref, o_ref, m_scr,
                           l_scr, acc_scr, *, scale, attn_softcap, window,
                           npages, dump, g):
    """Quantized-pool mixed kernel: int8 page tiles + per-entry scale
    rows dequantized in-register (exactly _paged_kernel_q8's stream)
    feeding the multi-query online-softmax body."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _, K1, Hq, D = q_ref.shape
    Hkv = k_ref.shape[2]

    @_on_live_page(j, nl_ref[b])
    def _step():
        q = q_ref[0].astype(jnp.float32)                   # (K1, Hq, D)
        k, v = _page_kv(q_ref, k_ref, v_ref, ks_ref, vs_ref)
        kp = _page_pos(kp_ref, bt_ref, b, j, nl_ref[b], dump)  # (1, page)
        qp = qp_ref[0]                                     # (K1, 1)
        mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)
        qg = q.reshape(K1, Hkv, g, D).transpose(1, 0, 2, 3)
        _attend_block_mq(qg, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                         attn_softcap=attn_softcap)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom) \
            .reshape(Hkv, K1, g, acc_scr.shape[-1]) \
            .transpose(1, 0, 2, 3).reshape(K1, Hq, acc_scr.shape[-1])
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_mixed_attention_q8(q, kpool, k_scale, vpool, v_scale, ppos,
                             block_tables, q_pos, layer=None, *,
                             window: Optional[int], scale: float,
                             attn_softcap: Optional[float] = None,
                             interpret: bool = False):
    """:func:`paged_mixed_attention` over an int8-quantized pool (same
    scale-pool contract as :func:`paged_decode_attention_q8`; q_pos of
    -1 marks padding queries exactly like the fp entry, and each row
    streams only its live pages)."""
    B, K1, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape[-4:]
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1
    ly, (kpool, k_scale, vpool, v_scale, ppos) = _stack(
        layer, (kpool, k_scale, vpool, v_scale, ppos))

    n_live = _row_live_pages(q_pos, block_tables, page)

    # index maps take the scalar-prefetch refs (n_live, block tables)
    def page_of(b, j, nl, bt):
        return _live_page_id(bt, b, j, nl[b], dump)

    kernel = functools.partial(_paged_mixed_kernel_q8, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, dump=dump, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, K1, Hq, D), lambda b, j, *pf: (b, 0, 0, 0)),
            *_pool_specs(kpool, vpool, k_scale, page_of),
            pl.BlockSpec((1, K1, 1), lambda b, j, *pf: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K1, Hq, Dv),
                               lambda b, j, *pf: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, K1 * g), jnp.float32),
            pltpu.VMEM((Hkv, K1 * g), jnp.float32),
            pltpu.VMEM((Hkv, K1 * g, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K1, Hq, Dv), q.dtype),
        interpret=interpret,
    )(ly, n_live, block_tables, q, kpool, k_scale, vpool, v_scale,
      _positions(ppos), q_pos.reshape(B, -1, 1))
    return out


paged_verify_attention_q8 = paged_mixed_attention_q8


PACKED_BLOCK_Q = 8
# the whole (1, T, Hq, D[v]) query/output blocks stay VMEM-resident
# (constant index maps), so T is capped by a VMEM budget, not the grid
PACKED_VMEM_BYTES = 8 * 1024 * 1024
# the packed kernel fetches a work row's live pages itself, BP pages at a
# time into one of two buffer sets (the next block is in flight while
# this one is read); both sets together stay within this share of VMEM
PACKED_PAGE_BUFFER_BYTES = 4 * 1024 * 1024
# the packed kernel's scoped VMEM: the resident blocks, both page buffer
# sets and a block's float32 K/V tiles (a v5e core has 128 MiB)
PACKED_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def paged_packed_shape_supported(q, kpool, block_tables,
                                 meta=None) -> bool:
    _, T, Hq, D = q.shape
    page, Hkv, Dv = kpool.shape[-3:]
    return (q.shape[0] == 1 and T >= PACKED_BLOCK_Q
            and T % PACKED_BLOCK_Q == 0 and Hq % Hkv == 0
            and D % 8 == 0 and Dv % 8 == 0 and page % 8 == 0
            and T * Hq * (D + Dv) * 4 <= PACKED_VMEM_BYTES)


def packed_meta_table(seg_starts, seg_lengths, seg_slots, n_tokens,
                      n_work):
    """Host-side helper: cut each packed segment into PACKED_BLOCK_Q-wide
    query windows and emit the (n_work, 4) int32 work table the packed
    kernel walks — rows ``(slot, tile_start, win_start, win_end)`` in
    global stream coordinates, where tile_start is the window's start
    clamped to ``n_tokens - PACKED_BLOCK_Q`` so the fixed-width q tile
    never reads past the stream.  Unused rows carry slot = -1 (fully
    masked no-ops)."""
    import numpy as np
    bq = PACKED_BLOCK_Q
    meta = np.full((n_work, 4), -1, np.int32)
    meta[:, 1:] = 0
    w = 0
    for s0, ln, slot in zip(seg_starts, seg_lengths, seg_slots):
        for blk in range(0, int(ln), bq):
            ws = int(s0) + blk
            we = min(int(s0) + int(ln), ws + bq)
            meta[w] = (int(slot), min(ws, n_tokens - bq), ws, we)
            w += 1
    assert w <= n_work, "packed meta overflow: raise n_work"
    return meta


def packed_live_pages(meta, q_pos, page: int, npages: int):
    """:func:`live_pages` of each work row of a :func:`packed_meta_table`:
    the position of its window's last lane (a segment's lanes carry
    consecutive positions), none for an unused row (slot -1).
    q_pos: (1, T)."""
    last = q_pos[0, jnp.maximum(meta[:, 3] - 1, 0)]
    return live_pages(jnp.where(meta[:, 0] >= 0, last, -1), page, npages)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes a VMEM array of ``shape`` takes: its minor dim padded to 128
    lanes, its second-minor to whole sublane tiles (8 rows of 32 bits,
    packed 16 or 32 rows deep for narrower types)."""
    size = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sub = 8 * 4 // size
    n = 1
    for d in lead:
        n *= d
    return n * -(-rows // sub) * sub * -(-lanes // 128) * 128 * size


def packed_block_pages(kpool, vpool, k_scale, npages: int) -> int:
    """BP, the pages the packed kernel fetches and attends per block: the
    largest power of two <= ``npages`` whose two buffer sets fit
    PACKED_PAGE_BUFFER_BYTES, at a page's VMEM bytes (K, V, their scale
    rows for an int8 pool, and the page's (page, POS_LANES) positions
    block).  Reads only shapes and dtypes, so the host counts the
    kernel's blocks with the kernel's own BP."""
    page = kpool.shape[-3]
    per_page = (_vmem_bytes(kpool.shape[-3:], kpool.dtype)
                + _vmem_bytes(vpool.shape[-3:], vpool.dtype)
                + _vmem_bytes((page, POS_LANES), jnp.int32))
    if k_scale is not None:
        per_page += 2 * _vmem_bytes(k_scale.shape[-2:], k_scale.dtype)
    bp = 1
    while 2 * bp <= npages and 2 * (2 * bp) * per_page \
            <= PACKED_PAGE_BUFFER_BYTES:
        bp *= 2
    return bp


def _block_kv(d, k, v, ks=None, vs=None):
    """A fetched block's K and V as float32 keys: (bp, page, Hkv, D[v])
    flattened to (bp*page, Hkv, D[v]), dequantized with their (bp, page,
    heads) scale rows for an int8 pool, K cut to the query's head dim
    and the scale rows to K's heads (as :func:`_page_kv`)."""
    bp, page, hkv = k.shape[:3]
    k = k.reshape(bp * page, hkv, -1)[..., :d].astype(jnp.float32)
    v = v.reshape(bp * page, hkv, -1).astype(jnp.float32)
    if ks is not None:
        k = k * ks.reshape(bp * page, -1)[:, :hkv].astype(
            jnp.float32)[..., None]
        v = v * vs.reshape(bp * page, -1)[:, :hkv].astype(
            jnp.float32)[..., None]
    return k, v


def _paged_packed_kernel(ly_ref, nl_ref, meta_ref, bt_ref, q_ref, *refs,
                         n_pool, tail, scale, attn_softcap, window, g, bp):
    """Token-packed ragged variant of _paged_mixed_kernel: grid step w is
    work row w of the flat (1, T) stream's work table.  Row w covers
    PACKED_BLOCK_Q stream lanes starting at meta[w, 1]; only lanes inside
    [meta[w, 2], meta[w, 3]) belong to its window's segment — the rest
    are masked off and their output lanes preserved via a masked
    read-modify-write at finalize (steps run sequentially, and a lane's
    owning window is unique, so the RMW never races).  The attended
    pages are the *segment's slot's* pages (meta[w, 0] indexes the block
    table); each query lane masks keys against its own absolute
    position, so every token of every slot gets its exact causal
    paged-attention in ONE kernel launch.

    The pools stay in HBM.  The row's nl[w] live pages (through its last
    lane) come in blocks of ``bp``: the kernel copies each live,
    allocated page of a block (K, V, their scale rows, and the page's
    (page, POS_LANES) positions block) into one of two VMEM buffer sets,
    starts the next block — or, after the row's last, the next row's
    first — into the other set, and attends the block's bp * page keys
    in one online-softmax update.  Pages it does not fetch (past nl[w],
    or unallocated) are masked out of the block, so their buffer entries,
    stale from an earlier block, contribute nothing (the V buffers are
    zeroed once, at the first step).  ``tail`` is the first page of a
    partial last POS_LANES column block of the positions (None when P is
    a whole number of them): a page there takes its positions from that
    block, resident in VMEM, since a copy cannot reach past the array."""
    kpools, vpools = refs[:n_pool], refs[n_pool:2 * n_pool]
    kp_hbm, refs = refs[2 * n_pool], refs[2 * n_pool + 1:]
    tail_ref, refs = (refs[0], refs[1:]) if tail is not None \
        else (None, refs)
    qp_ref, o_ref, m_scr, l_scr, acc_scr = refs[:5]
    kbufs, vbufs = refs[5:5 + n_pool], refs[5 + n_pool:5 + 2 * n_pool]
    pbuf, sem, nxt_ref = refs[5 + 2 * n_pool:]

    w, n_work = pl.program_id(0), pl.num_programs(0)
    bq = PACKED_BLOCK_Q
    npages = bt_ref.shape[1]
    _, _, page, Hkv, _ = kbufs[0].shape
    _, _, Hq, D = q_ref.shape
    keys = bp * page
    ly = ly_ref[0]
    offc = meta_ref[w, 1]
    ws = meta_ref[w, 2]
    we = meta_ref[w, 3]
    nl = nl_ref[w]

    def row_page(r, j):
        """Physical page of logical page j of work row r, and whether the
        kernel fetches it: live (j < nl[r]) and allocated."""
        pid = bt_ref[jnp.maximum(meta_ref[r, 0], 0),
                     jnp.minimum(j, npages - 1)]
        return pid, (j < nl_ref[r]) & (pid >= 0)

    def block_copies(r, b, s, start):
        """Start (``start``) or wait for the copies of block b of work row
        r into buffer set s.  A wait needs only a copy's size, and it is
        taken under its start's condition, so each started copy is
        waited for once."""
        def go(copies):
            for c in copies:
                if start:
                    c.start()
                else:
                    c.wait()

        def one(i, carry):
            pid, fetch = row_page(r, b * bp + i)
            pl.when(fetch)(lambda: go([
                pltpu.make_async_copy(src.at[ly, pid], dst.at[s, i],
                                      sem.at[s])
                for src, dst in zip(kpools + vpools, kbufs + vbufs)]))
            if tail != 0:          # some pages' columns are whole blocks
                col = pl.multiple_of(pid // POS_LANES * POS_LANES,
                                     POS_LANES)
                pos = pltpu.make_async_copy(
                    kp_hbm.at[ly, :, pl.ds(col, POS_LANES)], pbuf.at[s, i],
                    sem.at[s])
                pl.when(fetch if tail is None else fetch & (pid < tail))(
                    lambda: go([pos]))
            if tail is not None and start:
                @pl.when(fetch & (pid >= tail))
                def _():
                    pbuf[s, i] = tail_ref[...]
            return carry
        jax.lax.fori_loop(0, bp, one, 0)

    @pl.when(w == 0)
    def _first():
        nxt_ref[0] = 0                     # no row's first block in flight
        nxt_ref[1] = 0                     # the buffer set it goes to
        for buf in vbufs:
            buf[...] = jnp.zeros_like(buf)

    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    lane = offc + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    in_win = (lane >= ws) & (lane < we)                    # (bq, 1)

    @pl.when(nl > 0)
    def _row():
        q = q_ref[0, pl.ds(offc, bq)].astype(jnp.float32)  # (bq, Hq, D)
        qg = q.reshape(bq, Hkv, g, D).transpose(1, 0, 2, 3)  # (Hkv,bq,g,D)
        qp_eff = jnp.where(in_win, qp_ref[pl.ds(offc, bq)], -1)  # (bq, 1)
        key_page = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1) // page
        nb = (nl + bp - 1) // bp
        first = nxt_ref[1]

        @pl.when(nxt_ref[0] == 0)
        def _():
            block_copies(w, 0, first, True)

        def block(b, s):
            # the next block of this row, or after its last the next
            # row's first, goes to the other set while this one is read
            more = b + 1 < nb

            @pl.when(more | (w + 1 < n_work))
            def _():
                block_copies(jnp.where(more, w, w + 1),
                             jnp.where(more, b + 1, 0), 1 - s, True)

            block_copies(w, b, s, False)

            # each key's page: its column in the positions block, and
            # whether it was fetched (int32: a vector carry)
            def page_cols(i, carry):
                col, ok = carry
                pid, fetch = row_page(w, b * bp + i)
                here = key_page == i
                return (jnp.where(here, pid % POS_LANES, col),
                        jnp.where(here, fetch.astype(jnp.int32), ok))
            col, ok = jax.lax.fori_loop(
                0, bp, page_cols, (jnp.zeros((1, keys), jnp.int32),
                                   jnp.zeros((1, keys), jnp.int32)))
            cols = pbuf[s].reshape(keys, POS_LANES).T      # (lanes, keys)
            pick = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 0) == col
            kp = jnp.sum(jnp.where(pick, cols, 0), axis=0, keepdims=True)
            k, v = _block_kv(D, kbufs[0][s], vbufs[0][s],
                             *[buf[s] for buf in kbufs[1:] + vbufs[1:]])
            mask = _mq_mask(kp, qp_eff, ok > 0, window)    # (bq, keys)
            _attend_block_mq(qg, k, v, mask, m_scr, l_scr, acc_scr,
                             scale=scale, attn_softcap=attn_softcap)
            return 1 - s

        nxt_ref[1] = jax.lax.fori_loop(0, nb, block, first)
        nxt_ref[0] = ((w + 1 < n_work) & (
            nl_ref[jnp.minimum(w + 1, n_work - 1)] > 0)).astype(jnp.int32)

    denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
    out = (acc_scr[...] / denom) \
        .reshape(Hkv, bq, g, acc_scr.shape[-1]) \
        .transpose(1, 0, 2, 3).reshape(bq, Hq, acc_scr.shape[-1])
    old = o_ref[0, pl.ds(offc, bq)]
    o_ref[0, pl.ds(offc, bq)] = jnp.where(
        in_win[:, :, None], out.astype(o_ref.dtype), old)


def _whole_lanes(pool):
    """``pool`` with its minor dim zero-padded to whole 128-lane tiles: a
    page copy cannot cut a lane tile.  A TPU engine's pools are already
    whole lanes (``kv_cache.pool_lanes``), so only other callers' pools
    are copied here."""
    pad = -pool.shape[-1] % 128
    if not pad:
        return pool
    return jnp.pad(pool, [(0, 0)] * (pool.ndim - 1) + [(0, pad)])


def _paged_packed_call(q, kpools, vpools, ppos, block_tables, q_pos, meta,
                       layer, *, window, scale, attn_softcap, interpret):
    """The packed kernel over K pools ``kpools`` (codes[, scales]) and V
    pools ``vpools`` alike: one grid step per work row."""
    _, T, Hq, D = q.shape
    n_pool = len(kpools)
    Dv_out = vpools[0].shape[-1]
    ly, pools = _stack(layer, (*map(_whole_lanes, kpools + vpools), ppos))
    kpools, vpools, ppos = pools[:n_pool], pools[n_pool:-1], pools[-1]
    P, page, Hkv, Dv = vpools[0].shape[-4:]
    npages = block_tables.shape[1]
    g = Hq // Hkv
    bq = PACKED_BLOCK_Q
    bp = packed_block_pages(kpools[0], vpools[0],
                            kpools[1] if n_pool > 1 else None, npages)
    tail = P // POS_LANES * POS_LANES if P % POS_LANES else None

    n_live = packed_live_pages(meta, q_pos, page, npages)

    def resident(shape):
        return pl.BlockSpec(shape, lambda w, *pf: (0,) * len(shape))

    in_specs = [resident((1, T, Hq, D))] \
        + [pl.BlockSpec(memory_space=pl.ANY)] * (2 * n_pool + 1)
    if tail is not None:
        in_specs.append(pl.BlockSpec(
            (None, page, POS_LANES),
            lambda w, ly, *pf: (ly[0], 0, tail // POS_LANES)))
    in_specs.append(resident((T, 1)))
    kernel = functools.partial(_paged_packed_kernel, n_pool=n_pool,
                               tail=tail, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               g=g, bp=bp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(meta.shape[0],),
        in_specs=in_specs,
        out_specs=resident((1, T, Hq, Dv)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, bq * g), jnp.float32),
            pltpu.VMEM((Hkv, bq * g), jnp.float32),
            pltpu.VMEM((Hkv, bq * g, Dv), jnp.float32),
            *[pltpu.VMEM((2, bp) + p.shape[2:], p.dtype)
              for p in kpools + vpools],
            pltpu.VMEM((2, bp, page, POS_LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    positions = _positions(ppos)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, T, Hq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PACKED_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(ly, n_live, meta, block_tables, q, *kpools, *vpools, positions,
      *([positions] if tail is not None else []), q_pos.reshape(T, 1))
    # lanes no window owns (stream padding) are never written: zero them
    return jnp.where((q_pos >= 0)[..., None, None], out[..., :Dv_out], 0)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_packed_attention(q, kpool, vpool, ppos, block_tables, q_pos,
                           meta, layer=None, *, window: Optional[int],
                           scale: float,
                           attn_softcap: Optional[float] = None,
                           interpret: bool = False):
    """Token-packed ragged attention over a paged KV pool: the whole
    mixed iteration — every decode token and every prefill-chunk token of
    every slot — as ONE (1, T) dispatch.

    q: (1, T, Hq, D) flat token stream; q_pos: (1, T) absolute positions
    (-1 = padding lane, comes back zeros); block_tables: (slots, npages);
    meta: (n_work, 4) int32 work table from :func:`packed_meta_table`.
    The stream's own K/V must already be in the pool (written by
    ``kv_cache.paged_write_packed``); stored absolute positions give each
    query its exact causal mask over its own slot's history, including
    earlier tokens of its own chunk.  The grid has one step per work
    row, and row w fetches and attends only the live pages of its
    window's last lane (:func:`packed_live_pages`), in blocks of
    :func:`packed_block_pages` pages, double-buffered; an unused row
    (slot -1) fetches nothing.  Pools are stacked and read at ``layer``
    as in :func:`paged_decode_attention`."""
    return _paged_packed_call(
        q, (kpool,), (vpool,), ppos, block_tables, q_pos, meta, layer,
        window=window, scale=scale, attn_softcap=attn_softcap,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_packed_attention_q8(q, kpool, k_scale, vpool, v_scale, ppos,
                              block_tables, q_pos, meta, layer=None, *,
                              window: Optional[int], scale: float,
                              attn_softcap: Optional[float] = None,
                              interpret: bool = False):
    """:func:`paged_packed_attention` over an int8-quantized pool (same
    scale-pool contract as :func:`paged_decode_attention_q8`): each work
    row fetches its live pages' codes and scale rows and dequantizes
    them in-register."""
    return _paged_packed_call(
        q, (kpool, k_scale), (vpool, v_scale), ppos, block_tables, q_pos,
        meta, layer, window=window, scale=scale, attn_softcap=attn_softcap,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "block_k",
                                             "interpret"))
def decode_attention(q, k, v, k_pos, q_pos, *, window: Optional[int],
                     scale: float, attn_softcap: Optional[float] = None,
                     block_k: int = DEFAULT_BLOCK_K,
                     interpret: bool = False):
    """q: (B,1,Hq,D), k/v: (B,Sk,Hkv,Dv), k_pos: (B,Sk), q_pos: (B,1)."""
    B, _, Hq, D = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    g = Hq // Hkv
    bk = min(block_k, Sk)
    nk = Sk // bk

    kernel = functools.partial(_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               nk=nk, g=g)
    out = pl.pallas_call(
        kernel,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, 1, Hq, D), lambda b, ik: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, D), lambda b, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, Dv), lambda b, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, ik: (b, 0, ik)),
            pl.BlockSpec((1, 1, 1), lambda b, ik: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hq, Dv), lambda b, ik: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Hq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g), jnp.float32),
            pltpu.VMEM((Hkv, g, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, k_pos.reshape(B, 1, Sk), q_pos.reshape(B, 1, 1))
    return out
