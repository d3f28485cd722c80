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


def _page_positions(ppos):
    """(P, page) -> (P, 1, page): each page's stored positions become
    one lane row, so a (1, 1, page) block meets the TPU tiling rule
    (last two block dims equal to the array's)."""
    return ppos.reshape(ppos.shape[0], 1, ppos.shape[1])


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
    page, Hkv = kpool.shape[1], kpool.shape[2]
    return (Sq == 1 and Hq % Hkv == 0 and D % 8 == 0
            and kpool.shape[3] % 8 == 0 and page % 8 == 0
            and block_tables.shape[0] == B)


def _paged_kernel(bt_ref, q_ref, k_ref, v_ref, kp_ref, qp_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, attn_softcap, window,
                  npages, g):
    """Same online-softmax scheme as _kernel, but the grid walks the
    slot's block table: page j streams physical page bt[b, j] from the
    pool (the BlockSpec index_map does the indirection; bt itself arrives
    via scalar prefetch).  Unallocated entries resolve to the dump page,
    whose positions are always -1, so masking alone keeps them out."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (Hq, D)
    k = k_ref[0].astype(jnp.float32)                       # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32)                       # (page, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, page)
    qp = qp_ref[0]                                         # (1, 1)

    mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)     # (1, page)
    _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                  attn_softcap=attn_softcap, g=g)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom).reshape(q.shape[0], acc_scr.shape[-1])
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_decode_attention(q, kpool, vpool, ppos, block_tables, q_pos, *,
                           window: Optional[int], scale: float,
                           attn_softcap: Optional[float] = None,
                           interpret: bool = False):
    """Decode attention over a paged KV pool.

    q: (B,1,Hq,D); kpool/vpool: (P,page,Hkv,D[v]); ppos: (P,page) absolute
    positions (-1 empty); block_tables: (B,npages) physical page ids with
    -1 = unallocated; q_pos: (B,1).  Page P-1 is the dump page.
    """
    B, _, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1

    def page_of(b, j, bt):
        pid = bt[b, j]
        return jnp.where(pid < 0, dump, pid)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, 1, Hq, D), lambda b, j, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j, bt: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hq, Dv), lambda b, j, bt: (b, 0, 0, 0)),
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
    )(block_tables, q, kpool, vpool, _page_positions(ppos),
      q_pos.reshape(B, -1, 1))
    return out


def _paged_kernel_q8(bt_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, kp_ref,
                     qp_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                     attn_softcap, window, npages, g):
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

    q = q_ref[0, 0].astype(jnp.float32)                    # (Hq, D)
    k = k_ref[0].astype(jnp.float32) \
        * ks_ref[0].astype(jnp.float32)[..., None]         # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32) \
        * vs_ref[0].astype(jnp.float32)[..., None]         # (page, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, page)
    qp = qp_ref[0]                                         # (1, 1)

    mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)     # (1, page)
    _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                  attn_softcap=attn_softcap, g=g)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom).reshape(q.shape[0], acc_scr.shape[-1])
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_decode_attention_q8(q, kpool, k_scale, vpool, v_scale, ppos,
                              block_tables, q_pos, *,
                              window: Optional[int], scale: float,
                              attn_softcap: Optional[float] = None,
                              interpret: bool = False):
    """Decode attention over an int8-quantized paged KV pool.

    Same contract as :func:`paged_decode_attention` plus the parallel
    scale pools: kpool/vpool are (P,page,Hkv,D[v]) int8 codes and
    k_scale/v_scale are (P,page,Hkv) fp32 per-entry absmax scales.
    Dequantization is fused into the page stream (in-register, before
    the matmuls)."""
    B, _, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1

    def page_of(b, j, bt):
        pid = bt[b, j]
        return jnp.where(pid < 0, dump, pid)

    kernel = functools.partial(_paged_kernel_q8, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, 1, Hq, D), lambda b, j, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j, bt: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hq, Dv), lambda b, j, bt: (b, 0, 0, 0)),
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
    )(block_tables, q, kpool, k_scale, vpool, v_scale,
      _page_positions(ppos), q_pos.reshape(B, -1, 1))
    return out


def paged_mixed_shape_supported(q, kpool, block_tables) -> bool:
    B, Sq, Hq, D = q.shape
    page, Hkv = kpool.shape[1], kpool.shape[2]
    return (Sq >= 1 and Hq % Hkv == 0 and D % 8 == 0
            and kpool.shape[3] % 8 == 0 and page % 8 == 0
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


def _paged_mixed_kernel(bt_ref, q_ref, k_ref, v_ref, kp_ref, qp_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, scale, attn_softcap,
                        window, npages, g):
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

    q = q_ref[0].astype(jnp.float32)                       # (K1, Hq, D)
    k = k_ref[0].astype(jnp.float32)                       # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32)                       # (page, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, page)
    qp = qp_ref[0]                                         # (K1, 1)
    K1, Hq, D = q.shape
    Hkv = k.shape[1]

    mask = _mq_mask(kp, qp, bt_ref[b, j] >= 0, window)
    qg = q.reshape(K1, Hkv, g, D).transpose(1, 0, 2, 3)    # (Hkv, K1, g, D)
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
def paged_mixed_attention(q, kpool, vpool, ppos, block_tables, q_pos, *,
                          window: Optional[int], scale: float,
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
    chunk boundary is legal.
    """
    B, K1, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1

    def page_of(b, j, bt):
        pid = bt[b, j]
        return jnp.where(pid < 0, dump, pid)

    kernel = functools.partial(_paged_mixed_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, K1, Hq, D), lambda b, j, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, K1, 1), lambda b, j, bt: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K1, Hq, Dv),
                               lambda b, j, bt: (b, 0, 0, 0)),
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
    )(block_tables, q, kpool, vpool, _page_positions(ppos),
      q_pos.reshape(B, -1, 1))
    return out


# speculative verify = the mixed entry with every row's window full
paged_verify_attention = paged_mixed_attention


def _paged_mixed_kernel_q8(bt_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                           kp_ref, qp_ref, o_ref, m_scr, l_scr, acc_scr,
                           *, scale, attn_softcap, window, npages, g):
    """Quantized-pool mixed kernel: int8 page tiles + per-entry scale
    rows dequantized in-register (exactly _paged_kernel_q8's stream)
    feeding the multi-query online-softmax body."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # (K1, Hq, D)
    k = k_ref[0].astype(jnp.float32) \
        * ks_ref[0].astype(jnp.float32)[..., None]         # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32) \
        * vs_ref[0].astype(jnp.float32)[..., None]         # (page, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, page)
    qp = qp_ref[0]                                         # (K1, 1)
    K1, Hq, D = q.shape
    Hkv = k.shape[1]

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
                             block_tables, q_pos, *,
                             window: Optional[int], scale: float,
                             attn_softcap: Optional[float] = None,
                             interpret: bool = False):
    """:func:`paged_mixed_attention` over an int8-quantized pool (same
    scale-pool contract as :func:`paged_decode_attention_q8`; q_pos of
    -1 marks padding queries exactly like the fp entry)."""
    B, K1, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape
    npages = block_tables.shape[1]
    g = Hq // Hkv
    dump = P - 1

    def page_of(b, j, bt):
        pid = bt[b, j]
        return jnp.where(pid < 0, dump, pid)

    kernel = functools.partial(_paged_mixed_kernel_q8, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, npages),
        in_specs=[
            pl.BlockSpec((1, K1, Hq, D), lambda b, j, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, j, bt: (page_of(b, j, bt), 0, 0)),
            pl.BlockSpec((1, K1, 1), lambda b, j, bt: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K1, Hq, Dv),
                               lambda b, j, bt: (b, 0, 0, 0)),
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
    )(block_tables, q, kpool, k_scale, vpool, v_scale,
      _page_positions(ppos), q_pos.reshape(B, -1, 1))
    return out


paged_verify_attention_q8 = paged_mixed_attention_q8


PACKED_BLOCK_Q = 8
# the whole (1, T, Hq, D[v]) query/output blocks stay VMEM-resident
# (constant index maps), so T is capped by a VMEM budget, not the grid
PACKED_VMEM_BYTES = 8 * 1024 * 1024


def paged_packed_shape_supported(q, kpool, block_tables,
                                 meta=None) -> bool:
    _, T, Hq, D = q.shape
    page, Hkv, Dv = kpool.shape[1], kpool.shape[2], kpool.shape[3]
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


def _paged_packed_kernel(meta_ref, bt_ref, q_ref, k_ref, v_ref, kp_ref,
                         qp_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
                         attn_softcap, window, npages, g):
    """Token-packed ragged variant of _paged_mixed_kernel: the grid's
    first dim walks *query windows* of the flat (1, T) stream instead of
    slots.  Work item w covers PACKED_BLOCK_Q stream lanes starting at
    meta[w, 1]; only lanes inside [meta[w, 2], meta[w, 3]) belong to the
    window's segment — the rest are masked off and their output lanes
    preserved via a masked read-modify-write at finalize (grid items run
    sequentially, and a lane's owning window is unique, so the RMW never
    races).  The streamed pages are the *segment's slot's* pages
    (meta[w, 0] indexes the block table); each query lane masks keys
    against its own absolute position, so every token of every slot gets
    its exact causal paged-attention in ONE kernel launch."""
    w, j = pl.program_id(0), pl.program_id(1)
    bq = PACKED_BLOCK_Q
    slot = meta_ref[w, 0]
    offc = meta_ref[w, 1]
    ws = meta_ref[w, 2]
    we = meta_ref[w, 3]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, pl.ds(offc, bq)].astype(jnp.float32)      # (bq, Hq, D)
    k = k_ref[0].astype(jnp.float32)                       # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32)                       # (page, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, page)
    qp = qp_ref[pl.ds(offc, bq)]                           # (bq, 1)
    _, Hq, D = q.shape
    Hkv = k.shape[1]

    lane = offc + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    in_win = (lane >= ws) & (lane < we)                    # (bq, 1)
    qp_eff = jnp.where(in_win, qp, -1)
    allocated = (slot >= 0) & (bt_ref[jnp.maximum(slot, 0), j] >= 0)
    mask = _mq_mask(kp, qp_eff, allocated, window)
    qg = q.reshape(bq, Hkv, g, D).transpose(1, 0, 2, 3)    # (Hkv, bq, g, D)
    _attend_block_mq(qg, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                     attn_softcap=attn_softcap)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom) \
            .reshape(Hkv, bq, g, acc_scr.shape[-1]) \
            .transpose(1, 0, 2, 3).reshape(bq, Hq, acc_scr.shape[-1])
        old = o_ref[0, pl.ds(offc, bq)]
        o_ref[0, pl.ds(offc, bq)] = jnp.where(
            in_win[:, :, None], out.astype(o_ref.dtype), old)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_packed_attention(q, kpool, vpool, ppos, block_tables, q_pos,
                           meta, *, window: Optional[int], scale: float,
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
    earlier tokens of its own chunk."""
    _, T, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape
    npages = block_tables.shape[1]
    n_work = meta.shape[0]
    g = Hq // Hkv
    dump = P - 1
    bq = PACKED_BLOCK_Q

    def page_of(w, j, meta, bt):
        slot = meta[w, 0]
        pid = bt[jnp.maximum(slot, 0), j]
        return jnp.where((slot < 0) | (pid < 0), dump, pid)

    kernel = functools.partial(_paged_packed_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_work, npages),
        in_specs=[
            pl.BlockSpec((1, T, Hq, D), lambda w, j, meta, bt: (0, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dv),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0)),
            pl.BlockSpec((T, 1), lambda w, j, meta, bt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, Hq, Dv),
                               lambda w, j, meta, bt: (0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, bq * g), jnp.float32),
            pltpu.VMEM((Hkv, bq * g), jnp.float32),
            pltpu.VMEM((Hkv, bq * g, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, T, Hq, Dv), q.dtype),
        interpret=interpret,
    )(meta, block_tables, q, kpool, vpool, _page_positions(ppos),
      q_pos.reshape(T, 1))
    # lanes no window owns (stream padding) are never written: zero them
    return jnp.where((q_pos >= 0)[..., None, None], out, 0)


def _paged_packed_kernel_q8(meta_ref, bt_ref, q_ref, k_ref, ks_ref, v_ref,
                            vs_ref, kp_ref, qp_ref, o_ref, m_scr, l_scr,
                            acc_scr, *, scale, attn_softcap, window,
                            npages, g):
    """Quantized-pool packed kernel: int8 page tiles + per-entry scale
    rows dequantized in-register, feeding the same windowed
    online-softmax body as _paged_packed_kernel."""
    w, j = pl.program_id(0), pl.program_id(1)
    bq = PACKED_BLOCK_Q
    slot = meta_ref[w, 0]
    offc = meta_ref[w, 1]
    ws = meta_ref[w, 2]
    we = meta_ref[w, 3]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, pl.ds(offc, bq)].astype(jnp.float32)      # (bq, Hq, D)
    k = k_ref[0].astype(jnp.float32) \
        * ks_ref[0].astype(jnp.float32)[..., None]         # (page, Hkv, D)
    v = v_ref[0].astype(jnp.float32) \
        * vs_ref[0].astype(jnp.float32)[..., None]         # (page, Hkv, Dv)
    kp = kp_ref[0]                                         # (1, page)
    qp = qp_ref[pl.ds(offc, bq)]                           # (bq, 1)
    _, Hq, D = q.shape
    Hkv = k.shape[1]

    lane = offc + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    in_win = (lane >= ws) & (lane < we)                    # (bq, 1)
    qp_eff = jnp.where(in_win, qp, -1)
    allocated = (slot >= 0) & (bt_ref[jnp.maximum(slot, 0), j] >= 0)
    mask = _mq_mask(kp, qp_eff, allocated, window)
    qg = q.reshape(bq, Hkv, g, D).transpose(1, 0, 2, 3)
    _attend_block_mq(qg, k, v, mask, m_scr, l_scr, acc_scr, scale=scale,
                     attn_softcap=attn_softcap)

    @pl.when(j == npages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)[..., None]
        out = (acc_scr[...] / denom) \
            .reshape(Hkv, bq, g, acc_scr.shape[-1]) \
            .transpose(1, 0, 2, 3).reshape(bq, Hq, acc_scr.shape[-1])
        old = o_ref[0, pl.ds(offc, bq)]
        o_ref[0, pl.ds(offc, bq)] = jnp.where(
            in_win[:, :, None], out.astype(o_ref.dtype), old)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "interpret"))
def paged_packed_attention_q8(q, kpool, k_scale, vpool, v_scale, ppos,
                              block_tables, q_pos, meta, *,
                              window: Optional[int], scale: float,
                              attn_softcap: Optional[float] = None,
                              interpret: bool = False):
    """:func:`paged_packed_attention` over an int8-quantized pool (same
    scale-pool contract as :func:`paged_decode_attention_q8`)."""
    _, T, Hq, D = q.shape
    P, page, Hkv, Dv = vpool.shape
    npages = block_tables.shape[1]
    n_work = meta.shape[0]
    g = Hq // Hkv
    dump = P - 1
    bq = PACKED_BLOCK_Q

    def page_of(w, j, meta, bt):
        slot = meta[w, 0]
        pid = bt[jnp.maximum(slot, 0), j]
        return jnp.where((slot < 0) | (pid < 0), dump, pid)

    kernel = functools.partial(_paged_packed_kernel_q8, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               npages=npages, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_work, npages),
        in_specs=[
            pl.BlockSpec((1, T, Hq, D), lambda w, j, meta, bt: (0, 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0)),
            pl.BlockSpec((1, page, Hkv, Dv),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda w, j, meta, bt: (page_of(w, j, meta, bt),
                                                 0, 0)),
            pl.BlockSpec((T, 1), lambda w, j, meta, bt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, Hq, Dv),
                               lambda w, j, meta, bt: (0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, bq * g), jnp.float32),
            pltpu.VMEM((Hkv, bq * g), jnp.float32),
            pltpu.VMEM((Hkv, bq * g, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, T, Hq, Dv), q.dtype),
        interpret=interpret,
    )(meta, block_tables, q, kpool, k_scale, vpool, v_scale,
      _page_positions(ppos), q_pos.reshape(T, 1))
    return jnp.where((q_pos >= 0)[..., None, None], out, 0)


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
