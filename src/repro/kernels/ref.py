"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def _mask(q_pos, k_pos, window):
    m = (k_pos[:, None, :] <= q_pos[:, :, None]) & (k_pos[:, None, :] >= 0)
    if window is not None:
        m &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    return m


def flash_attention_ref(q, k, v, q_pos, k_pos, *, window: Optional[int],
                        scale: float, attn_softcap: Optional[float] = None):
    """q: (B,Sq,Hq,D), k/v: (B,Sk,Hkv,D) -> (B,Sq,Hq,Dv)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if attn_softcap is not None:
        logits = jnp.tanh(logits / attn_softcap) * attn_softcap
    mask = _mask(q_pos, k_pos, window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask.any(-1)[:, None, None, :, None], p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, v.shape[-1]).astype(q.dtype)


def decode_attention_ref(q, k, v, k_pos, q_pos, *, window: Optional[int],
                         scale: float, attn_softcap: Optional[float] = None):
    """q: (B,1,Hq,D) vs cache k/v: (B,Sk,Hkv,D) -> (B,1,Hq,Dv)."""
    return flash_attention_ref(q, k, v, q_pos, k_pos, window=window,
                               scale=scale, attn_softcap=attn_softcap)


def paged_decode_attention_ref(q, kpool, vpool, ppos, block_tables, q_pos, *,
                               window: Optional[int], scale: float,
                               attn_softcap: Optional[float] = None,
                               k_scale=None, v_scale=None, layer=None):
    """Dense-gather oracle for the paged decode kernel: resolve each slot's
    block table into a contiguous (B, npages*page, ...) view (the same
    ``kv_cache.paged_gather`` the production fallback uses), then run the
    dense decode reference.  With ``k_scale``/``v_scale`` the pools hold
    int8 codes and the gather dequantizes them — the fp32 target the
    fused-dequant Pallas kernel must match.  With ``layer`` the pools are
    whole layer stacks, gathered from at that layer."""
    from repro.core.kv_cache import paged_gather
    pool = {"pk": kpool, "pv": vpool, "ppos": ppos}
    if k_scale is not None:
        pool["pk_scale"] = k_scale
        pool["pv_scale"] = v_scale
    k, v, kp = paged_gather(pool, block_tables, layer)
    return decode_attention_ref(q, k.astype(q.dtype), v.astype(q.dtype),
                                kp, q_pos, window=window,
                                scale=scale, attn_softcap=attn_softcap)


def paged_mixed_attention_ref(q, kpool, vpool, ppos, block_tables, q_pos,
                              *, window: Optional[int], scale: float,
                              attn_softcap: Optional[float] = None,
                              k_scale=None, v_scale=None, layer=None):
    """Oracle for the multi-query paged *mixed* kernel (chunked prefill
    rows, decode rows, speculative verify windows): q (B, W, Hq, D) with
    per-slot query counts expressed through q_pos (B, W) — real queries
    carry absolute positions, padding queries carry -1 and come back as
    zeros.  Causal masking inside a window falls out of the stored
    absolute positions — the window's own K/V are already in the pool
    when it attends.  Shares the dense-gather + flash reference with the
    single-query oracle (which is the W == 1 case)."""
    return paged_decode_attention_ref(
        q, kpool, vpool, ppos, block_tables, q_pos, window=window,
        scale=scale, attn_softcap=attn_softcap, k_scale=k_scale,
        v_scale=v_scale, layer=layer)


# speculative verify = the mixed oracle with every row's window full
paged_verify_attention_ref = paged_mixed_attention_ref


def paged_packed_attention_ref(q, kpool, vpool, ppos, block_tables, q_pos,
                               slot_ids, *, window: Optional[int],
                               scale: float,
                               attn_softcap: Optional[float] = None,
                               k_scale=None, v_scale=None, layer=None):
    """Oracle for the token-packed ragged kernel: q (1, T, Hq, D) is one
    flat stream where token t belongs to slot ``slot_ids[t]`` and attends
    that slot's paged history only.  Gathers each *slot's* pages densely
    once (exactly ``paged_gather``), then runs every stream token as its
    own single-query attention against its slot's gathered context —
    same key order and count per query as the bucketed per-slot
    fallback, so greedy outputs stay bit-identical across the two paths.
    Padding lanes (slot_ids == -1) come back as zeros."""
    from repro.core.kv_cache import paged_gather
    pool = {"pk": kpool, "pv": vpool, "ppos": ppos}
    if k_scale is not None:
        pool["pk_scale"] = k_scale
        pool["pv_scale"] = v_scale
    k, v, kp = paged_gather(pool, block_tables, layer)   # (B, ctx, H, D)
    B = block_tables.shape[0]
    _, T, Hq, _ = q.shape
    sid = slot_ids.reshape(T)
    safe = jnp.clip(sid, 0, B - 1)
    k_t = k[safe]                                   # (T, ctx, Hkv, D)
    v_t = v[safe]
    kp_t = jnp.where((sid >= 0)[:, None], kp[safe], -1)
    out = decode_attention_ref(
        q.reshape(T, 1, Hq, -1), k_t.astype(q.dtype), v_t.astype(q.dtype),
        kp_t, q_pos.reshape(T, 1), window=window, scale=scale,
        attn_softcap=attn_softcap)
    return out.reshape(1, T, Hq, -1)


def rmsnorm_ref(x, w, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(dt)


def quant_matmul_ref(x, q, s):
    """Weight-quantized matmul oracle: x (..., K) @ int8 q (K, N) with
    per-output-channel fp32 scales s (N,).  Accumulates the codes in
    fp32 and rescales the product — the exact per-column identity
    ``x @ (q * s) == (x @ q) * s`` the fused kernel exploits.  Returns
    fp32; this is also the serve path's jnp fallback (kernel mode off),
    so CPU tier-1 runs the same math the kernel computes."""
    acc = jnp.matmul(x.astype(jnp.float32), q.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc * s.astype(jnp.float32)
