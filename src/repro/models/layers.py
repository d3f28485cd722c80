"""Core building blocks, pure-functional JAX (params are nested dicts).

Everything here is written against the *reference* jnp path; the Pallas
kernels in ``repro.kernels`` implement the hot paths (flash attention,
decode attention, rmsnorm) and are swapped in via ``repro.kernels.ops``
runtime mode without changing model code.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, in_dim: int, out_dim: int, dtype=jnp.float32):
    scale = in_dim ** -0.5
    return (jax.random.normal(rng, (in_dim, out_dim), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype=jnp.float32):
    return (jax.random.normal(rng, (vocab, dim), dtype=jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Dense matmul (weight-quantization aware)
# ---------------------------------------------------------------------------


def dense_matmul(x, w):
    """``x @ w`` for a serve-path dense weight.

    ``w`` is either a plain (..., in, out) array — cast to ``x.dtype``
    at point of use, the historical path — or an int8 weight record
    ``{"q": int8, "s": fp32}`` produced by ``precision.quantize_weights``
    (per-output-channel absmax; ``lax.scan`` over stacked weights slices
    the record's arrays per repeat, so call sites see 2-D codes).
    Quantized records dispatch to the fused-dequant Pallas kernel and
    fall back to the jnp oracle (identical math, fp32 accumulate-then-
    scale) when kernels are off/unsupported — CPU tier-1 stays exact.
    """
    if not isinstance(w, dict):
        return x @ w.astype(x.dtype)
    from repro.kernels import ops as kops
    out = kops.maybe_quant_matmul(x, w["q"], w["s"])
    if out is None:
        from repro.kernels import ref as kref
        out = kref.quant_matmul_ref(x, w["q"], w["s"])
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(dt)


def layernorm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * w + b
    return out.astype(dt)


def norm_init(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"w": jnp.zeros((d,))}
    return {"w": jnp.ones((d,)), "b": jnp.zeros((d,))}


def apply_norm(cfg: ModelConfig, p, x):
    if "b" in p:
        return layernorm(x, p["w"], p["b"])
    return rmsnorm(x, p["w"])


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                              # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

NEG_INF = -2.3819763e38  # most-negative bf16-representable; safe in fp32 too


def causal_mask(q_pos, k_pos, window: Optional[int] = None):
    """Boolean mask (..., Sq, Sk): True = attend.

    q_pos/k_pos: integer position arrays broadcastable to (..., Sq) / (..., Sk).
    """
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


# ---------------------------------------------------------------------------
# Attention (GQA) — reference / chunked(flash-at-HLO-level) / Pallas dispatch
# ---------------------------------------------------------------------------

# above this many keys the chunked (never-materialize-S^2) path is used, so
# prefill_32k / long_500k graphs stay within per-device HBM.
CHUNK_THRESHOLD = 2048
KV_BLOCK = 1024


def mha_attention(q, k, v, q_pos, k_pos, *, window: Optional[int],
                  scale: float, attn_softcap: Optional[float] = None):
    """Causal GQA attention driven by absolute positions.

    q: (B,Sq,Hq,D), k/v: (B,Sk,Hkv,D), q_pos: (B,Sq), k_pos: (B,Sk) with
    -1 marking invalid (empty cache slot / padding) keys.

    Dispatch order: Pallas flash kernel (if kernel mode enabled and shape
    qualifies) -> chunked lax.scan flash (large Sk) -> naive reference.
    All three compute identical math.
    """
    from repro.kernels import ops as kops
    out = kops.maybe_flash_attention(q, k, v, q_pos, k_pos, window=window,
                                     scale=scale, attn_softcap=attn_softcap)
    if out is not None:
        return out
    # chunked only for multi-query-token phases: single-token decode against
    # a (possibly sequence-sharded) cache contracts cleanly as one einsum,
    # and the block reshape would break the cache's sequence sharding.
    if q.shape[1] > 1 and k.shape[1] > CHUNK_THRESHOLD:
        return attention_chunked(q, k, v, q_pos, k_pos, window=window,
                                 scale=scale, attn_softcap=attn_softcap)
    return attention_ref(q, k, v, q_pos, k_pos, window=window, scale=scale,
                         attn_softcap=attn_softcap)


def mha_attention_paged(q, pool, block_tables, q_pos, *,
                        window: Optional[int], scale: float,
                        attn_softcap: Optional[float] = None, layer=None):
    """Decode / mixed-window attention against a paged KV pool
    (continuous batching).

    q: (B,Sq,Hq,D) with Sq == 1 for single-token decode and Sq == W > 1
    for a per-slot query window — a chunked-prefill chunk, a speculative
    verify window, or a decode token padded up to the batch width
    (q_pos (B,Sq) absolute positions, -1 marking padding queries whose
    outputs are zeroed and discarded; the window's own K/V must already
    be written to the pool, so the stored positions make intra-window
    causal masking exact); pool: {"pk"/"pv": (P,page,Hkv,D), "ppos":
    (P,page)}, plus "pk_scale"/"pv_scale" (P,page,Hkv) when the pool
    stores int8; block_tables: (B, pages_per_slot) physical page ids
    (-1 = none).  With ``layer`` the pool's leaves are whole layer
    stacks (a leading layer dim), read in place at that layer.  A pool
    whose rows are widened to whole lanes
    (``kv_cache.paged_layer_cache_shape``) is read to q's head dim.

    Dispatch: paged Pallas kernel (single- or multi-query variant;
    gathers pages in-kernel via scalar-prefetched block tables; int8
    pools dequantize in-register) -> dense gather (dequantizing) +
    reference attention.
    """
    from repro.core import kv_cache as KV
    from repro.kernels import ops as kops
    dispatch = (kops.maybe_paged_decode_attention if q.shape[1] == 1
                else kops.maybe_paged_mixed_attention)
    out = dispatch(
        q, pool["pk"], pool["pv"], pool["ppos"], block_tables, q_pos,
        window=window, scale=scale, attn_softcap=attn_softcap,
        k_scale=pool.get("pk_scale"), v_scale=pool.get("pv_scale"),
        layer=layer)
    if out is not None:
        return out[..., :q.shape[-1]]
    kk, vv, kp = KV.paged_gather(pool, block_tables, layer, q.shape[-1])
    return mha_attention(q, kk.astype(q.dtype), vv.astype(q.dtype),
                         q_pos, kp, window=window, scale=scale,
                         attn_softcap=attn_softcap)


def mha_attention_paged_packed(q, pool, block_tables, q_pos, slot_ids,
                               meta, *, window: Optional[int], scale: float,
                               attn_softcap: Optional[float] = None,
                               layer=None):
    """Token-packed ragged attention against a paged KV pool: one flat
    (1, T) query stream covering every slot's decode token and
    prefill-chunk tokens for a whole scheduler iteration.

    q: (1, T, Hq, D); q_pos: (1, T) absolute positions (-1 = padding
    lane, output zeroed); slot_ids: (T,) owning slot per lane (-1 =
    padding); meta: kernel work table from
    ``decode_attention.packed_meta_table`` (may be None — fallback only);
    block_tables: (slots, pages_per_slot).  The stream's own K/V must
    already be in the pool (``kv_cache.paged_write_packed``); ``layer``
    as in :func:`mha_attention_paged`.

    Dispatch: packed Pallas kernel -> per-token dense gather + the same
    ``mha_attention`` reference the bucketed per-slot fallback uses.
    The fallback gathers each lane's *slot* context in block-table order,
    so every query reduces over exactly the keys, in exactly the order,
    the bucketed path would give it — greedy outputs stay bit-identical
    across the packed and bucketed serving paths.
    """
    from repro.core import kv_cache as KV
    from repro.kernels import ops as kops
    out = kops.maybe_paged_packed_attention(
        q, pool["pk"], pool["pv"], pool["ppos"], block_tables, q_pos,
        meta, window=window, scale=scale, attn_softcap=attn_softcap,
        k_scale=pool.get("pk_scale"), v_scale=pool.get("pv_scale"),
        layer=layer)
    if out is not None:
        return out[..., :q.shape[-1]]
    kk, vv, kp = KV.paged_gather(pool, block_tables, layer,
                                 q.shape[-1])           # (slots, ctx, H, D)
    B = block_tables.shape[0]
    _, T, Hq, _ = q.shape
    safe = jnp.clip(slot_ids, 0, B - 1)
    kp_t = jnp.where((slot_ids >= 0)[:, None], kp[safe], -1)
    out = mha_attention(q.reshape(T, 1, Hq, q.shape[-1]),
                        kk[safe].astype(q.dtype), vv[safe].astype(q.dtype),
                        q_pos.reshape(T, 1), kp_t, window=window,
                        scale=scale, attn_softcap=attn_softcap)
    return out.reshape(1, T, Hq, out.shape[-1])


def position_mask(q_pos, k_pos, window: Optional[int]):
    """(B,Sq,Sk) bool: causal, windowed, and k_pos>=0 validity."""
    m = (k_pos[:, None, :] <= q_pos[:, :, None]) & (k_pos[:, None, :] >= 0)
    if window is not None:
        m &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    return m


def _score_inputs(q, k):
    """attn_bf16 (§Perf): feed half-precision operands straight into the
    MXU with fp32 accumulation instead of materializing fp32 casts of the
    (potentially multi-GB) KV cache."""
    from repro import perf_flags
    if perf_flags.flag("attn_bf16"):
        return q, k
    return q.astype(jnp.float32), k.astype(jnp.float32)


def _pv_inputs(p, v):
    from repro import perf_flags
    if perf_flags.flag("attn_bf16"):
        return p.astype(v.dtype), v
    return p, v.astype(jnp.float32)


def attention_ref(q, k, v, q_pos, k_pos, *, window, scale, attn_softcap=None):
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg, kk = _score_inputs(q.reshape(B, Sq, Hkv, g, D), k)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kk,
                        preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, attn_softcap)
    mask = position_mask(q_pos, k_pos, window)                    # (B,Sq,Sk)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    # rows with no valid key (all -inf) -> softmax gives uniform; zero them.
    any_valid = mask.any(-1)[:, None, None, :, None]
    p = jnp.where(any_valid, p, 0.0)
    pp, vv = _pv_inputs(p, v)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", pp, vv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, Hq, v.shape[-1]).astype(q.dtype)


def attention_chunked(q, k, v, q_pos, k_pos, *, window, scale,
                      attn_softcap=None, block: int = KV_BLOCK):
    """Flash-attention algorithm expressed as a lax.scan over KV blocks.

    Never materializes the (Sq, Sk) score matrix: peak extra memory is one
    (B, Hq, Sq, block) tile reused across scan steps.  This is the compiled
    fallback for huge-context graphs on hosts where the Pallas kernel is
    unavailable; math matches attention_ref exactly (tested).
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    nblk = -(-Sk // block)
    pad = nblk * block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    Dv = v.shape[-1]
    kb = k.reshape(B, nblk, block, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(B, nblk, block).transpose(1, 0, 2)
    qg = q.reshape(B, Sq, Hkv, g, D)
    acc0 = jnp.zeros((B, Hkv, g, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, Hkv, g, Sq), -jnp.inf, jnp.float32)
    d0 = jnp.zeros((B, Hkv, g, Sq), jnp.float32)

    def step(carry, blk):
        acc, m_run, den = carry
        kc, vc, pc = blk
        qq, kk = _score_inputs(qg, kc)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qq, kk,
                            preferred_element_type=jnp.float32) * scale
        logits = softcap(logits, attn_softcap)
        mask = position_mask(q_pos, pc, window)                   # (B,Sq,blk)
        logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
        blk_max = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m_run, blk_max)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - safe_m), 0.0)
        pexp = jnp.exp(logits - safe_m[..., None])
        pexp = jnp.where(mask[:, None, None], pexp, 0.0)
        pp, vv = _pv_inputs(pexp, vc)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", pp, vv,
            preferred_element_type=jnp.float32)
        den = den * alpha + pexp.sum(-1)
        return (acc, new_m, den), None

    (acc, _, den), _ = jax.lax.scan(step, (acc0, m0, d0), (kb, vb, pb))
    out = acc / jnp.maximum(den[..., None], 1e-37)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dv)
    return out.astype(q.dtype)


def attn_init(rng, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"w": jnp.zeros((hd,))}
        p["k_norm"] = {"w": jnp.zeros((hd,))}
    return p


def attn_qkv(cfg: ModelConfig, p, x, positions, theta: Optional[float] = None):
    """Project to rotated q, k, v.  x: (B,S,d) -> q(B,S,Hq,D), k/v(B,S,Hkv,D)."""
    B, S, _ = x.shape
    theta = theta if theta is not None else cfg.rope_theta
    hd = cfg.resolved_head_dim
    q = dense_matmul(x, p["wq"]).reshape(B, S, cfg.num_heads, hd)
    k = dense_matmul(x, p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = dense_matmul(x, p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"]["w"])
        k = rmsnorm(k, p["k_norm"]["w"])
    if cfg.pos_emb == "rope":
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


def attn_out(cfg: ModelConfig, p, ctx):
    B, S = ctx.shape[:2]
    return dense_matmul(ctx.reshape(B, S, -1), p["wo"])


def attn_scale(cfg: ModelConfig) -> float:
    return (cfg.attn_scale if cfg.attn_scale is not None
            else cfg.resolved_head_dim ** -0.5)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def ffn_init(rng, cfg: ModelConfig, width: Optional[int] = None):
    d = cfg.d_model
    w = width or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {"wi": dense_init(ks[0], d, w), "wg": dense_init(ks[1], d, w),
                "wo": dense_init(ks[2], w, d)}
    return {"wi": dense_init(ks[0], d, w), "wo": dense_init(ks[2], w, d)}


def ffn_apply(cfg: ModelConfig, p, x):
    h = dense_matmul(x, p["wi"])
    if cfg.activation == "swiglu":
        h = jax.nn.silu(dense_matmul(x, p["wg"])) * h
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(dense_matmul(x, p["wg"]), approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    return dense_matmul(h, p["wo"])


# ---------------------------------------------------------------------------
# Embeddings / unembedding
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ModelConfig, params, tokens):
    """tokens: (B,S) int or (B,S,C) int for multi-codebook audio."""
    emb = params["embed"]["tokens"]
    if cfg.num_codebooks:
        # emb: (C, V, d), tokens: (B, S, C) — gather per codebook, sum streams
        parts = [jnp.take(emb[c], tokens[..., c], axis=0)
                 for c in range(cfg.num_codebooks)]
        x = sum(parts)
    else:
        x = jnp.take(emb, tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def unembed(cfg: ModelConfig, params, x):
    """x: (B,S,d) -> logits fp32. Multi-codebook: (B,S,C,V)."""
    xf = x.astype(jnp.float32)
    if cfg.num_codebooks:
        heads = params["embed"].get("heads")
        if heads is None:
            heads = params["embed"]["tokens"]       # tied: (C,V,d)
        logits = jnp.einsum("bsd,cvd->bscv", xf, heads.astype(jnp.float32))
    else:
        embed = params["embed"]
        if cfg.tie_embeddings:
            # tied models unembed through the int8 copy of the (d, V)
            # transposed gather table when the policy quantized one
            # (precision.compress_weights); the gather table itself is
            # never quantized, so embedding lookups stay exact
            head_q8 = embed.get("head_q8")
            if head_q8 is not None:
                logits = dense_matmul(xf, head_q8)
            else:
                logits = xf @ embed["tokens"].astype(jnp.float32).T
        else:
            logits = dense_matmul(xf, embed["head"])
    return softcap(logits, cfg.final_softcap)


def embed_params_init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 3)
    p = {}
    if cfg.num_codebooks:
        p["tokens"] = jnp.stack([
            embed_init(k, cfg.vocab_size, cfg.d_model)
            for k in jax.random.split(ks[0], cfg.num_codebooks)])
    else:
        p["tokens"] = embed_init(ks[0], cfg.vocab_size, cfg.d_model)
        if not cfg.tie_embeddings:
            p["head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size)
    if cfg.pos_emb == "learned":
        p["pos"] = embed_init(ks[2], cfg.max_seq_len, cfg.d_model)
    return p
