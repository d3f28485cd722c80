"""KV / state caches — paper pillar P1 (the K-V cache mechanism, Fig. 2).

The paper caches attention K/V to eliminate recomputation during
autoregressive decoding.  Here the idea is generalized into a *state cache*
abstraction covering every assigned architecture family:

  * full attention   -> (B, S_max, H_kv, D) K/V ring-less cache
  * sliding window   -> (B, W, H_kv, D) ring buffer (bounded memory)
  * MLA (DeepSeek)   -> compressed latent (B, S_max, kv_rank) + shared rope key
  * mLSTM / sLSTM    -> O(1) recurrent matrix/scalar memory
  * hybrid (Hymba)   -> window ring + SSM state + conv state

Every positional cache carries an explicit ``pos`` array (absolute token
position per cache slot, -1 = empty), which makes attention masks exact for
ring buffers and padded batches alike.

All update functions are functional (return a new cache pytree); the decode
step donates the cache buffers (XLA buffer donation = the paper's "memory
reuse"), so on device the update is in-place.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, HYBRID, MLA, MLSTM, SLSTM, LayerSpec,
                                ModelConfig)

# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def layer_cache_shape(cfg: ModelConfig, spec: LayerSpec, batch: int,
                      max_len: int, dtype) -> dict:
    """Abstract (ShapeDtypeStruct-friendly) cache for one layer."""
    hd = cfg.resolved_head_dim
    window = effective_window(cfg, spec, max_len)
    # +1 "dump" slot: prefill padding tokens scatter there (marked pos=-1),
    # so ragged batches never evict live ring entries.  The allocation is
    # rounded up to a multiple of 256 so the sequence dim shards evenly
    # over the mesh (ring arithmetic uses shape-1; entries between the
    # window and the ring age out via the position mask).
    s = (min(window, max_len) if window else max_len) + 1
    s = -(-s // 256) * 256 if s > 256 else s

    def z(shape, dt=dtype):
        return jnp.zeros(shape, dt)

    if spec.mixer == ATTN:
        return {"k": z((batch, s, cfg.num_kv_heads, hd)),
                "v": z((batch, s, cfg.num_kv_heads, hd)),
                "pos": jnp.full((batch, s), -1, jnp.int32)}
    if spec.mixer == MLA:
        m = cfg.mla
        return {"ckv": z((batch, s, m.kv_lora_rank)),
                "kr": z((batch, s, m.rope_head_dim)),
                "pos": jnp.full((batch, s), -1, jnp.int32)}
    if spec.mixer == MLSTM:
        dh = (2 * cfg.d_model) // cfg.num_heads    # mLSTM runs at 2x width
        return {"C": z((batch, cfg.num_heads, dh, dh), jnp.float32),
                "n": z((batch, cfg.num_heads, dh), jnp.float32),
                "m": z((batch, cfg.num_heads), jnp.float32)}
    if spec.mixer == SLSTM:
        dh = cfg.d_model // cfg.num_heads
        return {"c": z((batch, cfg.num_heads, dh), jnp.float32),
                "n": z((batch, cfg.num_heads, dh), jnp.float32),
                "h": z((batch, cfg.num_heads, dh), jnp.float32),
                "m": z((batch, cfg.num_heads), jnp.float32)}
    if spec.mixer == HYBRID:
        ssm = cfg.ssm
        d_inner = ssm.expand * cfg.d_model
        out = {"k": z((batch, s, cfg.num_kv_heads, hd)),
               "v": z((batch, s, cfg.num_kv_heads, hd)),
               "pos": jnp.full((batch, s), -1, jnp.int32),
               "ssm": z((batch, d_inner, ssm.state_size), jnp.float32),
               "conv": z((batch, ssm.conv_size - 1, d_inner))}
        return out
    raise ValueError(spec.mixer)


def effective_window(cfg: ModelConfig, spec: LayerSpec,
                     max_len: int) -> Optional[int]:
    """Layer window, with the beyond-paper long-context override applied to
    global attention layers when serving beyond the native context."""
    w = spec.window
    if (w is None and spec.mixer in (ATTN, MLA)
            and cfg.long_context_override is not None
            and max_len > cfg.native_context):
        w = cfg.long_context_override
    return w


# ---------------------------------------------------------------------------
# Attention-cache updates
# ---------------------------------------------------------------------------


def write_prefill(cache: dict, new: dict, positions) -> dict:
    """Write a full prompt into a (possibly ring) positional cache.

    new: {"k": (B,S,H,D), ...} values aligned with ``positions`` (B,S); a
    position of -1 marks right-padding and is routed to the dump slot.
    For ring caches (ring size W < S) only the last W tokens land.
    """
    out = dict(cache)
    ring = cache["pos"].shape[1] - 1                           # last = dump
    B, S = positions.shape
    take = min(S, ring)
    # per-row: the last `take` *valid* tokens (positions are arange-based or
    # -1 for right-padding, so valid count = max(pos)+1).
    valid = jnp.maximum(positions.max(axis=1) + 1, 0)          # (B,)
    start = jnp.clip(valid - take, 0, S - take)
    idx = start[:, None] + jnp.arange(take)[None, :]           # (B, take)
    b_idx = jnp.arange(B)[:, None]
    pos_w = positions[b_idx, idx]
    slots = jnp.where(pos_w >= 0, pos_w % ring, ring)          # (B, take)
    for key, val in new.items():
        out[key] = cache[key].at[b_idx, slots].set(
            val[b_idx, idx].astype(cache[key].dtype))
    out["pos"] = cache["pos"].at[b_idx, slots].set(pos_w)
    return out


def write_decode(cache: dict, new: dict, lengths) -> dict:
    """Write one token per slot at absolute position ``lengths`` (B,)."""
    out = dict(cache)
    ring = cache["pos"].shape[1] - 1
    slots = lengths % ring
    b_idx = jnp.arange(cache["pos"].shape[0])
    for key, val in new.items():
        out[key] = cache[key].at[b_idx, slots].set(
            val[:, 0].astype(cache[key].dtype))
    out["pos"] = cache["pos"].at[b_idx, slots].set(lengths)
    return out


def cache_mask(cache_pos, q_pos, window: Optional[int]):
    """(B,Sq,Sk) bool mask from stored absolute positions.

    Empty slots (pos == -1) are never attended; ring overwrite correctness
    follows from the stored positions themselves.
    """
    valid = cache_pos[:, None, :] >= 0
    m = (cache_pos[:, None, :] <= q_pos[:, :, None]) & valid
    if window is not None:
        m &= cache_pos[:, None, :] > (q_pos[:, :, None] - window)
    return m


# ---------------------------------------------------------------------------
# Paged (block-table) KV layout — continuous-batching serving path
# ---------------------------------------------------------------------------
#
# Instead of a dense (B, S_max, H, D) cache per slot, K/V live in a shared
# pool of fixed-size pages:
#
#   pk / pv : (P, page, H_kv, D)   physical page pool (per layer)
#   ppos    : (P, page)            absolute position per entry, -1 = empty
#
# plus one *global* block table (B_slots, pages_per_slot) of physical page
# ids shared by every attention layer (page id p belongs to the same request
# in all layers' pools).  Page P-1 is the reserved "dump" page: writes from
# inactive slots and prompt padding land there with pos = -1, so masking
# stays exact without branching.  A sliding-window layer maps positions into
# a logical ring of ceil((window+1)/page) pages — the same physical pages
# are cyclically overwritten, and the stored absolute positions keep the
# attention mask exact (same trick as the dense ring cache above).
#
# Quantized storage mode (Policy.kv_dtype == "int8"): pk/pv hold int8 codes
# and two parallel *scale pools* hold per-entry, per-kv-head fp32 absmax
# scales:
#
#   pk_scale / pv_scale : (P, page, H_kv)
#
# Each written token row (H_kv, D) is quantized independently —
# q = round(x / s), s = absmax(x)/127 — so scatter writes stay
# read-modify-write-free and a token's stored code depends only on its own
# K/V values.  That per-entry determinism is what keeps shared-prefix
# serving bit-identical to unshared serving on a quantized pool: the same
# token row quantizes to the same bytes no matter which request wrote it.
# Scales travel with their pages through COW copies, trie mappings and
# eviction exactly like pk/pv.
#
# A model cache stacks each pool over its layer stack's scan repeats:
# (R, P, page, ...).  The serving layer scan carries the whole stack and
# writes a layer's entries at [layer, phys, off] (the writes below take
# ``layer``), so the step programs update the pool in place; given
# ``layer=None`` they act on one layer's (P, page, ...) pool.

PAGED_KEYS = ("pk", "pv", "ppos", "pk_scale", "pv_scale")
PAGED_DATA_KEYS = ("pk", "pv", "pk_scale", "pv_scale")

INT8_QMAX = 127.0


def quantize_kv(x):
    """Quantize K/V rows to int8 with per-entry, per-head absmax scales.

    x: (..., H, D) float -> (int8 codes (..., H, D), fp32 scales (..., H)).
    All-zero rows get scale 0 (codes 0 -> dequantize to exact 0).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / INT8_QMAX
    q = jnp.round(xf / jnp.maximum(scale, 1e-30)[..., None])
    q = jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` (up to rounding)."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)) \
        .astype(dtype)


def _at(layer, *idx):
    """Index of the entries ``idx`` in a pool leaf: of one layer's pool
    (``layer`` None), or of layer ``layer`` of a whole stack."""
    return idx if layer is None else (layer,) + idx


def _pages_of(cache, pages, layer=None):
    """Index arrays of physical ``pages`` (any shape) in a pool's leaves:
    in layer ``layer`` of a stack, in every layer of a stack where
    ``layer`` is None, or in one layer's pool."""
    if cache["ppos"].ndim == 2:
        return (pages,)
    if layer is None:
        R = cache["ppos"].shape[0]
        return (jnp.arange(R).reshape((R,) + (1,) * pages.ndim), pages[None])
    return (layer, pages)


def _index(key, x, at):
    """``at``, index arrays over the leading dims of pool leaf ``x``,
    extended to reach what a page move of leaf ``key`` reads and writes
    at a time: K/V rows (H, D) whole, positions element by element, and
    scales as whole (page, H) blocks.  These are the forms XLA runs in
    the layout each leaf is kept in; others have it relay the whole
    stack out around them."""
    lead = len(at)
    n = {"pk": 1, "pv": 1, "ppos": x.ndim - lead}.get(key, 0)
    at = tuple(jnp.reshape(a, jnp.shape(a) + (1,) * n) for a in at)
    return at + tuple(jnp.arange(d).reshape((d,) + (1,) * (n - 1 - i))
                      for i, d in enumerate(x.shape[lead:lead + n]))


def _scatter_kv(cache: dict, out: dict, val, idx) -> None:
    """Scatter new K/V rows into ``out``'s pools at ``idx`` ((phys, off)
    or (layer, phys, off)) — quantizing (codes + scale rows) when the
    pool is int8, casting to the pool dtype otherwise.  ``val``:
    {"k"/"v": (..., H, D)} rows aligned with phys/off.  Shared by the
    prefill-chunk and decode-single-token writes so the two paths can
    never desynchronize their quantized layout."""
    quant = "pk_scale" in cache
    for key, pool_key in (("k", "pk"), ("v", "pv")):
        if quant:
            q, sc = quantize_kv(val[key])
            out[pool_key] = cache[pool_key].at[idx].set(
                _widen(q, cache[pool_key]))
            skey = pool_key + "_scale"
            out[skey] = cache[skey].at[idx].set(_widen(sc, cache[skey]))
        else:
            out[pool_key] = cache[pool_key].at[idx].set(
                _widen(val[key].astype(cache[pool_key].dtype),
                       cache[pool_key]))


def _widen(rows, leaf):
    """``rows`` zero-padded in their minor dim to pool ``leaf``'s, which
    :func:`paged_layer_cache_shape` may have widened to whole lanes."""
    pad = leaf.shape[-1] - rows.shape[-1]
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)]) \
        if pad else rows


def paged_pool_bytes(cache: dict) -> int:
    """Total bytes of every paged-pool leaf (K/V pages + scale pools +
    positions) across a full model cache — the number pool sizing and
    the serving metrics report."""
    total = 0
    for stack_c in cache["layers"]:
        for c in stack_c:
            if isinstance(c, dict):
                for key in PAGED_KEYS:
                    if key in c:
                        a = c[key]
                        total += a.size * a.dtype.itemsize
    return total


def pool_lanes(device) -> int:
    """The multiple a paged pool's rows are widened to on ``device``: a
    TPU keeps an array whose minor dim is not a whole number of its
    128 lanes with another dim minor (pages, for a pool), which the
    paged kernels cannot read in place; at whole lanes its default
    layout is row-major, the kernels' layout."""
    return 128 if device.platform == "tpu" else 1


def paged_layer_cache_shape(cfg: ModelConfig, spec: LayerSpec,
                            num_pages: int, page_size: int, max_slots: int,
                            max_len: int, dtype,
                            kv_dtype: str = "auto", lanes: int = 1) -> dict:
    """Paged cache for one layer.  ATTN / HYBRID attention K/V become page
    pools; MLA and recurrent families keep their dense per-slot state (the
    slot API — admit/retire — is identical for them).

    kv_dtype selects pool storage: "auto" stores at ``dtype``; "bf16" /
    "fp16" override the pool dtype; "int8" stores quantized codes plus
    per-entry scale pools — for pure attention layers only.  Hybrid
    layers keep full-precision pools (their SSM/conv state is dense fp32
    anyway), the same families that opt out of prefix sharing.

    ``lanes`` (:func:`pool_lanes`) widens each K/V row (head dim) and
    each scale row (heads) to a multiple of it, zero-padded: the writes
    pad new rows, and readers take the first head-dim (heads) entries.
    """
    from repro.core.precision import kv_store_dtype
    hd, H = cfg.resolved_head_dim, cfg.num_kv_heads
    quant = kv_dtype == "int8" and spec.mixer == ATTN
    pool_dtype = (jnp.int8 if quant
                  else kv_store_dtype(kv_dtype, dtype, allow_int8=False))

    def wide(n):
        return -(-n // lanes) * lanes

    def pool():
        P = num_pages + 1                               # +1 dump page
        out = {"pk": jnp.zeros((P, page_size, H, wide(hd)), pool_dtype),
               "pv": jnp.zeros((P, page_size, H, wide(hd)), pool_dtype),
               "ppos": jnp.full((P, page_size), -1, jnp.int32)}
        if quant:
            out["pk_scale"] = jnp.zeros((P, page_size, wide(H)),
                                        jnp.float32)
            out["pv_scale"] = jnp.zeros((P, page_size, wide(H)),
                                        jnp.float32)
        return out

    if spec.mixer == ATTN:
        return pool()
    if spec.mixer == HYBRID:
        out = pool()
        dense = layer_cache_shape(cfg, spec, max_slots, max_len, dtype)
        out["ssm"] = dense["ssm"]
        out["conv"] = dense["conv"]
        return out
    # MLA / mLSTM / sLSTM: dense per-slot state behind the same slot API
    return layer_cache_shape(cfg, spec, max_slots, max_len, dtype)


def paged_ring_len(window: Optional[int], page_size: int,
                   pages_per_slot: int) -> int:
    """Logical ring length (multiple of page_size) a layer writes into.
    Full attention uses the whole per-slot page range; windowed layers
    cycle through ceil((window+1)/page) logical pages."""
    if window is None:
        return pages_per_slot * page_size
    pages_w = -(-(window + 1) // page_size)
    return min(pages_w, pages_per_slot) * page_size


def paged_write_prefill(cache: dict, new: dict, cache_pos, block_tables, *,
                        ring_len: int, layer=None) -> dict:
    """Scatter a prompt's K/V into pool pages via the slot block tables.

    cache_pos: (B, S) absolute positions (-1 = padding); block_tables:
    (B, pages_per_slot) physical page ids (-1 = unallocated).  Only the
    last min(S, ring_len) valid tokens per row are written (ring layers
    would otherwise scatter twice into one entry, which is unordered).
    """
    out = dict(cache)
    page = cache["ppos"].shape[-1]
    dump = cache["ppos"].shape[-2] - 1
    B, S = cache_pos.shape
    take = min(S, ring_len)
    valid = jnp.maximum(cache_pos.max(axis=1) + 1, 0)          # (B,)
    start = jnp.clip(valid - take, 0, S - take)
    idx = start[:, None] + jnp.arange(take)[None, :]           # (B, take)
    b_idx = jnp.arange(B)[:, None]
    pos_w = cache_pos[b_idx, idx]                              # (B, take)
    rp = jnp.where(pos_w >= 0, pos_w % ring_len, 0)
    lp, off = rp // page, rp % page
    phys = jnp.take_along_axis(block_tables, lp, axis=1)       # (B, take)
    ok = (pos_w >= 0) & (phys >= 0)
    phys = jnp.where(ok, phys, dump)
    at = _at(layer, phys, off)
    _scatter_kv(cache, out,
                {key: new[key][b_idx, idx] for key in ("k", "v")},
                at)                                            # (B,take,H,D)
    out["ppos"] = cache["ppos"].at[at].set(jnp.where(ok, pos_w, -1))
    return out


def paged_write_decode(cache: dict, new: dict, lengths, block_tables,
                       active=None, *, ring_len: int, layer=None) -> dict:
    """Write one token per slot at absolute position ``lengths`` (B,).
    Inactive slots (active == False) are routed to the dump page."""
    out = dict(cache)
    page = cache["ppos"].shape[-1]
    dump = cache["ppos"].shape[-2] - 1
    B = lengths.shape[0]
    rp = lengths % ring_len
    lp, off = rp // page, rp % page
    phys = block_tables[jnp.arange(B), lp]
    ok = phys >= 0
    if active is not None:
        ok &= active
    phys = jnp.where(ok, phys, dump)
    at = _at(layer, phys, off)
    _scatter_kv(cache, out, {key: new[key][:, 0] for key in ("k", "v")},
                at)                                            # (B, H, D)
    out["ppos"] = cache["ppos"].at[at].set(jnp.where(ok, lengths, -1))
    return out


def paged_write_decode_multi(cache: dict, new: dict, lengths, block_tables,
                             active=None, *, ring_len: int,
                             layer=None) -> dict:
    """Scatter a speculation window of ``K1`` tokens per slot at absolute
    positions ``lengths[b] .. lengths[b] + K1 - 1`` (the draft-verify
    forward writes the pending token plus every drafted token in one
    pass; rejected entries are rewound afterwards via
    :func:`paged_truncate`).

    new: {"k"/"v": (B, K1, H, D)}; ``active``: (B,) or (B, K1) bool —
    masked entries go to the dump page.  Unlike the single-token decode
    write, positions at or beyond ``ring_len`` are *dumped*, never
    wrapped: a speculative write that wrapped the ring would clobber
    live early-context entries that a rejection could not restore
    (windowed/ring layers therefore must not take this path — the
    engine gates speculation to non-windowed attention).
    """
    out = dict(cache)
    page = cache["ppos"].shape[-1]
    dump = cache["ppos"].shape[-2] - 1
    B, K1 = new["k"].shape[:2]
    pos = lengths[:, None] + jnp.arange(K1)[None, :]           # (B, K1)
    ok = pos < ring_len
    rp = jnp.where(ok, pos, 0)
    lp, off = rp // page, rp % page
    phys = jnp.take_along_axis(block_tables, lp, axis=1)       # (B, K1)
    ok &= phys >= 0
    if active is not None:
        ok &= active if active.ndim == 2 else active[:, None]
    phys = jnp.where(ok, phys, dump)
    at = _at(layer, phys, off)
    _scatter_kv(cache, out, {key: new[key] for key in ("k", "v")},
                at)                                            # (B,K1,H,D)
    out["ppos"] = cache["ppos"].at[at].set(jnp.where(ok, pos, -1))
    return out


def paged_write_packed(cache: dict, new: dict, slot_ids, positions,
                       block_tables, *, ring_len: int, layer=None) -> dict:
    """Scatter a token-packed stream's K/V: token t of the flat stream
    belongs to slot ``slot_ids[t]`` at absolute position ``positions[t]``
    and lands in that slot's pages via ``block_tables``.

    new: {"k"/"v": (1, T, H, D)}; slot_ids/positions: (T,) with -1 =
    padding lane.  Like the speculative multi-write, positions at or
    beyond ``ring_len`` are *dumped*, never wrapped — the packed path is
    gated to non-windowed attention, so a wrap would only ever clobber
    live context.  Padding lanes, unallocated pages and out-of-range
    positions all route to the dump page.
    """
    out = dict(cache)
    page = cache["ppos"].shape[-1]
    dump = cache["ppos"].shape[-2] - 1
    B = block_tables.shape[0]
    ok = (slot_ids >= 0) & (positions >= 0) & (positions < ring_len)
    rp = jnp.where(ok, positions, 0)
    lp, off = rp // page, rp % page
    safe_slot = jnp.clip(slot_ids, 0, B - 1)
    phys = block_tables[safe_slot, lp]                          # (T,)
    ok &= phys >= 0
    phys = jnp.where(ok, phys, dump)
    at = _at(layer, phys, off)
    _scatter_kv(cache, out, {key: new[key][0] for key in ("k", "v")},
                at)                                             # (T, H, D)
    out["ppos"] = cache["ppos"].at[at].set(jnp.where(ok, positions, -1))
    return out


def paged_truncate(cache, block_tables, keep_len) -> dict:
    """Rewind speculative writes: mark every entry of the slots' pages
    whose absolute position is >= ``keep_len[b]`` empty (pos = -1).

    Stale K/V codes (and int8 scale rows) may remain in the page pools —
    they are unreachable once their positions are -1, exactly like the
    stale data :func:`reset_pages` leaves behind — so only ``ppos``
    needs rewriting.  Safe under sharing: a shared prefix page only
    holds positions < matched_len <= keep_len, so its write-back is a
    no-op even when several slots scatter it in one call, and dump-page
    rows (block table -1) are always -1 already.
    """
    if "ppos" not in cache:
        return cache
    out = dict(cache)
    # pool dim is second-to-last: ppos is (P, page) or (R, P, page)
    P = cache["ppos"].shape[-2]
    safe = jnp.where(block_tables >= 0, block_tables, P - 1)  # (B, npages)
    # each page's bound, the least over the rows that hold it (a shared
    # page holds no position at or past any sharer's bound)
    bound = jnp.full((P,), jnp.iinfo(jnp.int32).max, jnp.int32).at[safe] \
        .min(jnp.broadcast_to(keep_len[:, None], safe.shape)
             .astype(jnp.int32))
    out["ppos"] = jnp.where(cache["ppos"] >= bound[:, None], -1,
                            cache["ppos"])
    return out


def paged_truncate_all(cache: dict, block_tables, keep_len) -> dict:
    """:func:`paged_truncate` over every paged layer of a model cache."""
    return {"layers": tuple(
        tuple(paged_truncate(c, block_tables, keep_len) for c in stack_c)
        for stack_c in cache["layers"])}


def paged_gather(cache: dict, block_tables, layer=None, dim=None):
    """Dense per-slot view of the pool (of layer ``layer`` of a stacked
    one): (B, pages*page, H, D) k/v plus (B, pages*page) positions, D
    the pool's row or the first ``dim`` entries of it (a widened pool,
    :func:`paged_layer_cache_shape`).  Unallocated table entries read
    the dump page and are masked to pos = -1.  Quantized pools are
    dequantized on gather (fp32 out; callers cast to their compute
    dtype)."""
    dump = cache["ppos"].shape[-2] - 1
    at = _at(layer, jnp.where(block_tables >= 0, block_tables, dump))
    rows = {k: a[_index(k, a, at)] for k, a in cache.items()
            if k in PAGED_KEYS}
    k, v = rows["pk"][..., :dim], rows["pv"][..., :dim]   # (B,pages,page,H,D)
    if "pk_scale" in cache:
        H = k.shape[-2]
        k = dequantize_kv(k, rows["pk_scale"][..., :H])
        v = dequantize_kv(v, rows["pv_scale"][..., :H])
    kp = jnp.where((block_tables >= 0)[..., None],
                   rows["ppos"], -1)           # (B, pages, page)
    B, npg, page = kp.shape
    return (k.reshape(B, npg * page, *k.shape[3:]),
            v.reshape(B, npg * page, *v.shape[3:]),
            kp.reshape(B, npg * page))


def copy_pages(cache, src, dst, keep_below, layer=None) -> dict:
    """Copy-on-write clone: page ``dst[i]`` becomes a copy of ``src[i]``
    with only the entries at absolute positions ``0 <= pos <
    keep_below[i]`` kept valid (the rest are masked to pos = -1).  On a
    stacked pool, in layer ``layer`` (the serving layer scan copies each
    layer's tail pages before that layer writes), or in every layer.

    This is how a request whose prefix match ends *inside* a page gets a
    private tail page: the shared source page is read, never written,
    and the writer's suffix prefill / decode lands in the copy.  Rows
    may be padded with src = dst = dump, keep_below = 0 (the dump page's
    positions are forced to -1, which is their invariant anyway).
    """
    if "ppos" not in cache:
        return cache
    out = dict(cache)
    at_src = _pages_of(cache, src, layer)
    at_dst = _pages_of(cache, dst, layer)
    for k in PAGED_KEYS:
        if k in cache:
            rows = cache[k][_index(k, cache[k], at_src)]
            if k == "ppos":                            # ([R,] N, page)
                keep = (rows >= 0) & (rows < keep_below[:, None])
                rows = jnp.where(keep, rows, -1)
            out[k] = cache[k].at[_index(k, cache[k], at_dst)].set(rows)
    return out


def reset_pages_all(cache: dict, pages) -> dict:
    """:func:`reset_pages` over every layer of a full model cache."""
    return {"layers": tuple(tuple(reset_pages(c, pages) for c in stack_c)
                            for stack_c in cache["layers"])}


def reset_pages(cache, pages) -> dict:
    """Mark freshly (re)allocated physical pages empty (``pages`` may be
    padded with the dump page id, whose pos is always -1 anyway).  Only
    ``ppos`` needs clearing: stale K/V from a page's previous owner is
    unreachable once its positions are -1."""
    if "ppos" not in cache:
        return cache
    out = dict(cache)
    fresh = jnp.zeros(cache["ppos"].shape[-2], bool).at[pages].set(True)
    out["ppos"] = jnp.where(fresh[:, None], -1, cache["ppos"])
    return out


# -- host-memory offload / restore: the overload escape valve ---------------
#
# Under pool pressure the scheduler preempts a slot: its pages' bytes move
# to host memory (``offload_pages``) so the device pages can be freed, and
# move back verbatim (``restore_pages``) when the request is re-admitted —
# decode then resumes bit-identically, no recompute.  The same primitives
# back the prefix cache's host spill tier.  Both run outside jit (rare
# events on the slow path); ordering is safe because the engine always
# threads the *latest* cache pytree through them.


def offload_pages(cache: dict, pages) -> list:
    """Snapshot the full contents of physical ``pages`` to host memory.

    Returns a nested blob ``[per stack][per layer]`` where paged layers
    contribute ``{leaf key: np.ndarray}`` covering every paged leaf
    (K/V codes, int8 scale pools, positions — ``PAGED_KEYS``) and
    non-paged layers (dense per-slot state) contribute ``None``.  The
    gather device-syncs; leaves with a leading scan-repeats dim keep it.
    """
    import numpy as np
    pages = np.asarray(pages, np.int32)
    blob = []
    for stack_c in cache["layers"]:
        row = []
        for c in stack_c:
            if not (isinstance(c, dict) and "ppos" in c):
                row.append(None)
                continue
            rep = c["ppos"].ndim == 3          # leading scan-repeats dim
            row.append({k: np.asarray(c[k][:, pages] if rep
                                      else c[k][pages])
                        for k in PAGED_KEYS if k in c})
        blob.append(row)
    return blob


def restore_pages(cache: dict, blob: list, pages) -> dict:
    """Scatter an :func:`offload_pages` blob back into physical ``pages``
    (any pages — restore need not land where the snapshot was taken).
    Every paged leaf row is overwritten wholesale, so no prior
    ``reset_pages`` is needed: stale previous-owner state cannot survive.
    """
    pages = jnp.asarray(pages, jnp.int32)
    layers = []
    for stack_c, brow in zip(cache["layers"], blob):
        row = []
        for c, b in zip(stack_c, brow):
            if b is None:
                row.append(c)
                continue
            at = _pages_of(c, pages)
            row.append({k: c[k].at[_index(k, c[k], at)].set(b[k])
                        if k in b else c[k] for k in c})
        layers.append(tuple(row))
    return {"layers": tuple(layers)}


def blob_bytes(blob: list) -> int:
    """Host bytes an :func:`offload_pages` blob occupies (what the
    byte-budgeted host tier accounts against its capacity)."""
    return sum(a.nbytes for row in blob for d in row if d
               for a in d.values())


# -- slot view / merge: admission prefill on a slot subset ------------------


def slot_view(cache: dict, n_view: int) -> dict:
    """A fresh ``n_view``-slot working view of a persistent multi-slot
    cache: paged pool leaves pass through (they are shared, indexed via
    block tables), per-slot leaves come back *empty* (zeros, pos = -1) —
    an admitted request always starts from clean slot state."""

    def fresh(key, a):
        shape = (a.shape[0], n_view) + a.shape[2:]      # [repeats, slots,...]
        if key == "pos":
            return jnp.full(shape, -1, a.dtype)
        return jnp.zeros(shape, a.dtype)

    def layer(c):
        return {k: (v if k in PAGED_KEYS else fresh(k, v))
                for k, v in c.items()}

    return {"layers": tuple(tuple(layer(c) for c in stack_c)
                            for stack_c in cache["layers"])}


def slot_merge(cache: dict, view: dict, slots) -> dict:
    """Scatter a slot view produced by :func:`slot_view` (and updated by a
    prefill) back into the persistent cache at ``slots`` (n_view,)."""

    def layer(c, vv):
        return {k: (vv[k] if k in PAGED_KEYS
                    else c[k].at[:, slots].set(vv[k].astype(c[k].dtype)))
                for k in c}

    return {"layers": tuple(
        tuple(layer(c, vv) for c, vv in zip(sc, sv))
        for sc, sv in zip(cache["layers"], view["layers"]))}
