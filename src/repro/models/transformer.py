"""Unified decoder over stacks of repeating layer patterns.

Every assigned architecture — dense, MoE(+MLA), xLSTM, Hymba hybrid, VLM and
audio backbones — is this one module driven by its :class:`ModelConfig`.
Layer stacks run as ``lax.scan`` over pattern repeats (HLO stays the size of
one pattern), with dense caches carried as scan xs/ys for prefill/decode and
paged pools whole in the scan's carry, updated in place.

Entry points:
    init_params(rng, cfg, policy)
    forward_train(params, cfg, tokens, ...)   -> (logits, aux)
    forward_prefill(params, cfg, tokens, ...) -> (logits, cache, moe)
    forward_decode(params, cfg, tokens, ...)  -> (logits, cache, moe)

Every serving forward (prefill, decode, verify, mixed, packed) returns,
after its logits and cache, the routing counters of its expert layers:
int32 [token-expert assignments, experts hit, most rows of one expert],
zeros for a model with none.
    init_cache(cfg, batch, max_len, dtype)    -> cache pytree
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, DENSE_FFN, HYBRID, MLA, MLSTM, MOE_FFN,
                                NO_FFN, SLSTM, LayerSpec, ModelConfig, Stack)
from repro.core import kv_cache as KV
from repro.core.precision import FP32, Policy
from repro.models import attention_mla as MLAT
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def layer_init(rng, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(rng, 6)
    p = {"norm1": L.norm_init(cfg)}
    if spec.mixer == ATTN:
        p["attn"] = L.attn_init(ks[0], cfg)
    elif spec.mixer == MLA:
        p["attn"] = MLAT.mla_init(ks[0], cfg)
    elif spec.mixer == MLSTM:
        p["mixer"] = SSM.mlstm_init(ks[0], cfg)
    elif spec.mixer == SLSTM:
        p["mixer"] = SSM.slstm_init(ks[0], cfg)
    elif spec.mixer == HYBRID:
        p["attn"] = L.attn_init(ks[0], cfg)
        p["mamba"] = SSM.mamba_init(ks[1], cfg)
        p["bn_attn"] = L.norm_init(cfg)
        p["bn_ssm"] = L.norm_init(cfg)
    if cfg.sandwich_norm:
        p["norm1_post"] = L.norm_init(cfg)
    if spec.ffn != NO_FFN:
        p["norm2"] = L.norm_init(cfg)
        p["ffn"] = (L.ffn_init(ks[2], cfg) if spec.ffn == DENSE_FFN
                    else MOE.moe_init(ks[2], cfg))
        if cfg.sandwich_norm:
            p["norm2_post"] = L.norm_init(cfg)
    return p


def _stack_init(rng, cfg: ModelConfig, stack: Stack):
    out = []
    for pi, spec in enumerate(stack.pattern):
        keys = jax.random.split(jax.random.fold_in(rng, pi), stack.repeats)
        out.append(jax.vmap(lambda k, s=spec: layer_init(k, cfg, s))(keys))
    return tuple(out)


def mtp_init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 3)
    return {"norm_h": L.norm_init(cfg), "norm_e": L.norm_init(cfg),
            "proj": L.dense_init(ks[0], 2 * cfg.d_model, cfg.d_model),
            "layer": layer_init(ks[1], cfg, LayerSpec(mixer=ATTN,
                                                      ffn=DENSE_FFN))}


def init_params(rng, cfg: ModelConfig, policy: Policy = FP32):
    """Random parameters in ``policy.param_dtype``.  Built under one jit
    with the cast inside, so each leaf's fp32 sample fuses into its cast
    and only the param-dtype tree is ever allocated (a full-width bf16
    model then fits a device its fp32 tree would not)."""
    return _init_params_jit(rng, cfg, policy)


def _init_params(rng, cfg: ModelConfig, policy: Policy):
    ks = jax.random.split(rng, len(cfg.stacks) + 3)
    params = {
        "embed": L.embed_params_init(ks[0], cfg),
        "final_norm": L.norm_init(cfg),
        "stacks": tuple(_stack_init(ks[2 + i], cfg, s)
                        for i, s in enumerate(cfg.stacks)),
    }
    if cfg.mtp:
        params["mtp"] = mtp_init(ks[1], cfg)
    return policy.cast_params(params)


_init_params_jit = jax.jit(_init_params, static_argnums=(1, 2))


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Allocate the full model cache (stacked over scan repeats)."""
    layers = []
    for stack in cfg.stacks:
        per_pos = []
        for spec in stack.pattern:
            one = KV.layer_cache_shape(cfg, spec, batch, max_len, dtype)
            per_pos.append(jax.tree.map(
                lambda a, r=stack.repeats: jnp.tile(
                    a[None], (r,) + (1,) * a.ndim), one))
        layers.append(tuple(per_pos))
    return {"layers": tuple(layers)}


def cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16):
    """ShapeDtypeStruct version (no allocation) for dry-run lowering."""
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_len, dtype))


def init_paged_cache(cfg: ModelConfig, *, num_pages: int, page_size: int,
                     max_slots: int, max_len: int, dtype=jnp.bfloat16,
                     kv_dtype: str = "auto", lanes: int = 1):
    """Paged-pool model cache for continuous batching: attention layers
    share ``num_pages`` fixed-size pages (+1 reserved dump page) indexed
    through per-slot block tables; MLA / recurrent layers keep dense
    per-slot state.  Same stacked-over-repeats layout as init_cache.
    ``kv_dtype`` selects pool storage (int8 adds per-entry scale pools)
    and ``lanes`` the multiple its rows are widened to (see
    ``kv_cache.paged_layer_cache_shape``)."""
    layers = []
    for stack in cfg.stacks:
        per_pos = []
        for spec in stack.pattern:
            one = KV.paged_layer_cache_shape(cfg, spec, num_pages, page_size,
                                             max_slots, max_len, dtype,
                                             kv_dtype=kv_dtype, lanes=lanes)
            per_pos.append(jax.tree.map(
                lambda a, r=stack.repeats: jnp.tile(
                    a[None], (r,) + (1,) * a.ndim), one))
        layers.append(tuple(per_pos))
    return {"layers": tuple(layers)}


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------


def layer_apply(cfg: ModelConfig, spec: LayerSpec, p, x, *, positions,
                cache_pos, cache, mode: str, max_len: int,
                attend_cache: bool = False, paged=None):
    """Returns (x, new_cache, aux, moe). cache is None in train mode;
    moe is the routing counters of an expert layer (``moe.no_counts()``
    elsewhere and in training).
    attend_cache: prefill continues from a pre-filled cache (prefix
    caching) — queries attend to cache contents, not just in-context k/v.
    paged: {"block_tables": (B, pages), "active": (B,) bool | None} when
    the cache uses the paged pool layout (continuous batching); with
    "layer", the cache's pool leaves are the layer stack's whole pools,
    written and read in place at that layer; "cow": (src, dst,
    keep_below) copy-on-write tail pages, copied in the layer before it
    writes (``kv_cache.copy_pages``).
    """
    aux = jnp.zeros((), jnp.float32)
    B, S, _ = x.shape
    window = KV.effective_window(cfg, spec, max_len)
    h = L.apply_norm(cfg, p["norm1"], x)
    new_cache = None
    is_paged = cache is not None and "pk" in cache
    if mode in ("verify", "packed") and (not is_paged
                                         or spec.mixer != ATTN):
        # multi-token windows (speculative verify, chunked prefill, the
        # token-packed ragged stream) are defined only over paged
        # pure-attention layers (the same families prefix sharing
        # supports): ring layers cannot roll back overwrites,
        # recurrent/MLA state has no per-position rewind and no legal
        # mid-prompt chunk boundary.  The engine gates before dispatch;
        # this is the backstop.
        raise NotImplementedError(
            f"{mode} mode is unsupported for layer family '{spec.mixer}' "
            f"/ dense caches")

    # ----- mixer ----------------------------------------------------------
    if spec.mixer in (ATTN, HYBRID):
        theta = (cfg.rope_theta_local
                 if (window is not None and cfg.rope_theta_local is not None)
                 else cfg.rope_theta)
        q, k, v = L.attn_qkv(cfg, p["attn"], h, positions, theta=theta)
        scale = L.attn_scale(cfg)
        if is_paged:
            bt = paged["block_tables"]
            lyr = paged.get("layer")
            pool = {n: cache[n] for n in KV.PAGED_KEYS if n in cache}
            if paged.get("cow") is not None:
                pool = KV.copy_pages(pool, *paged["cow"], layer=lyr)
            quant = "pk_scale" in pool
            ring = KV.paged_ring_len(window, pool["ppos"].shape[-1],
                                     bt.shape[1])
            if mode == "decode":
                c_attn = KV.paged_write_decode(
                    pool, {"k": k, "v": v}, positions[:, 0], bt,
                    paged.get("active"), ring_len=ring, layer=lyr)
                ctx = L.mha_attention_paged(
                    q, c_attn, bt, positions, window=window, scale=scale,
                    attn_softcap=cfg.attn_softcap, layer=lyr)
            elif mode == "verify":
                # speculative window: write the pending + drafted tokens'
                # K/V (positions[:, 0] .. positions[:, 0] + K), THEN
                # attend — the stored positions give each of the K+1
                # queries an exact causal mask over earlier drafts.
                # Rejected entries are rewound by the engine afterwards
                # (kv_cache.paged_truncate).
                c_attn = KV.paged_write_decode_multi(
                    pool, {"k": k, "v": v}, positions[:, 0], bt,
                    paged.get("active"), ring_len=ring, layer=lyr)
                ctx = L.mha_attention_paged(
                    q, c_attn, bt, positions, window=window, scale=scale,
                    attn_softcap=cfg.attn_softcap, layer=lyr)
            elif mode == "packed":
                # token-packed ragged stream: scatter every lane's K/V
                # into its OWN slot's pages, then attend each lane to its
                # slot's whole paged history (block tables are indexed
                # per lane via slot_ids, not per row).
                c_attn = KV.paged_write_packed(
                    pool, {"k": k, "v": v}, paged["slot_ids"],
                    positions[0], bt, ring_len=ring, layer=lyr)
                ctx = L.mha_attention_paged_packed(
                    q, c_attn, bt, positions, paged["slot_ids"],
                    paged.get("packed_meta"), window=window, scale=scale,
                    attn_softcap=cfg.attn_softcap, layer=lyr)
            elif attend_cache or (quant and window is None):
                # prefix-cached admission: the prompt's suffix is written
                # into this request's own pages first, then queries attend
                # the *gathered* block table — shared prefix pages (mapped
                # zero-copy by the radix cache) and the fresh suffix alike.
                # Only windowless full attention reaches here (ring layers
                # opt out of sharing: their pages are overwritten in
                # place, see prefix_cache.shareable).
                # Quantized pools take this path even without a prefix
                # match (start == 0): attending the written-then-gathered
                # pages means every query sees the same dequantized K/V
                # that decode will later read, which keeps shared-prefix
                # int8 serving bit-identical to unshared int8 serving.
                c_attn = KV.paged_write_prefill(
                    pool, {"k": k, "v": v}, cache_pos, bt, ring_len=ring,
                    layer=lyr)
                kk, vv, kp = KV.paged_gather(c_attn, bt, lyr, q.shape[-1])
                ctx = L.mha_attention(q, kk.astype(x.dtype),
                                      vv.astype(x.dtype), positions, kp,
                                      window=window, scale=scale,
                                      attn_softcap=cfg.attn_softcap)
            else:                                   # admission prefill
                ctx = L.mha_attention(q, k, v, positions, positions,
                                      window=window, scale=scale,
                                      attn_softcap=cfg.attn_softcap)
                c_attn = KV.paged_write_prefill(
                    pool, {"k": k, "v": v}, cache_pos, bt, ring_len=ring,
                    layer=lyr)
        elif mode == "decode":
            c_attn = {n: cache[n] for n in ("k", "v", "pos")}
            c_attn = KV.write_decode(c_attn, {"k": k, "v": v}, positions[:, 0])
            ctx = L.mha_attention(q, c_attn["k"].astype(x.dtype),
                                  c_attn["v"].astype(x.dtype),
                                  positions, c_attn["pos"], window=window,
                                  scale=scale, attn_softcap=cfg.attn_softcap)
        elif mode == "prefill" and attend_cache:
            c_attn = KV.write_prefill(
                {n: cache[n] for n in ("k", "v", "pos")},
                {"k": k, "v": v}, cache_pos)
            ctx = L.mha_attention(q, c_attn["k"].astype(x.dtype),
                                  c_attn["v"].astype(x.dtype),
                                  positions, c_attn["pos"], window=window,
                                  scale=scale, attn_softcap=cfg.attn_softcap)
        else:
            ctx = L.mha_attention(q, k, v, positions, positions,
                                  window=window, scale=scale,
                                  attn_softcap=cfg.attn_softcap)
            c_attn = None
            if mode == "prefill":
                c_attn = KV.write_prefill(
                    {n: cache[n] for n in ("k", "v", "pos")},
                    {"k": k, "v": v}, cache_pos)
        mixer_out = L.attn_out(cfg, p["attn"], ctx)

        if spec.mixer == HYBRID:
            if mode == "train":
                ssm_state, conv_state = SSM.mamba_zero_state(cfg, B, x.dtype)
            else:
                ssm_state, conv_state = cache["ssm"], cache["conv"]
            ssm_out, ssm_state, conv_state = SSM.mamba_apply(
                cfg, p["mamba"], h, ssm_state, conv_state, mode)
            mixer_out = 0.5 * (L.apply_norm(cfg, p["bn_attn"], mixer_out)
                               + L.apply_norm(cfg, p["bn_ssm"], ssm_out))
            if mode != "train":
                new_cache = dict(c_attn)
                new_cache["ssm"] = ssm_state
                new_cache["conv"] = conv_state
        else:
            new_cache = c_attn

    elif spec.mixer == MLA:
        if mode == "decode":
            mixer_out, new_cache = MLAT.mla_decode(cfg, p["attn"], h, cache,
                                                   positions[:, 0])
        elif mode == "prefill" and attend_cache:
            mixer_out, new_cache = MLAT.mla_prefill_cached(
                cfg, p["attn"], h, cache, positions, cache_pos,
                window=window)
        else:
            mixer_out, to_cache = MLAT.mla_full(cfg, p["attn"], h, positions,
                                                positions, window=window)
            if mode == "prefill":
                new_cache = KV.write_prefill(cache, to_cache, cache_pos)

    elif spec.mixer in (MLSTM, SLSTM):
        fn = SSM.mlstm_apply if spec.mixer == MLSTM else SSM.slstm_apply
        zero = (SSM.mlstm_zero_state if spec.mixer == MLSTM
                else SSM.slstm_zero_state)
        state = zero(cfg, B) if mode == "train" else cache
        mixer_out, state = fn(cfg, p["mixer"], h, state, mode)
        if mode != "train":
            new_cache = state
    else:
        raise ValueError(spec.mixer)

    if cfg.sandwich_norm:
        mixer_out = L.apply_norm(cfg, p["norm1_post"], mixer_out)
    x = x + mixer_out

    # ----- ffn -------------------------------------------------------------
    moe = MOE.no_counts()
    if spec.ffn != NO_FFN:
        h2 = L.apply_norm(cfg, p["norm2"], x)
        kind = "sigmoid" if cfg.mla is not None else "softmax"
        if spec.ffn == DENSE_FFN:
            out = L.ffn_apply(cfg, p["ffn"], h2)
        elif mode == "train":
            out, moe_aux = MOE.moe_apply_capacity(cfg, p["ffn"], h2, kind)
            aux = aux + moe_aux
        else:
            out, moe = MOE.moe_apply_dropless(
                cfg, p["ffn"], h2, kind,
                valid=_real_tokens(mode, cache_pos, paged))
        if cfg.sandwich_norm:
            out = L.apply_norm(cfg, p["norm2_post"], out)
        x = x + out

    return x, new_cache, aux, moe


def _real_tokens(mode: str, cache_pos, paged):
    """Which rows of a serving forward are real tokens, broadcastable to
    (B, S), or None for all: packed lanes with a slot, active decode and
    verify rows, prompt positions inside each prompt's length."""
    paged = paged or {}
    if mode == "packed":
        return (paged["slot_ids"] >= 0)[None, :]
    if mode == "prefill":
        return cache_pos >= 0
    active = paged.get("active")
    if active is None:
        return None
    return active if active.ndim == 2 else active[:, None]


# ---------------------------------------------------------------------------
# Stack runner (scan over repeats)
# ---------------------------------------------------------------------------


def _run_stack(cfg, stack: Stack, stack_p, stack_c, x, *, positions,
               cache_pos, mode, max_len, remat, attend_cache=False,
               paged=None):
    has_cache = mode != "train"
    # serving keeps each expert layer's stacks whole, outside the scan:
    # the grouped matmul reads the scanned layer's experts in place, where
    # the scan's slice of a stack would be copied out before the kernel
    # could read it (MOE.EXPERT_MATS)
    whole = {}
    if has_cache:
        stack_p = list(stack_p)
        for pi, spec in enumerate(stack.pattern):
            if spec.ffn == MOE_FFN:
                ffn = dict(stack_p[pi]["ffn"])
                whole[pi] = {n: ffn.pop(n) for n in MOE.EXPERT_MATS
                             if n in ffn}
                stack_p[pi] = {**stack_p[pi], "ffn": ffn}
        stack_p = tuple(stack_p)
    # likewise each paged pool rides whole in the scan's carry: a layer
    # writes and reads its entries in place at [layer, ...], where a pool
    # in xs/ys would be sliced out and written back whole every layer.
    # Dense per-slot state (MLA, recurrent, a hybrid's SSM) stays in xs.
    pools = {}
    if has_cache:
        stack_c = list(stack_c)
        for pi, c in enumerate(stack_c):
            if "pk" in c:
                pools[pi] = {k: c[k] for k in KV.PAGED_KEYS if k in c}
                stack_c[pi] = {k: v for k, v in c.items()
                               if k not in KV.PAGED_KEYS}
        stack_c = tuple(stack_c)

    def body(carry, xs):
        xx, aux, moe, pools = carry
        if has_cache:
            p_r, c_r, layer = xs
        else:
            (p_r,) = xs
            c_r = (None,) * len(stack.pattern)
        new_cs = []
        for pi, spec in enumerate(stack.pattern):
            p_l, c_l, paged_l = p_r[pi], c_r[pi], paged
            if pi in whole:
                p_l = {**p_l, "ffn": {**p_l["ffn"], **whole[pi],
                                      "layer": layer}}
            if pi in pools:
                c_l = {**c_l, **pools[pi]}
                paged_l = {**paged, "layer": layer}
            xx, nc, a, mc = layer_apply(
                cfg, spec, p_l, xx, positions=positions,
                cache_pos=cache_pos, cache=c_l, mode=mode,
                max_len=max_len, attend_cache=attend_cache, paged=paged_l)
            if pi in pools:
                pools = {**pools, pi: {k: nc[k] for k in pools[pi]}}
                nc = {k: v for k, v in nc.items() if k not in pools[pi]}
            new_cs.append(nc)
            aux = aux + a
            moe = MOE.merge_counts(moe, mc)
        return (xx, aux, moe, pools), (tuple(new_cs) if has_cache else None)

    if remat and mode == "train":
        body = jax.checkpoint(body)

    xs = ((stack_p, stack_c, jnp.arange(stack.repeats)) if has_cache
          else (stack_p,))
    (x, aux, moe, pools), new_cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), MOE.no_counts(), pools), xs)
    if pools:
        new_cache = tuple({**c, **pools.get(pi, {})}
                          for pi, c in enumerate(new_cache))
    return x, new_cache, aux, moe


def _run_all(cfg, params, x, *, positions, cache_pos, cache, mode, max_len,
             remat=False, attend_cache=False, paged=None):
    """Every stack in turn -> (x, new cache, aux loss, routing counters
    summed over the expert layers)."""
    new_layers = []
    aux = jnp.zeros((), jnp.float32)
    moe = MOE.no_counts()
    for si, stack in enumerate(cfg.stacks):
        sc = cache["layers"][si] if cache is not None else None
        x, nc, a, mc = _run_stack(cfg, stack, params["stacks"][si], sc, x,
                                  positions=positions, cache_pos=cache_pos,
                                  mode=mode, max_len=max_len, remat=remat,
                                  attend_cache=attend_cache, paged=paged)
        new_layers.append(nc)
        aux = aux + a
        moe = MOE.merge_counts(moe, mc)
    new_cache = {"layers": tuple(new_layers)} if cache is not None else None
    return x, new_cache, aux, moe


# ---------------------------------------------------------------------------
# Embedding plumbing
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens, prefix_embeds, positions, policy):
    x = L.embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    if cfg.pos_emb == "learned":
        pe = params["embed"]["pos"]
        x = x + jnp.take(pe, jnp.clip(positions, 0, pe.shape[0] - 1),
                         axis=0).astype(x.dtype)
    elif cfg.pos_emb == "sinusoidal":
        d = cfg.d_model
        half = d // 2
        freqs = jnp.exp(-jnp.arange(half) / half * jnp.log(10000.0))
        ang = positions[..., None].astype(jnp.float32) * freqs
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + pe.astype(x.dtype)
    x = x.astype(policy.compute_dtype)
    return _maybe_seq_parallel(x)


def _maybe_seq_parallel(x):
    """seq_parallel (§Perf): shard the token/sequence dim of activations
    over the `model` axis instead of tensor-parallel weights — the right
    scheme when head counts don't divide the TP degree (GSPMD would
    otherwise reshard full activations around every per-head op).  The
    constraint propagates through the whole stack; attention gathers K/V
    across the axis as needed."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import perf_flags
    from repro.sharding import partition as SH
    if not perf_flags.flag("seq_parallel"):
        return x
    mesh = SH.current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or x.shape[1] % mesh.shape["model"] != 0):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, "model", None)))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def forward_train(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
                  policy: Policy = FP32, remat: bool = True):
    """tokens: (B,S) int32 (or (B,S,C) audio). Returns (logits, aux dict)."""
    B = tokens.shape[0]
    S = tokens.shape[1] + (prefix_embeds.shape[1] if prefix_embeds is not None
                           else 0)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = _embed(cfg, params, tokens, prefix_embeds, positions, policy)
    x, _, aux, _ = _run_all(cfg, params, x, positions=positions,
                            cache_pos=None, cache=None, mode="train",
                            max_len=S, remat=remat)
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    logits = policy.output_cast(L.unembed(cfg, params, h_final))
    aux_d = {"moe_aux": aux}
    if cfg.mtp and "mtp" in params:
        aux_d["mtp_logits"] = _mtp_forward(params, cfg, x, tokens, positions,
                                           policy)
    return logits, aux_d


def _mtp_forward(params, cfg, h, tokens, positions, policy):
    """DeepSeek multi-token prediction: predict t_{i+2} from h_i + emb_{i+1}."""
    p = params["mtp"]
    emb_next = L.embed_tokens(cfg, params, tokens[:, 1:]).astype(h.dtype)
    h_cur = h[:, :-1]
    merged = jnp.concatenate(
        [L.apply_norm(cfg, p["norm_h"], h_cur),
         L.apply_norm(cfg, p["norm_e"], emb_next)], axis=-1)
    x = merged @ p["proj"].astype(h.dtype)
    x, _, _, _ = layer_apply(cfg, LayerSpec(ATTN, DENSE_FFN), p["layer"], x,
                             positions=positions[:, :-1], cache_pos=None,
                             cache=None, mode="train",
                             max_len=positions.shape[1])
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    return policy.output_cast(L.unembed(cfg, params, h_final))


def forward_prefill(params, cfg: ModelConfig, tokens, prompt_lengths, cache,
                    *, prefix_embeds=None, policy: Policy = FP32,
                    max_len: Optional[int] = None, last_only: bool = False,
                    start: int = 0, paged=None):
    """Process full (right-padded) prompts, fill the cache.

    prompt_lengths: (B,) valid token count per row *including* prefix
    embeddings but *excluding* ``start``.  ``start`` > 0 continues from a
    pre-filled cache (prefix caching: the paper's "extract content
    offline" applied to a shared prompt's KV); it may be a static int or
    a per-row (B,) array (paged admission, where each request resumes
    from its own matched prefix length).  Returns
    (logits (B,S,V), cache, moe) — or (B,1,V) logits when ``last_only``
    (production serving: unembed only the sampled position, which for a
    262k vocab saves terabytes of logits at 32k prefill).
    """
    B = tokens.shape[0]
    S = tokens.shape[1] + (prefix_embeds.shape[1] if prefix_embeds is not None
                           else 0)
    max_len = max_len or _cache_max_len(cfg, cache)
    attend = not (isinstance(start, int) and start == 0)
    start = jnp.asarray(start, jnp.int32).reshape(-1, 1)    # (1,1) or (B,1)
    positions = start + jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cache_pos = jnp.where(positions < start + prompt_lengths[:, None],
                          positions, -1)
    x = _embed(cfg, params, tokens, prefix_embeds, positions, policy)
    x, cache, _, moe = _run_all(cfg, params, x, positions=positions,
                                cache_pos=cache_pos, cache=cache,
                                mode="prefill", max_len=max_len,
                                attend_cache=attend, paged=paged)
    if last_only:
        x = jnp.take_along_axis(
            x, (prompt_lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    logits = policy.output_cast(L.unembed(cfg, params, h_final))
    return logits, cache, moe


def forward_decode(params, cfg: ModelConfig, tokens, cache, lengths, *,
                   policy: Policy = FP32, max_len: Optional[int] = None,
                   paged=None):
    """One new token per slot. tokens: (B,1); lengths: (B,) current context
    length (the new token's absolute position). Returns (logits, cache,
    moe)."""
    B = tokens.shape[0]
    max_len = max_len or _cache_max_len(cfg, cache)
    positions = lengths[:, None]
    x = _embed(cfg, params, tokens, None, positions, policy)
    x, cache, _, moe = _run_all(cfg, params, x, positions=positions,
                                cache_pos=None, cache=cache, mode="decode",
                                max_len=max_len, paged=paged)
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    logits = policy.output_cast(L.unembed(cfg, params, h_final))
    return logits, cache, moe


def forward_verify(params, cfg: ModelConfig, tokens, cache, lengths, *,
                   policy: Policy = FP32, max_len: Optional[int] = None,
                   paged=None):
    """Speculative verify: score a K+1-token window per slot in ONE
    forward against the paged cache.

    tokens: (B, K+1) — the pending token followed by K drafted tokens;
    lengths: (B,) the pending token's absolute position (same convention
    as :func:`forward_decode`, which is the K == 0 case).  Every layer
    writes the whole window's K/V into its paged pool (masked by
    ``paged["active"]``), and each query position attends causally via
    the stored positions — including the window's own earlier tokens.
    Returns (logits (B, K+1, V), cache, moe); logits[:, j] is the target
    distribution for the token following tokens[:, j], so the rejection
    sampler (``sampling.speculative_verify``) reads acceptance straight
    off this one pass.  The caller must rewind rejected entries
    (``kv_cache.paged_truncate_all``) before the next step retires or
    shares those pages.

    Only paged pure-attention models support verify (see layer_apply's
    gate) — the engine falls back to plain decode elsewhere.
    """
    B, K1 = tokens.shape
    max_len = max_len or _cache_max_len(cfg, cache)
    positions = lengths[:, None] + jnp.arange(K1)[None, :]
    x = _embed(cfg, params, tokens, None, positions, policy)
    x, cache, _, moe = _run_all(cfg, params, x, positions=positions,
                                cache_pos=None, cache=cache, mode="verify",
                                max_len=max_len, paged=paged)
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    logits = policy.output_cast(L.unembed(cfg, params, h_final))
    return logits, cache, moe


def forward_mixed(params, cfg: ModelConfig, tokens, cache, row_start, n_q, *,
                  policy: Policy = FP32, max_len: Optional[int] = None,
                  paged=None):
    """Mixed chunked-prefill / decode forward: per-slot variable-length
    token windows against the paged pool in one pass.  The unified
    engine calls it with packed single-chunk rows (B = 1, W = the
    iteration's width bucket); the layout is general — any mix of
    decode rows (1 token), chunk rows, and idle rows batches fine.

    tokens: (B, W) — row b carries ``n_q[b]`` real tokens left-aligned
    (1 pending token for decode rows, a prompt chunk for prefill rows,
    0 for idle slots); row_start: (B,) the absolute position of each
    row's first token (its write position).  Every real token's K/V is
    scattered into the slot's pages (``paged_write_decode_multi``,
    quantizing on int8 pools) and each query attends the slot's whole
    paged history — pages written by *earlier* chunks, prefix-cache
    pages mapped zero-copy at admission, and the window's own earlier
    tokens (stored positions make the intra-window causal mask exact),
    so any chunk boundary is legal, page-aligned or not.

    Returns (logits (B, 1, V) at each row's LAST real token, cache,
    moe):
    for decode rows that is the next-token distribution, for a prompt's
    final chunk it seeds sampling; other chunk rows' logits are
    computed-and-discarded by the caller.  Padding lanes carry -1
    positions: their writes land on the dump page and their queries are
    fully masked (zero output), so idle slots never perturb the pool.

    Gated like speculative verify to paged pure-attention families (see
    ``layer_apply``); the engine falls back to bucketed whole-prompt
    admission elsewhere.
    """
    B, W = tokens.shape
    max_len = max_len or _cache_max_len(cfg, cache)
    valid = jnp.arange(W)[None, :] < n_q[:, None]
    positions = jnp.where(valid,
                          row_start[:, None] + jnp.arange(W)[None, :], -1)
    paged = dict(paged or {})
    paged["active"] = valid
    x = _embed(cfg, params, tokens, None, positions, policy)
    x, cache, _, moe = _run_all(cfg, params, x, positions=positions,
                                cache_pos=None, cache=cache, mode="verify",
                                max_len=max_len, paged=paged)
    # unembed only each row's sampled position (last real token) — the
    # same logits economy as forward_prefill(last_only=True)
    idx = jnp.maximum(n_q - 1, 0)
    x = jnp.take_along_axis(x, idx[:, None, None].astype(jnp.int32), axis=1)
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    logits = policy.output_cast(L.unembed(cfg, params, h_final))
    return logits, cache, moe


def forward_packed(params, cfg: ModelConfig, tokens, cache, slot_ids,
                   positions, seg_last, *, policy: Policy = FP32,
                   max_len: Optional[int] = None, paged=None):
    """Token-packed ragged forward: a WHOLE scheduler iteration — every
    live slot's decode token plus every admitting slot's prefill-chunk
    tokens — as ONE (1, T) dispatch against the paged pool.

    tokens: (1, T) flat stream (decode tokens first, then chunk tokens,
    zero-padded to the width bucket); slot_ids: (T,) owning slot per
    lane (-1 = padding); positions: (T,) absolute positions (-1 =
    padding); seg_last: (S,) stream index of each segment's LAST real
    token (one segment per decode slot, then one per chunk; padded
    entries point at lane 0 and are discarded by the caller).

    Generalizes :func:`forward_mixed` from per-slot rows to a flat
    ragged stream: no per-chunk width buckets, no per-row padding —
    the only padded lanes are the tail up to the single global bucket
    T, so padded-FLOP waste is ~zero and the engine issues one dispatch
    per iteration instead of ``1 + #chunks``.  K/V writes are scattered
    per lane into each lane's own slot's pages
    (``kv_cache.paged_write_packed``: quant-aware, dump-page routed for
    padding, COW-safe because admission re-points fresh pages before
    dispatch exactly as on the bucketed path), and each lane attends its
    slot's whole paged history under its own causal mask.

    Returns (logits (1, S, V) at each segment's last token, cache, moe) —
    decode segments read their next-token distribution, final chunks
    seed sampling, earlier chunks are computed-and-discarded.  Gated
    like verify/mixed to paged pure-attention families.
    """
    max_len = max_len or _cache_max_len(cfg, cache)
    pos2 = positions[None, :]
    paged = dict(paged or {})
    paged["slot_ids"] = slot_ids
    x = _embed(cfg, params, tokens, None, pos2, policy)
    x, cache, _, moe = _run_all(cfg, params, x, positions=pos2,
                                cache_pos=None, cache=cache, mode="packed",
                                max_len=max_len, paged=paged)
    # unembed only the sampled positions (forward_mixed's logits economy,
    # one gather for all segments)
    x = jnp.take_along_axis(x, seg_last[None, :, None].astype(jnp.int32),
                            axis=1)
    h_final = L.apply_norm(cfg, params["final_norm"], x)
    logits = policy.output_cast(L.unembed(cfg, params, h_final))
    return logits, cache, moe


def _cache_max_len(cfg: ModelConfig, cache) -> int:
    """Recover the max_len the cache was built with (largest pos dim - 1)."""
    best = 0
    for stack_c in cache["layers"]:
        for c in stack_c:
            if isinstance(c, dict) and "pos" in c:
                best = max(best, c["pos"].shape[-1] - 1)
    return best or cfg.max_seq_len
