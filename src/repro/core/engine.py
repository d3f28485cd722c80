"""Batched inference engine — where the paper's pillars compose.

  * P1: KV-cache prefill/decode split, half-precision policy, buffer
    donation (decode updates the cache in place = Paddle "memory reuse").
  * P2: optionally runs a pruned model with id remapping at the boundary.
  * P4: dynamic length-bucketed batching via :class:`DynamicBatcher`.

Also provides the *baseline* path (``use_kv_cache=False``) that re-runs the
full forward for every generated token — the paper's Table-1 row 1 — so the
speedup of the optimized stack is measurable against it.
"""
from __future__ import annotations

import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, HYBRID, MLSTM, SLSTM,
                                ModelConfig)
from repro.core import kv_cache as KV
from repro.core import prefix_cache as PC
from repro.core import pruning as PR
from repro.core.continuous import (ContinuousScheduler, FaultConfig,
                                   HostKVStore, PageAllocator, ServeMetrics,
                                   attention_work, page_work)
from repro.core.precision import BF16, Policy, compress_weights
from repro.core.sampling import SamplingParams, sample, speculative_verify
from repro.core.speculative import SpecConfig, get_drafter
from repro.core.scheduler import (DEFAULT_BUCKETS, Batch, DynamicBatcher,
                                  Request, pad_batch, pick_bucket,
                                  truncate_prompt)
from repro.core.tokenizer import EOS
from repro.models import moe as MOE
from repro.models import transformer as T


# Default per-iteration token budget of the unified scheduler: decode
# tokens from every live slot plus prefill-chunk tokens from admitting
# slots must fit under it, so a long prompt can never monopolize a step.
DEFAULT_MAX_BATCHED_TOKENS = 256


def mixed_width_buckets(budget: int) -> tuple:
    """Padded window widths the unified scheduler's mixed forwards are
    traced at: per-iteration chunk widths bucket up into this set, so
    the compiled-shape count stays bounded no matter how scheduling
    timing slices the prompts; the budget itself caps the set.  Exposed
    so benches can pre-warm every width (a chunk's width depends on how
    many slots were decoding when it was scheduled — i.e. on arrival
    timing — so a measured run may otherwise hit an uncompiled shape)."""
    return tuple(w for w in (8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                             4096) if w < budget) + (budget,)


def packed_width_buckets(budget: int) -> tuple:
    """Stream widths the packed (1, T) dispatch is traced at.  The
    packed kernel only constrains T to multiples of its 8-lane query
    tile, so the stream buckets far finer than the power-of-two chunk
    widths: a <=32-shape ladder whose step scales with the budget keeps
    padded lanes near the ladder-step remainder (under 10% of stream
    lanes in steady state) without growing the compiled-shape count
    unboundedly.  Exposed so benches can pre-warm every stream width."""
    cap = -(-budget // 8) * 8                # budget, 8-lane aligned
    step = max(8, -(-(cap // 32) // 8) * 8)  # ~cap/32, 8-lane aligned
    return tuple(sorted({min(i * step, cap)
                         for i in range(1, -(-cap // step) + 1)}))


@dataclass
class EngineStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    nocache_s: float = 0.0
    prompt_tokens: int = 0
    generated_tokens: int = 0
    batches: int = 0

    def merge(self, other: "EngineStats"):
        for f in self.__dataclass_fields__:
            setattr(self, f, getattr(self, f) + getattr(other, f))


def _packed_block_pages(cache, pages_per_slot: int) -> Optional[int]:
    """The packed kernel's pages per block
    (``decode_attention.packed_block_pages``) at this paged cache's
    attention pools; None without one."""
    from repro.kernels import decode_attention as DA
    for stack_c in cache["layers"]:
        for c in stack_c:
            if isinstance(c, dict) and "pk" in c:
                return DA.packed_block_pages(c["pk"], c["pv"],
                                             c.get("pk_scale"),
                                             pages_per_slot)
    return None


class InferenceEngine:
    """Single-host serving engine for one model (single-stream vocab).

    Multi-codebook (audio) models are served through ``launch/serve.py``'s
    serve_step directly; this engine covers the text path the paper targets.
    """

    def __init__(self, cfg: ModelConfig, params, *, policy: Policy = BF16,
                 max_batch: int = 8, max_len: int = 512,
                 use_kv_cache: bool = True, donate: bool = True,
                 prune_maps: Optional[PR.PruneMaps] = None, seed: int = 0):
        self.cfg = cfg
        self.policy = policy
        self.params = policy.cast_params(params)
        # serve-time weight compression (weights_dtype axis): quantize /
        # recast the dense serve-path matmul weights AFTER cast_params
        # (which would recast the fp32 scales of a quantized tree).
        # Timed so the serve trace's weights event can carry it.
        t_q = time.perf_counter()
        self.params, self.weight_stats = compress_weights(self.params,
                                                          policy)
        jax.block_until_ready(self.params)
        self.weight_quant_s = time.perf_counter() - t_q
        self.max_batch = max_batch
        self.max_len = max_len
        self.use_kv_cache = use_kv_cache
        self.prune_maps = prune_maps
        self.rng = jax.random.PRNGKey(seed)
        self.stats = EngineStats()
        self._donate = donate
        self._cont_cache = {}          # (sp, steps) -> jitted fns
        self._paged_ctx = None         # persistent paged pool + radix trie

        def prefill_fn(params, tokens, lengths, cache):
            return T.forward_prefill(params, cfg, tokens, lengths, cache,
                                     policy=policy, max_len=max_len)[:2]

        def decode_fn(params, tokens, cache, lengths):
            return T.forward_decode(params, cfg, tokens, cache, lengths,
                                    policy=policy, max_len=max_len)[:2]

        def full_fn(params, tokens):
            return T.forward_train(params, cfg, tokens, policy=policy,
                                   remat=False)[0]

        def decode_n_fn(params, first_tok, cache, lengths, n_steps):
            """Fused greedy decode loop (beyond-paper): one compiled
            lax.scan instead of n host dispatches — removes per-token
            launch overhead, keeps the cache update in place."""

            def body(carry, _):
                tok, cache, lens, done = carry
                logits, cache, _ = T.forward_decode(
                    params, cfg, tok[:, None], cache, lens, policy=policy,
                    max_len=max_len)
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                nxt = jnp.where(done, 0, nxt)
                done = done | (nxt == EOS)     # EOS itself is not emitted
                emit = jnp.where(done, -1, nxt)
                return (nxt, cache, lens + 1, done), emit

            B = first_tok.shape[0]
            done0 = first_tok == EOS
            carry = (jnp.where(done0, 0, first_tok), cache, lengths, done0)
            carry, emitted = jax.lax.scan(body, carry, None, length=n_steps)
            return emitted.T, carry[1]                    # (B, n), cache

        dn = (3,) if donate else ()
        self._prefill = jax.jit(prefill_fn, donate_argnums=dn)
        self._decode = jax.jit(decode_fn,
                               donate_argnums=(2,) if donate else ())
        self._decode_n = jax.jit(decode_n_fn, static_argnums=(4,),
                                 donate_argnums=(2,) if donate else ())
        self._full = jax.jit(full_fn)

    # ------------------------------------------------------------------
    def prompt_buckets(self):
        """Prompt length buckets bounded by the engine context: max_len is
        always the final bucket, so prompts that fit are never truncated
        below it and prompts beyond it can't silently overflow the cache."""
        return tuple(b for b in DEFAULT_BUCKETS if b < self.max_len) \
            + (self.max_len,)

    # ------------------------------------------------------------------
    def generate_batch(self, tokens: np.ndarray, lengths: np.ndarray,
                       max_new_tokens: int,
                       sp: SamplingParams = SamplingParams(),
                       stop_at_eos: bool = True) -> np.ndarray:
        """tokens: (B, L) right-padded int32. Returns (B, max_new) ids
        (PAD-filled after EOS)."""
        if self.prune_maps is not None:
            tokens = PR.remap_tokens(tokens, self.prune_maps)
        if self.use_kv_cache:
            out = self._generate_kv(tokens, lengths, max_new_tokens, sp,
                                    stop_at_eos)
        else:
            out = self._generate_nocache(tokens, lengths, max_new_tokens, sp,
                                         stop_at_eos)
        if self.prune_maps is not None:
            out = PR.unmap_tokens(np.maximum(out, 0), self.prune_maps) \
                * (out >= 0) + out * (out < 0)
        return out

    # -- prefix caching (paper §1: "extracted relevant content offline") --
    def set_prefix(self, prefix_tokens, *, page_size: int = 16,
                   num_pages: Optional[int] = None,
                   slots: Optional[int] = None) -> None:
        """Prefill a shared prompt prefix into the paged pool ONCE and
        pin it in the radix prefix cache: every later request admitted by
        :meth:`serve_continuous` that starts with these tokens maps the
        prefix pages zero-copy and only prefills its own suffix.

        Only layer families that support page sharing can be seeded (see
        ``prefix_cache.shareable``); for opted-out families this warns
        and is a no-op — serving stays correct, just without reuse.  The
        geometry arguments must match the later ``serve_continuous`` call
        (they share the persistent pool).
        """
        toks = [int(t) for t in prefix_tokens]
        reason = PC.shareable(self.cfg, self.max_len)
        if reason is not None:
            warnings.warn(f"set_prefix: prefix sharing disabled — {reason}")
            return
        if len(toks) > self.max_len - 1:
            raise ValueError(f"prefix of {len(toks)} tokens leaves no room "
                             f"to generate within max_len={self.max_len}")
        ctx = self._paged_context(page_size, num_pages, slots)
        n = -(-len(toks) // page_size)
        pages = ctx["alloc"].alloc(n)
        if pages is None:
            ctx["trie"].evict(n - ctx["alloc"].free_count)
            pages = ctx["alloc"].alloc(n)
        if pages is None:
            raise ValueError(f"prefix needs {n} pages; pool has only "
                             f"{ctx['alloc'].free_count} free")
        seed = self._cont_cache.get("seed")
        if seed is None:
            cfg, policy, max_len = self.cfg, self.policy, self.max_len

            def seed_fn(params, tokens, length, block_row, pages_a, cache):
                cache = KV.reset_pages_all(cache, pages_a)
                view = KV.slot_view(cache, 1)
                paged = {"block_tables": block_row,
                         "active": jnp.ones((1,), bool)}
                _, view, _ = T.forward_prefill(
                    params, cfg, tokens, length, view, policy=policy,
                    max_len=max_len, last_only=True, paged=paged)
                return KV.slot_merge(cache, view,
                                     jnp.zeros((1,), jnp.int32))

            seed = jax.jit(seed_fn,
                           donate_argnums=(5,) if self._donate else ())
            self._cont_cache["seed"] = seed
        row = np.full((1, ctx["pages_per_slot"]), -1, np.int32)
        row[0, :n] = pages
        pages_a = np.full((1, ctx["pages_per_slot"]), ctx["dump"], np.int32)
        pages_a[0, :n] = pages
        ctx["cache"] = seed(self.params,
                            jnp.asarray([toks], jnp.int32),
                            jnp.asarray([len(toks)], jnp.int32),
                            jnp.asarray(row), jnp.asarray(pages_a),
                            ctx["cache"])
        jax.block_until_ready(ctx["cache"]["layers"])
        # the trie takes its own reference on retained pages; ours drops
        ctx["trie"].insert(toks, pages, len(toks), pin=True)
        for p in pages:
            ctx["alloc"].decref(p)

    def clear_prefix(self) -> None:
        """Unpin all seeded prefixes: their pages stay cached but become
        ordinary LRU-evictable radix entries."""
        if self._paged_ctx is not None:
            self._paged_ctx["trie"].unpin_all()

    def reset_prefix_cache(self) -> None:
        """Drop the persistent paged pool and radix trie entirely (cold
        cache).  Jitted functions are kept, so the next serve pays no
        retrace — benchmarks use this to measure cold-trie serving with
        warm compilation."""
        self._paged_ctx = None

    # -- optimized path (P1) --------------------------------------------
    def _generate_kv(self, tokens, lengths, max_new, sp, stop_at_eos):
        B = tokens.shape[0]
        cache = T.init_cache(self.cfg, B, self.max_len,
                             self.policy.kv_cache_dtype(dense=True))
        t0 = time.perf_counter()
        toks = jnp.asarray(tokens, jnp.int32)
        lens = jnp.asarray(lengths, jnp.int32)
        logits, cache = self._prefill(self.params, toks,
                                      jnp.asarray(lengths, jnp.int32),
                                      cache)
        logits = jax.block_until_ready(logits)
        t1 = time.perf_counter()

        out = np.full((B, max_new), -1, np.int64)
        # logits cover the suffix only; last real token is suffix-local
        last = logits[jnp.arange(B), jnp.asarray(lengths, jnp.int32) - 1]
        self.rng, sub = jax.random.split(self.rng)
        first = sample(last, sub, sp)

        if sp.temperature <= 0.0 and max_new > 1 and stop_at_eos:
            # fused greedy loop: a single compiled scan over the steps;
            # `first` sits at absolute position `lens`
            first_np = np.asarray(first)
            out[:, 0] = np.where(first_np == EOS, -1, first_np)
            emitted, cache = self._decode_n(self.params, first, cache,
                                            lens, max_new - 1)
            out[:, 1:] = np.asarray(emitted)
        else:
            done = np.zeros((B,), bool)
            nxt = first
            for step in range(max_new):
                nxt_np = np.asarray(nxt)
                if stop_at_eos:
                    done |= nxt_np == EOS
                out[~done, step] = nxt_np[~done]
                if done.all() or step == max_new - 1:
                    break
                logits1, cache = self._decode(self.params, nxt[:, None],
                                              cache, lens + step)
                self.rng, sub = jax.random.split(self.rng)
                nxt = sample(logits1[:, 0], sub, sp)
        jax.block_until_ready(cache["layers"])
        t2 = time.perf_counter()
        self.stats.merge(EngineStats(
            prefill_s=t1 - t0, decode_s=t2 - t1,
            prompt_tokens=int(lengths.sum()),
            generated_tokens=int((out >= 0).sum()), batches=1))
        return out

    # -- paper Table-1 baseline: no KV cache ------------------------------
    def _generate_nocache(self, tokens, lengths, max_new, sp, stop_at_eos):
        B, L = tokens.shape
        total = L + max_new
        buf = np.zeros((B, total), np.int32)
        buf[:, :L] = tokens
        lens = np.asarray(lengths).copy()
        out = np.full((B, max_new), -1, np.int64)
        done = np.zeros((B,), bool)
        t0 = time.perf_counter()
        for step in range(max_new):
            logits = self._full(self.params, jnp.asarray(buf))
            last = logits[jnp.arange(B), jnp.asarray(lens - 1)]
            self.rng, sub = jax.random.split(self.rng)
            nxt = np.asarray(sample(last, sub, sp))
            if stop_at_eos:
                done |= nxt == EOS
            out[~done, step] = nxt[~done]
            buf[np.arange(B), lens] = np.where(done, 0, nxt)
            lens = lens + (~done).astype(lens.dtype)
            if done.all():
                break
        t1 = time.perf_counter()
        self.stats.merge(EngineStats(
            nocache_s=t1 - t0, prompt_tokens=int(np.sum(lengths)),
            generated_tokens=int((out >= 0).sum()), batches=1))
        return out

    # -- continuous batching (paged KV, in-flight admission) --------------
    def _paged_context(self, page_size: int, num_pages: Optional[int],
                       slots: Optional[int]) -> dict:
        """The persistent paged serving context: pool arrays, refcounted
        allocator, and the radix prefix trie.  It survives across
        ``serve_continuous`` calls (and is what ``set_prefix`` seeds), so
        cached prefixes keep paying off run after run.  A geometry change
        rebuilds it from scratch (dropping any cached prefixes, loudly).

        Pool storage follows ``policy.kv_dtype``: int8 halves K/V bytes
        per token (pool sizing below accounts for the parallel scale
        pools), so the same byte budget holds ~2x the pages.
        """
        slots = slots or self.max_batch
        pages_per_slot = -(-self.max_len // page_size)
        if num_pages is None:
            num_pages = slots * pages_per_slot
        key = (page_size, num_pages, slots)
        if self._paged_ctx is not None and self._paged_ctx["key"] == key:
            return self._paged_ctx
        if self._paged_ctx is not None:
            warnings.warn(
                f"paged pool geometry changed {self._paged_ctx['key']} -> "
                f"{key}; rebuilding (cached prefixes are dropped)")
        kv_dtype = self.policy.kv_dtype
        if kv_dtype == "int8" and not any(
                spec.mixer == ATTN
                for stack in self.cfg.stacks for spec in stack.pattern):
            warnings.warn("kv_dtype=int8 requested but no attention layer "
                          "has a paged pool to quantize; state stays at "
                          "full precision")
        alloc = PageAllocator(num_pages)
        cache = T.init_paged_cache(
            self.cfg, num_pages=num_pages, page_size=page_size,
            max_slots=slots, max_len=self.max_len,
            dtype=self.policy.compute_dtype, kv_dtype=kv_dtype,
            lanes=KV.pool_lanes(jax.devices()[0]))
        pool_bytes = KV.paged_pool_bytes(cache)
        self._paged_ctx = {
            "key": key, "page_size": page_size, "num_pages": num_pages,
            "slots": slots, "pages_per_slot": pages_per_slot,
            "dump": num_pages, "alloc": alloc,
            "kv_dtype": kv_dtype,
            "kv_pool_bytes": pool_bytes,
            # per token of pool capacity (incl. the dump page), summed
            # over layers — scale pools and position bookkeeping included
            "kv_bytes_per_token": pool_bytes / ((num_pages + 1) * page_size),
            "trie": PC.RadixPrefixCache(alloc, page_size),
            "cache": cache,
        }
        return self._paged_ctx

    def _continuous_fns(self, sp: SamplingParams, steps_per_sync: int):
        """Build (once per (sp, steps) combo) the jitted entry points of
        the continuous path.  ``step`` drives every decode-only sync;
        the admit functions are the *bucketed fallback* for layer
        families the unified chunked scheduler cannot serve (ring /
        recurrent / MLA state — see ``serve_continuous``):

        * admit: bucket-padded prefill of a batch of same-bucket requests
          that scatters K/V straight into their freshly allocated pool
          pages (and resets those pages' stale positions), merges dense
          per-slot state into the slot rows, and samples each first
          token — one dispatch per admission group.
        * admit_prefix: the radix-cache variant — copies each request's
          partial tail page (copy-on-write; shared pages are never
          written), prefills only the *unmatched suffix* from its per-row
          start offset, and attends over the gathered block table so the
          suffix sees the shared prefix KV it never computed.
        * step: a lax.scan fusing ``steps_per_sync`` iterations of
          [decode all slots -> sample on device -> scatter KV into pages],
          so the sampled path costs one host round-trip per *sync*, not
          per token.
        """
        key = (sp, steps_per_sync)
        cached = self._cont_cache.get(key)
        if cached is not None:
            return cached
        cfg, policy, max_len = self.cfg, self.policy, self.max_len

        def admit_fn(params, tokens, length, slot, block_row, pages, cache,
                     rng):
            cache = KV.reset_pages_all(cache, pages)
            view = KV.slot_view(cache, tokens.shape[0])
            paged = {"block_tables": block_row,
                     "active": jnp.ones((tokens.shape[0],), bool)}
            logits, view, moe = T.forward_prefill(
                params, cfg, tokens, length, view, policy=policy,
                max_len=max_len, last_only=True, paged=paged)
            cache = KV.slot_merge(cache, view, slot)
            rng, sub = jax.random.split(rng)
            first = sample(logits[:, 0], sub, sp)
            return first, cache, rng, moe

        def admit_prefix_fn(params, tokens, length, start, slot, block_row,
                            pages, cow_src, cow_dst, cow_keep, cache, rng):
            # order matters: reset the fresh pages' stale positions, THEN
            # copy-on-write the partial tail (the destination is one of
            # the fresh pages), THEN prefill the suffix into it (each
            # layer copies its tail before it writes)
            cache = KV.reset_pages_all(cache, pages)
            view = KV.slot_view(cache, tokens.shape[0])
            paged = {"block_tables": block_row,
                     "active": jnp.ones((tokens.shape[0],), bool),
                     "cow": (cow_src, cow_dst, cow_keep)}
            logits, view, moe = T.forward_prefill(
                params, cfg, tokens, length, view, policy=policy,
                max_len=max_len, last_only=True, start=start, paged=paged)
            cache = KV.slot_merge(cache, view, slot)
            rng, sub = jax.random.split(rng)
            first = sample(logits[:, 0], sub, sp)
            return first, cache, rng, moe

        def step_fn(params, tok, lens, rem, act, block_tables, cache, rng):
            paged = {"block_tables": block_tables}

            def body(carry, _):
                tok, lens, rem, act, cache, rng, moe = carry
                logits, cache, mc = T.forward_decode(
                    params, cfg, tok[:, None], cache, lens, policy=policy,
                    max_len=max_len, paged={**paged, "active": act})
                rng, sub = jax.random.split(rng)
                nxt = sample(logits[:, 0], sub, sp)
                is_eos = nxt == EOS                  # EOS is not emitted
                emit = jnp.where(act & ~is_eos, nxt, -1)
                still = act & ~is_eos & (rem > 1)
                lens = lens + act.astype(lens.dtype)
                rem = rem - act.astype(rem.dtype)
                tok = jnp.where(still, nxt, tok)
                return (tok, lens, rem, still, cache, rng,
                        MOE.merge_counts(moe, mc)), (emit, act)

            carry, (emits, acts) = jax.lax.scan(
                body, (tok, lens, rem, act, cache, rng, MOE.no_counts()),
                None, length=steps_per_sync)
            tok, lens, rem, act, cache, rng, moe = carry
            return tok, lens, rem, act, cache, rng, emits.T, acts.T, moe

        fns = (jax.jit(admit_fn,
                       donate_argnums=(6,) if self._donate else ()),
               jax.jit(admit_prefix_fn,
                       donate_argnums=(10,) if self._donate else ()),
               jax.jit(step_fn,
                       donate_argnums=(6,) if self._donate else ()))
        self._cont_cache[key] = fns
        return fns

    def _mixed_fns(self, sp: SamplingParams):
        """Build (once per sp) the chunk entry point of the unified
        iteration: one jitted dispatch per scheduled prefill chunk.
        Fresh pages of a slot running its first chunk are reset and its
        partial COW tail page copied in the same call (dump-page no-ops
        otherwise), then the chunk window is scattered into the paged
        pool and attended in a single mixed forward
        (``T.forward_mixed``), and the row's last-token logits are
        sampled on device (consumed only by a prompt's final chunk).
        Retraced once per padded window-width bucket, so the
        compiled-shape set stays small regardless of scheduling timing
        — this replaces the per-(B, bucket) power-of-two
        admission-chunk machinery on chunked families.
        """
        key = ("mixed", sp)
        cached = self._cont_cache.get(key)
        if cached is not None:
            return cached
        cfg, policy, max_len = self.cfg, self.policy, self.max_len

        def mixed_fn(params, tokens, row_start, n_q, block_tables,
                     reset_rows, cow_src, cow_dst, cow_keep, cache, rng):
            cache = KV.reset_pages_all(cache, reset_rows)
            logits, cache, moe = T.forward_mixed(
                params, cfg, tokens, cache, row_start, n_q, policy=policy,
                max_len=max_len, paged={"block_tables": block_tables,
                                        "cow": (cow_src, cow_dst, cow_keep)})
            rng, sub = jax.random.split(rng)
            nxt = sample(logits[:, 0], sub, sp)
            return nxt, cache, rng, moe

        fn = jax.jit(mixed_fn, donate_argnums=(9,) if self._donate else ())
        self._cont_cache[key] = fn
        return fn

    def _packed_fns(self, sp: SamplingParams):
        """Build (once per sp) the token-packed iteration entry point:
        a WHOLE scheduler iteration — every decoding slot's token plus
        every scheduled prefill-chunk token, flattened into one (1, T)
        ragged stream — as ONE jitted dispatch.  Page resets and COW
        tail copies for every admitting slot in the iteration are fused
        in (dump-page no-ops otherwise), the stream's K/V is scattered
        per-token into each lane's own slot pages, each query attends
        its slot's paged history under its own causal mask
        (``T.forward_packed``), and sampling runs fused on every
        segment's last token.  Retraced once per global stream-width
        bucket (:func:`packed_width_buckets`) — dispatches per mixed
        iteration drop from ``1 + #chunks`` to exactly 1, and
        padded-lane waste is the ladder-step remainder instead of
        per-chunk width padding."""
        key = ("packed", sp)
        cached = self._cont_cache.get(key)
        if cached is not None:
            return cached
        cfg, policy, max_len = self.cfg, self.policy, self.max_len

        def packed_fn(params, tokens, slot_ids, positions, meta, seg_last,
                      block_tables, reset_rows, cow_src, cow_dst, cow_keep,
                      cache, rng):
            cache = KV.reset_pages_all(cache, reset_rows)
            logits, cache, moe = T.forward_packed(
                params, cfg, tokens, cache, slot_ids, positions, seg_last,
                policy=policy, max_len=max_len,
                paged={"block_tables": block_tables, "packed_meta": meta,
                       "cow": (cow_src, cow_dst, cow_keep)})
            rng, sub = jax.random.split(rng)
            nxt = sample(logits[0], sub, sp)        # (S,)
            return nxt, cache, rng, moe

        fn = jax.jit(packed_fn,
                     donate_argnums=(11,) if self._donate else ())
        self._cont_cache[key] = fn
        return fn

    def _spec_fns(self, sp: SamplingParams, k: int):
        """Build (once per (sp, k)) the jitted draft-verify decode step:
        ONE target forward scores the pending token plus ``k`` drafted
        tokens per slot against the paged pools (multi-token KV write +
        multi-query paged attention), the rejection sampler keeps the
        longest valid prefix per slot (exact-match greedy at temperature
        0), EOS/budget clamps are applied on device, and the rejected
        tail's KV entries are rewound (``paged_truncate_all``) before
        anything downstream — retire-time prefix-cache inserts in
        particular — can observe them."""
        key = ("spec", sp, k)
        cached = self._cont_cache.get(key)
        if cached is not None:
            return cached
        cfg, policy, max_len = self.cfg, self.policy, self.max_len

        def verify_fn(params, tok, lens, rem, act, drafts, block_tables,
                      cache, rng):
            K = drafts.shape[1]
            toks_in = jnp.concatenate([tok[:, None], drafts], axis=1)
            logits, cache, moe = T.forward_verify(
                params, cfg, toks_in, cache, lens, policy=policy,
                max_len=max_len,
                paged={"block_tables": block_tables, "active": act})
            rng, sub = jax.random.split(rng)
            accept_len, nxt = speculative_verify(logits, drafts, sub, sp)
            # the step's nominal emit stream: the accepted drafts
            # verbatim, then the corrective/bonus token at index
            # accept_len — each element exactly distributed as
            # sequential sampling (greedy: each is an argmax)
            idx1 = jnp.arange(K + 1)[None, :]
            stream = jnp.concatenate(
                [drafts, jnp.zeros_like(nxt[:, None])], axis=1)
            stream = jnp.where(idx1 == accept_len[:, None], nxt[:, None],
                               stream)                          # (B, K+1)
            # budget truncation keeps a PREFIX of the stream (the last
            # budgeted token is the accepted draft itself — recomputing
            # a prediction at the clamped position would skip ahead)
            limit = jnp.minimum(accept_len + 1, jnp.maximum(rem, 0))
            # EOS anywhere in the emittable prefix ends the request
            # there; EOS itself is never emitted
            eos_hit = (stream == EOS) & (idx1 < limit[:, None])
            eos_pos = jnp.min(jnp.where(eos_hit, idx1, K + 1), axis=1)
            n_emit = jnp.where(act, jnp.minimum(limit, eos_pos), 0)
            done = eos_pos < limit
            emits = jnp.where(idx1 < n_emit[:, None], stream, -1)
            # written accepted context = pending token + the drafts that
            # were emitted (a trailing emitted `nxt` is pending, not yet
            # written — it lands at new_lens on the next step)
            d_count = jnp.minimum(accept_len, n_emit)
            new_lens = lens + jnp.where(act, d_count + 1, 0)
            new_rem = rem - n_emit.astype(rem.dtype)
            still = act & ~done & (new_rem > 0)
            tok = jnp.where(still, nxt, tok)
            # rewind rejected/stale entries: after this, every stored
            # position < new_lens holds final accepted context and
            # nothing at or beyond it is visible
            cache = KV.paged_truncate_all(cache, block_tables, new_lens)
            return (tok, new_lens, new_rem, still, cache, rng, emits,
                    jnp.where(act, d_count, 0), moe)

        fn = jax.jit(verify_fn,
                     donate_argnums=(7,) if self._donate else ())
        self._cont_cache[key] = fn
        return fn

    def serve_continuous(self, requests: List[Request],
                         sp: SamplingParams = SamplingParams(), *,
                         page_size: int = 16,
                         num_pages: Optional[int] = None,
                         slots: Optional[int] = None,
                         steps_per_sync: int = 4,
                         arrivals: Optional[List[float]] = None,
                         prefix_cache: Optional[bool] = None,
                         spec: Optional[SpecConfig] = None,
                         max_batched_tokens: Optional[int] = None,
                         chunked_prefill: Optional[bool] = None,
                         packed: Optional[bool] = None,
                         preemption: str = "off",
                         max_preemptions: int = 2,
                         host_kv_bytes: Optional[int] = None,
                         faults: Optional[FaultConfig] = None,
                         debug_audit: bool = False,
                         trace=None):
        """Serve requests with continuous batching over a paged KV cache.

        Unlike :meth:`serve` (sort -> bucket -> drain), decode slots are
        persistent: a request is admitted into a free slot the moment one
        exists (and the page pool can hold its worst-case context), and
        is retired at EOS — other slots never wait for it.  KV lives in
        ``num_pages`` refcounted shared pages; per-request pages are
        allocated at admission and released at retirement.

        chunked_prefill / max_batched_tokens: the unified token-budget
        scheduler.  Instead of dispatching each admitted prompt as one
        whole-prompt prefill (which stalls every decoding slot for the
        prompt's full forward), each iteration packs one decode token
        per live slot plus up to the remaining budget in prefill-chunk
        tokens from admitting slots (FCFS) — executed as one fused
        decode dispatch plus packed per-chunk mixed forwards, so
        iteration compute tracks the budget's real token count and
        prompts prefill in budget-bounded chunks interleaved with
        decode, bounding inter-token latency.  With speculation, the
        budget also covers the verify step (the largest iteration:
        k+1 tokens per slot); iterations that carry prefill chunks
        pause drafting and charge one decode token per slot.  ``chunked_prefill=None``
        (default) enables it for the layer families that support it
        (paged pure non-windowed attention — the prefix-sharing gate;
        chunk attention needs per-position paged history, which
        ring/recurrent/MLA state does not expose), falling back to
        bucketed whole-prompt admission elsewhere; True warns and falls
        back on unsupported families; False forces the bucketed path.
        ``max_batched_tokens`` (default 256) is clamped up to one token
        per slot (k+1 under speculation) so decode can always step.
        Decode-only iterations still fuse ``steps_per_sync`` steps into
        one dispatch.  Greedy outputs are bit-identical chunked or not;
        pool dtypes narrower than the compute dtype (int8 aside, which
        always round-trips the pool) may flip near-tie greedy picks
        because chunk queries attend the written pool bytes.

        Requests that arrive faster than slots/pages free up queue FCFS,
        exactly as before — the budget only reshapes *how* an admitted
        prompt's prefill is scheduled.

        packed: token-packed ragged execution of mixed iterations.  The
        iteration's decode tokens and prefill-chunk tokens are
        flattened into one (1, T) stream (decode lanes first, then FCFS
        chunks) and the whole iteration runs as ONE dispatch — per-token
        KV scatter, per-segment causal attention against each lane's
        own slot pages, fused sampling on every segment's last token.
        T pads to one global bucket, so dispatches per mixed iteration
        drop from ``1 + #chunks`` to 1 and padded-lane waste is the
        bucket remainder rather than per-chunk width padding.  ``None``
        (default) enables it whenever the unified chunked scheduler is
        on (same family gate); False keeps the legacy
        decode-micro-step + per-chunk dispatches (the A/B baseline);
        True warns and falls back where chunking itself is unsupported.
        Greedy outputs are bit-identical packed or bucketed.


        prefix_cache: share identical prompt-prefix pages across requests
        through a radix trie (copy-on-write; zero prefill cost for the
        matched span).  None (default) enables it whenever every layer
        family supports sharing (see ``prefix_cache.shareable``); True
        warns and falls back to unshared serving for opted-out families;
        False disables matching (the pool still evicts stale cached
        prefixes under pressure).  Results are exact either way.

        arrivals: optional per-request arrival offsets in seconds (same
        order as ``requests``) for open-loop traces; requests only become
        admissible once their arrival time has passed.  TTFT and latency
        (``ServeMetrics`` and the trace) count from that due time, so the
        loop's lag in noticing an arrival is part of them; without
        ``arrivals`` they count from submission.

        spec: a :class:`~repro.core.speculative.SpecConfig` enables
        draft–verify decoding: each decode step drafts ``spec.k`` tokens
        per slot (host-side), verifies them in ONE multi-token forward,
        and accepts the longest valid prefix — distribution preserving,
        bit-identical under greedy.  Requires the same layer families as
        prefix sharing (pure non-windowed attention; ring overwrites and
        recurrent state cannot be rolled back on rejection) — elsewhere
        it warns and serves non-speculatively.  ``steps_per_sync`` is
        ignored in speculative mode: drafting needs the emitted history
        after every verify, so each step is one host sync.

        preemption / host_kv_bytes: overload survivability.  With
        ``preemption`` "lru" (victim = most recently admitted) or
        "priority" (victim = lowest ``Request.priority``, strictly below
        the blocked head's), an admission that fails for *pages* while a
        slot is free evicts a decoding victim: its paged KV is
        snapshotted into a host-memory :class:`HostKVStore` of
        ``host_kv_bytes`` capacity (when set), its device pages are
        freed, and the request re-queues at the back with its generated
        tokens preserved.  On re-admission the snapshot is restored and
        decoding resumes bit-identically; if the host tier was full the
        context (prompt + generated tokens) is re-prefilled instead —
        same greedy stream, paid in compute.  The prefix trie spills
        evicted full-page leaves into the same tier and re-promotes them
        on a match.  Preemption requires the unified chunked scheduler
        (resume re-prefill rides the chunk machinery) and is disabled,
        loudly, on bucketed-only families.  ``max_preemptions`` bounds
        per-request churn: a request evicted that many times keeps its
        slot thereafter.

        Deadlines: ``Request.deadline`` (absolute seconds on the serve
        clock — the arrivals timeline) and ``Request.max_queue_wait``
        cancel still-queued work once expired (structured ``timed_out``
        outcome; a preempted request's generated tokens survive as a
        partial result).  Running slots are never cancelled — a request
        that finishes past its deadline completes and counts a deadline
        miss.  Every submitted request ends with a terminal
        ``Request.outcome``.

        faults / debug_audit: deterministic fault injection (see
        :class:`~repro.core.continuous.FaultConfig`) and a per-iteration
        allocator + host-tier audit for the overload test harness.

        trace: an optional :class:`~repro.core.trace.ServeTracer`.  When
        attached, the serve loop emits a structured event timeline —
        per-iteration records (budget use, decode lanes vs. chunk
        segments, packed width/padding, host/device wall split, the
        attention work of the iteration's dispatches, pool gauges),
        request lifecycle events (enqueue → admit →
        prefill_chunk → first_token → preempt/offload/restore → retire)
        and scheduler decisions (admission denials, victim choices,
        prefix/host-tier events) — and sources EVERY wall-clock reading
        from the tracer's injectable clock, so a deterministic fake
        clock yields byte-reproducible traces.  ``None`` (default) is
        zero-cost: every emit site is guarded.  Greedy outputs are
        bit-identical traced or not (tracing never touches device work).

        Returns (requests, ServeMetrics); ``r.result`` is filled like
        :meth:`serve`.
        """
        if arrivals is not None and len(arrivals) != len(requests):
            raise ValueError("arrivals must match requests 1:1")
        ctx = self._paged_context(page_size, num_pages, slots)
        slots, num_pages = ctx["slots"], ctx["num_pages"]
        pages_per_slot, dump = ctx["pages_per_slot"], ctx["dump"]
        trie = ctx["trie"]
        share_reason = PC.shareable(self.cfg, self.max_len)
        share = share_reason is None if prefix_cache is None \
            else bool(prefix_cache)
        if share and share_reason is not None:
            warnings.warn(f"prefix_cache requested but disabled — "
                          f"{share_reason}")
            share = False
        spec_on = spec is not None
        if spec_on:
            spec_reason = PC.shareable(self.cfg, self.max_len)
            if spec_reason is not None:
                warnings.warn("speculative decoding requested but "
                              f"disabled — {spec_reason}")
                spec_on = False
        drafter = verify_fn = None
        if spec_on:
            # one-entry cache keyed on the SpecConfig object itself (held
            # strongly, so `is` can never alias a recycled address): the
            # draft-model drafter carries jit caches worth keeping across
            # serve calls with the same spec
            cached = self._cont_cache.get("drafter")
            if cached is not None and cached[0] is spec:
                drafter = cached[1]
            else:
                drafter = get_drafter(spec, self.cfg, self.params,
                                      policy=self.policy)
                self._cont_cache["drafter"] = (spec, drafter)
            verify_fn = self._spec_fns(sp, drafter.k)
        # -- unified token-budget scheduler (chunked prefill) --------------
        # same family gate as prefix sharing: chunk queries attend the
        # already-written paged history, which ring/recurrent/MLA state
        # cannot expose; opted-out families keep bucketed admission.
        chunked = share_reason is None if chunked_prefill is None \
            else bool(chunked_prefill)
        if chunked and share_reason is not None:
            warnings.warn(f"chunked prefill requested but disabled — "
                          f"{share_reason}")
            chunked = False
        budget = max_batched_tokens or DEFAULT_MAX_BATCHED_TOKENS
        floor = slots * ((drafter.k + 1) if spec_on else 1)
        if chunked and budget < floor:
            warnings.warn(
                f"max_batched_tokens={budget} cannot cover one "
                f"{'verify window' if spec_on else 'decode token'} per "
                f"slot; raising to {floor}")
            budget = floor
        # -- overload survivability: preemption + host KV tier -------------
        if preemption not in ("off", "lru", "priority"):
            raise ValueError(f"unknown preemption policy {preemption!r}")
        if preemption != "off" and not chunked:
            warnings.warn("preemption requested but disabled — it needs "
                          "the unified chunked scheduler (resume "
                          "re-prefill rides the chunk machinery)")
            preemption = "off"
        host = None
        if host_kv_bytes is not None or (faults is not None
                                         and faults.host_full):
            hb = 0 if (faults is not None and faults.host_full) \
                else host_kv_bytes
            host = ctx.get("host")
            if host is None or host.max_bytes != hb:
                # spilled prefixes from a previous budget are dropped
                # with the old store; preempt blobs never outlive a call
                host = HostKVStore(hb)
                ctx["host"] = host
        # -- token-packed ragged execution ---------------------------------
        packed_on = chunked if packed is None else bool(packed)
        if packed_on and not chunked:
            warnings.warn("packed execution requested but disabled — it "
                          "rides the unified chunked scheduler"
                          + (f" ({share_reason})"
                             if share_reason is not None else ""))
            packed_on = False
        mixed_fn = self._mixed_fns(sp) if (chunked and not packed_on) \
            else None
        packed_fn = self._packed_fns(sp) if packed_on else None
        # the decode share of a mixed iteration is a single fused step
        step_fn1 = self._continuous_fns(sp, 1)[2] \
            if (chunked and not packed_on) else None
        # mixed forwards are traced per padded window width; bucket the
        # width so the compiled-shape set stays small and deterministic
        width_buckets = mixed_width_buckets(budget)
        packed_buckets = packed_width_buckets(budget)
        admit_fn, admit_prefix_fn, step_fn = \
            self._continuous_fns(sp, steps_per_sync)
        buckets = self.prompt_buckets()
        # Recurrent mixers fold PAD steps into their state, so they are
        # sensitive to prompt padding (the dense bucket path shares the
        # limitation for ragged batches).  Admit those architectures at
        # exact prompt length instead — one retrace per distinct length,
        # but exact results.  (Expert layers route padding to no expert.)
        pad_sensitive = any(
            spec.mixer in (MLSTM, SLSTM, HYBRID)
            for stack in self.cfg.stacks for spec in stack.pattern)

        cache = ctx["cache"]
        sched = ContinuousScheduler(slots, ctx["alloc"], page_size,
                                    max_pages_per_slot=pages_per_slot,
                                    prefix_cache=trie, match_prefix=share,
                                    preemption=preemption,
                                    max_preemptions=max_preemptions,
                                    trace=trace)

        # device closures for the host-side scheduler/trie: both always
        # see the *latest* cache pytree (restore rebinds it)
        def offload_fn(pages):
            return KV.offload_pages(cache, pages)

        def restore_fn(blob, pages):
            nonlocal cache
            cache = KV.restore_pages(cache, blob, pages)

        sched.host_store = host
        sched.offload_fn = offload_fn
        sched.restore_fn = restore_fn
        trie.host_store = host
        trie.offload_fn = offload_fn if host is not None else None
        # like offload_fn, the tracer must not outlive this call on the
        # persistent trie/host objects (reset alongside it below)
        trie.trace = trace
        if host is not None:
            host.trace = trace
        spill_base = trie.spilled_pages
        promote_base = sched.promoted_pages
        ws = self.weight_stats
        metrics = ServeMetrics(kv_dtype=ctx["kv_dtype"],
                               kv_pool_bytes=ctx["kv_pool_bytes"],
                               kv_bytes_per_token=ctx["kv_bytes_per_token"],
                               weight_dtype=ws["weights_dtype"],
                               weight_bytes=int(ws["weight_bytes"]),
                               weight_bytes_saved=int(
                                   ws["weight_bytes_saved"]),
                               spec_mode=drafter.name if spec_on else "off",
                               spec_k=drafter.k if spec_on else 0,
                               scheduler="unified" if chunked
                               else "bucketed",
                               max_batched_tokens=budget if chunked else 0)
        stats = EngineStats(batches=1)
        trie_base = trie.evicted_pages

        block_tables = np.full((slots, pages_per_slot), -1, np.int32)
        tok = np.zeros((slots,), np.int32)
        lens = np.zeros((slots,), np.int32)
        rem = np.zeros((slots,), np.int32)
        act = np.zeros((slots,), bool)
        rng = self.rng

        if faults is not None and faults.collapse_arrivals:
            arrivals = None            # adversarial burst: all at t=0
        order = sorted(range(len(requests)),
                       key=lambda i: arrivals[i]) if arrivals else \
            list(range(len(requests)))
        incoming = [(arrivals[i] if arrivals else 0.0, requests[i])
                    for i in order]
        fault_hold: List[int] = []     # pool pages a fault is squatting on
        # with a tracer attached, EVERY wall reading this loop takes comes
        # from its injectable clock — a deterministic fake clock therefore
        # reproduces the exact event stream, timestamps included
        tr = trace
        clk = tr.clock if tr is not None else time.perf_counter
        t0 = clk()
        if tr is not None:
            tr.set_origin(t0)
            # weight-compression state gauge at serve start, with the
            # construction-time quantization wall (only when weights
            # actually compressed: that duration would otherwise break
            # fake-clock byte-determinism for uncompressed runs)
            tr.emit("weights", 0.0, dtype=ws["weights_dtype"],
                    weight_bytes=int(ws["weight_bytes"]),
                    weight_bytes_dense=int(ws["weight_bytes_dense"]),
                    quantized_tensors=int(ws["n_quantized"]),
                    **({"quantize_s": float(self.weight_quant_s)}
                       if ws["n_quantized"] else {}))

        def now():
            return clk() - t0

        # per-iteration accounting for the trace timeline: device time
        # and attention work accumulate across this iteration's
        # dispatches, then one iteration record carries the host/device
        # split, the attention counters and the pool gauges
        it_acc = {"t0": 0.0, "iter": 0, "device_s": 0.0, "attn_kv": 0,
                  "attn_pairs": 0, "attn_pages": 0, "attn_page_steps": 0,
                  "moe": np.zeros(3, np.int64)}

        @contextmanager
        def dev_span(name, phase):
            """Time one blocking device dispatch — books the wall into
            ``stats.prefill_s``/``decode_s`` exactly like the inline
            timers it replaces, plus (when tracing) a named device-track
            span and the iteration's device share."""
            ts = clk()
            try:
                yield
            finally:
                dt = clk() - ts
                if phase == "prefill":
                    stats.prefill_s += dt
                else:
                    stats.decode_s += dt
                it_acc["device_s"] += dt
                if tr is not None:
                    tr.emit("span", t=ts - t0, name=name, dur=dt,
                            track="device")

        def count_attention(starts, lengths):
            """Add one attention dispatch's work (``attention_work`` of
            its segments) to the iteration record; callers guard on
            ``tr`` so the untraced loop never computes it."""
            kv, pairs = attention_work(starts, lengths)
            it_acc["attn_kv"] += kv
            it_acc["attn_pairs"] += pairs

        def count_pages(max_qpos, block_pages=None):
            """Add one paged-kernel dispatch's page steps (``page_work``
            of its work items' largest query positions)."""
            live, steps = page_work(max_qpos, page_size, pages_per_slot,
                                    block_pages)
            it_acc["attn_pages"] += live
            it_acc["attn_page_steps"] += steps

        def row_qpos(qpos):
            """A block-table row's query position as the paged kernels
            see it: none (-1) for a row with no first page."""
            return np.where(block_tables[:, 0] >= 0, qpos, -1)

        def count_decode_attention(lens0, acts):
            """A fused decode scan's work: lane s active at step k
            (``acts[s, k]``) is one query at position ``lens0[s] + k``.
            Every slot is a kernel work item at every step, at the
            position its length has reached by then."""
            pos = lens0[:, None] + np.arange(acts.shape[1])[None, :]
            count_attention(pos[acts], 1)
            lens_k = lens0[:, None] + np.cumsum(acts, axis=1) - acts
            count_pages(row_qpos(lens_k.T).ravel())

        def count_moe(moe):
            """Add one dispatch's routing counters (the step program's
            [assignments, experts hit, most rows of one expert]); called
            with a tracer attached, after the dispatch's sync."""
            moe = np.asarray(moe)
            acc = it_acc["moe"]
            acc[:2] += moe[:2]
            acc[2] = max(acc[2], moe[2])

        def emit_iteration(**kw):
            dev = it_acc["device_s"]
            it_acc["device_s"] = 0.0
            it_acc["iter"] += 1
            if tr is None:
                return
            kv, pairs = it_acc["attn_kv"], it_acc["attn_pairs"]
            pages, page_steps = it_acc["attn_pages"], it_acc["attn_page_steps"]
            it_acc["attn_kv"] = it_acc["attn_pairs"] = 0
            it_acc["attn_pages"] = it_acc["attn_page_steps"] = 0
            moe = [int(v) for v in it_acc["moe"]]
            it_acc["moe"][:] = 0
            t_it = it_acc["t0"]
            dur = max(0.0, now() - t_it)
            tr.emit("iteration", t=t_it, iter=it_acc["iter"] - 1, dur=dur,
                    host_s=max(0.0, dur - dev), device_s=dev,
                    budget=budget if chunked else 0,
                    pages_in_use=int(sched.allocator.allocated_count),
                    host_bytes=int(host.used_bytes) if host is not None
                    else 0,
                    trie_nodes=int(trie.num_nodes), attn_kv_tokens=kv,
                    attn_pairs=pairs, attn_pages=pages,
                    attn_page_steps=page_steps, moe_rows=moe[0],
                    moe_experts_hit=moe[1], moe_rows_max=moe[2], **kw)

        def count_outcome(req):
            """Fold a request's terminal outcome into the run metrics —
            called exactly once per request, at its terminal point."""
            oc = req.outcome
            metrics.outcome_counts[oc.status] = \
                metrics.outcome_counts.get(oc.status, 0) + 1
            if oc.deadline_missed:
                metrics.deadline_misses += 1
            if oc.status == "timed_out":
                metrics.timed_out += 1
            elif oc.status == "rejected":
                metrics.rejected += 1

        def retire(slot):
            st = sched.retire(slot, now())
            block_tables[slot, :] = -1
            act[slot] = False
            metrics.retired += 1
            metrics.generated_tokens += len(st.request.result)
            count_outcome(st.request)
            # queue wait counts: latency is due time -> completion
            metrics.latency_s.append(st.finished_at - st.due_at)
            if tr is not None:
                oc = st.request.outcome
                tr.emit("retire", t=st.finished_at, uid=st.request.uid,
                        slot=int(slot), status=oc.status,
                        preemptions=int(oc.preemptions),
                        deadline_missed=bool(oc.deadline_missed),
                        latency_s=st.finished_at - st.due_at,
                        generated=len(st.request.result))

        def record_emit(st, n, t):
            """TTFT / ITL bookkeeping: ``n`` tokens appended to ``st`` at
            wall time ``t``.  A multi-token sync (fused decode scan,
            accepted speculation window) spreads its wall time evenly
            over the tokens it emitted — per-token arrival inside one
            dispatch is unobservable."""
            if n <= 0:
                return
            if st.last_token_at is None:
                # a slot's first emission is always the single admission /
                # final-chunk sample: it defines TTFT and no ITL gap
                assert n == 1, "first emission must be a single token"
                metrics.ttft_s.append(t - st.due_at)
                if tr is not None:
                    tr.emit("first_token", t=t, uid=st.request.uid,
                            ttft_s=t - st.due_at)
            else:
                metrics.itl_s.extend([(t - st.last_token_at) / n] * n)
            st.last_token_at = t

        def apply_decode_results(tok_d, lens_d, rem_d, act_d, emits):
            """Fold a decode/verify dispatch's device results back into
            the host slot arrays: append emits, record TTFT/ITL, retire
            finished slots."""
            nonlocal tok, lens, rem, act
            tok, lens, rem = (np.array(tok_d), np.array(lens_d),
                              np.array(rem_d))
            act_new = np.array(act_d)
            metrics.decode_tokens += int((emits >= 0).sum())
            t_now = now()
            for slot in list(sched.slots):
                st = sched.slots[slot]
                if not st.prefill_done:
                    continue        # admitting slot rode along inactive
                n_emit = 0
                for t in emits[slot]:
                    if t >= 0:
                        st.emitted.append(int(t))
                        n_emit += 1
                record_emit(st, n_emit, t_now)
                if not act_new[slot]:
                    retire(slot)
            act = act_new

        def decode_micro_step():
            """One 1-token decode dispatch over every live slot — the
            decode share of a mixed iteration (each decoding slot's
            budget cost is exactly one token, so admitting prompts can
            never starve decode).  Dispatch only: no host sync here —
            the caller folds the results in after the iteration's
            single coalesced fetch."""
            nonlocal cache, rng
            with dev_span("decode_micro", "decode"):
                (tok_d, lens_d, rem_d, act_d, cache, rng, emits,
                 acts, moe) = step_fn1(self.params, jnp.asarray(tok),
                                       jnp.asarray(lens), jnp.asarray(rem),
                                       jnp.asarray(act),
                                       jnp.asarray(block_tables), cache,
                                       rng)
            metrics.steps += 1
            metrics.slot_steps_total += slots
            return tok_d, lens_d, rem_d, act_d, emits, acts, moe

        def run_mixed(plan):
            """One mixed iteration, ONE host sync.  The decode
            micro-step and every prefill-chunk forward are dispatched
            back-to-back asynchronously; a single ``jax.device_get``
            then drains the iteration's scalar results (decode emits +
            final-chunk samples) before any bookkeeping runs.  The
            per-dispatch ``block_until_ready`` calls this replaces were
            the mixed path's dominant host-time term — the device sat
            idle between dispatches while the host did bookkeeping.

            Each chunk runs as one packed single-row mixed forward (page
            reset + COW copy fused into the slot's first chunk), so an
            iteration's prefill compute tracks the budget's *real*
            token count — decode rows never pad chunk-wide, chunk rows
            never pad slot-deep.  Chunk dispatches are (1, W-bucket)
            shaped: a small deterministic trace set regardless of how
            arrival timing slices the prompts.

            Bookkeeping replays the pre-coalescing order exactly —
            decode results first, then chunks in plan order — so greedy
            token streams, trie insertions, and allocator state stay
            bit-identical to the one-sync-per-dispatch loop.  Returns
            the total padded lanes across this plan's chunk dispatches
            (the iteration record's ``padded_lanes``)."""
            nonlocal cache, rng
            dec = decode_micro_step() if plan.decode_slots else None
            padded = 0
            finals = {}        # chunk index -> final-chunk logits handle
            moes = []          # each dispatch's routing counters
            inited = set()     # slots whose page init this plan consumed
            for ci, c in enumerate(plan.chunks):
                st = sched.slots[c.slot]
                W = pick_bucket(c.length, width_buckets)
                toks = np.zeros((1, W), np.int32)
                # st.ctx == the prompt, except on a recompute-resume
                # where it also replays the pre-preemption output
                toks[0, :c.length] = st.ctx[c.start:c.start + c.length]
                reset_row = np.full((1, pages_per_slot), dump, np.int32)
                cow_src = np.full((1,), dump, np.int32)
                cow_dst = np.full((1,), dump, np.int32)
                cow_keep = np.zeros((1,), np.int32)
                if st.needs_init and c.slot not in inited:
                    # page init rides the slot's FIRST chunk only —
                    # needs_init itself clears in the bookkeeping phase
                    inited.add(c.slot)
                    reset_row[0, :len(st.fresh_pages)] = st.fresh_pages
                    if st.cow_src >= 0:
                        # COW invariant: the destination must be private
                        if sched.allocator.refcount(st.fresh_pages[0]) != 1:
                            raise AssertionError(
                                "COW write target is a shared page")
                        cow_src[0] = st.cow_src
                        cow_dst[0] = st.fresh_pages[0]
                        cow_keep[0] = st.matched_len
                        metrics.cow_copies += 1
                with dev_span("chunk", "prefill"):
                    nxt, cache, rng, moe = mixed_fn(
                        self.params, jnp.asarray(toks),
                        jnp.asarray([c.start], jnp.int32),
                        jnp.asarray([c.length], jnp.int32),
                        jnp.asarray(block_tables[c.slot:c.slot + 1]),
                        jnp.asarray(reset_row), jnp.asarray(cow_src),
                        jnp.asarray(cow_dst), jnp.asarray(cow_keep), cache,
                        rng)
                if tr is not None:
                    tr.emit_now("prefill_chunk", uid=st.request.uid,
                                slot=int(c.slot), start=int(c.start),
                                len=int(c.length))
                moes.append(moe)
                padded += W - c.length
                metrics.prefill_chunks += 1
                metrics.prefill_tokens += c.length
                metrics.prefill_padded += W
                # only a prompt's FINAL chunk consumes its sampled token
                if c.start + c.length >= st.ctx_len and not st.is_resume:
                    finals[ci] = nxt
            # the iteration's single device->host transfer: every
            # dispatch above is in flight; blocking time books into
            # prefill_s (async dispatches' device time lands on whoever
            # blocks — here, always this span)
            with dev_span("mixed_sync", "prefill"):
                sync = jax.device_get(
                    {"emits": None if dec is None else dec[4],
                     "acts": None if dec is None else dec[5],
                     "finals": finals,
                     "moe": None if tr is None else
                     moes + ([] if dec is None else [dec[6]])})
            metrics.host_syncs += 1
            if tr is not None:
                for moe in sync["moe"]:
                    count_moe(moe)
                if dec is not None:
                    count_decode_attention(lens, np.asarray(sync["acts"]))
                count_attention([c.start for c in plan.chunks],
                                [c.length for c in plan.chunks])
                # each chunk: one row, through its last query
                count_pages([c.start + c.length - 1 for c in plan.chunks])
            if dec is not None:
                tok_d, lens_d, rem_d, act_d = dec[:4]
                metrics.slot_steps_active += int(sync["acts"].sum())
                apply_decode_results(tok_d, lens_d, rem_d, act_d,
                                     np.asarray(sync["emits"]))
            for ci, c in enumerate(plan.chunks):
                st = sched.slots[c.slot]
                req = st.request
                if st.needs_init:
                    st.needs_init = False
                    sched.release_cow_source(st)
                st.prefill_pos = c.start + c.length
                if not st.prefill_done:
                    continue
                # final chunk: its last-token logits seeded sampling
                plen = st.ctx_len
                # newly produced page-aligned context KV joins the trie
                # now (the partial tail joins at retire, once decode can
                # no longer write into it)
                sched.insert_prefix(st, (plen // page_size) * page_size)
                if st.is_resume:
                    # recompute-resume: the next token was already
                    # sampled before the preemption — continue from it
                    # verbatim (greedy bit-identity) instead of the
                    # replayed final chunk's fresh sample
                    tok[c.slot] = st.resume_pending
                    lens[c.slot] = plen
                    rem[c.slot] = st.resume_rem
                    act[c.slot] = True
                    st.last_token_at = now()
                    continue
                first = int(np.asarray(sync["finals"][ci])[0])
                gen_budget = min(req.max_new_tokens, self.max_len - plen)
                if first != EOS and gen_budget > 0:
                    st.emitted.append(first)
                    record_emit(st, 1, now())
                if first == EOS or gen_budget <= 1:
                    retire(c.slot)
                else:
                    tok[c.slot] = first
                    lens[c.slot] = plen
                    rem[c.slot] = gen_budget - 1
                    act[c.slot] = True
            return padded

        def run_packed(plan):
            """One token-packed ragged iteration: the WHOLE plan — every
            decoding slot's token plus every scheduled prefill chunk —
            as ONE (1, T) dispatch.  T buckets on the plan's real token
            count (same width set the mixed forwards use), so the
            compiled-shape set stays small while padded lanes are the
            bucket remainder, not per-chunk width padding.  Greedy
            bookkeeping after the dispatch replicates the bucketed
            path's exactly (decode-step semantics for decode segments,
            final-chunk sampling/resume semantics for chunk segments),
            so outputs stay bit-identical between the two executions."""
            nonlocal cache, rng
            from repro.kernels import decode_attention as DA
            W = pick_bucket(plan.total_tokens, packed_buckets)
            pb = sched.pack_batch(plan, tok, lens, W)
            if tr is not None:
                seg = slice(0, pb.n_segments)
                count_attention(pb.positions[pb.seg_start[seg]],
                                pb.seg_len[seg])
            reset_rows = np.full((slots, pages_per_slot), dump, np.int32)
            cow_src = np.full((slots,), dump, np.int32)
            cow_dst = np.full((slots,), dump, np.int32)
            cow_keep = np.zeros((slots,), np.int32)
            for c in plan.chunks:
                st = sched.slots[c.slot]
                if not st.needs_init:
                    continue
                reset_rows[c.slot, :len(st.fresh_pages)] = st.fresh_pages
                if st.cow_src >= 0:
                    # COW invariant: the destination must be private
                    if sched.allocator.refcount(st.fresh_pages[0]) != 1:
                        raise AssertionError(
                            "COW write target is a shared page")
                    cow_src[c.slot] = st.cow_src
                    cow_dst[c.slot] = st.fresh_pages[0]
                    cow_keep[c.slot] = st.matched_len
                    metrics.cow_copies += 1
            # static per-W work-table height: every segment adds at most
            # one partial query block, so ceil-sum <= T/BQ + #segments
            n_work = W // DA.PACKED_BLOCK_Q + slots
            meta = DA.packed_meta_table(pb.seg_start[:pb.n_segments],
                                        pb.seg_len[:pb.n_segments],
                                        pb.seg_slots[:pb.n_segments],
                                        W, n_work)
            if tr is not None:
                # each work row (query window) through its last lane, in
                # the kernel's blocks; unused rows (slot -1) have none
                count_pages(np.where(
                    meta[:, 0] >= 0,
                    pb.positions[np.maximum(meta[:, 3] - 1, 0)], -1),
                    _packed_block_pages(cache, pages_per_slot))
            # one dispatch carries both shares; device_s sums both pools
            with dev_span("packed", "prefill"):
                nxt, cache, rng, moe = packed_fn(
                    self.params, jnp.asarray(pb.tokens[None, :]),
                    jnp.asarray(pb.slot_ids), jnp.asarray(pb.positions),
                    jnp.asarray(meta), jnp.asarray(pb.last_idx),
                    jnp.asarray(block_tables), jnp.asarray(reset_rows),
                    jnp.asarray(cow_src), jnp.asarray(cow_dst),
                    jnp.asarray(cow_keep), cache, rng)
                if tr is None:
                    nxt = np.asarray(jax.block_until_ready(nxt))
                else:
                    nxt, moe = jax.device_get((nxt, moe))
                    count_moe(moe)
            metrics.host_syncs += 1
            metrics.steps += 1
            metrics.slot_steps_total += slots
            metrics.slot_steps_active += len(plan.decode_slots)
            metrics.mixed_iters += 1
            metrics.mixed_dispatches += 1
            metrics.packed_tokens_real += pb.n_tokens
            metrics.packed_tokens_padded += W
            real = sum(c.length for c in plan.chunks)
            metrics.prefill_chunks += len(plan.chunks)
            metrics.prefill_tokens += real
            metrics.prefill_padded += real   # pad is per-stream, not
            t_emit = now()                   # per-chunk — see packed_*
            for i in range(pb.n_decode):
                s = int(pb.seg_slots[i])
                st = sched.slots[s]
                v = int(nxt[i])
                lens[s] += 1
                rem[s] -= 1
                if v != EOS:
                    st.emitted.append(v)
                    record_emit(st, 1, t_emit)
                    metrics.decode_tokens += 1
                if v == EOS or rem[s] <= 0:
                    retire(s)
                else:
                    tok[s] = v
            for i in range(pb.n_decode, pb.n_segments):
                c = plan.chunks[i - pb.n_decode]
                st = sched.slots[c.slot]
                req = st.request
                if tr is not None:
                    tr.emit("prefill_chunk", t=t_emit, uid=req.uid,
                            slot=int(c.slot), start=int(c.start),
                            len=int(c.length))
                if st.needs_init:
                    st.needs_init = False
                    sched.release_cow_source(st)
                st.prefill_pos = c.start + c.length
                if not st.prefill_done:
                    continue
                # final chunk: its segment's logits seeded sampling
                plen = st.ctx_len
                sched.insert_prefix(st, (plen // page_size) * page_size)
                if st.is_resume:
                    tok[c.slot] = st.resume_pending
                    lens[c.slot] = plen
                    rem[c.slot] = st.resume_rem
                    act[c.slot] = True
                    st.last_token_at = now()
                    continue
                first = int(nxt[i])
                gen_budget = min(req.max_new_tokens, self.max_len - plen)
                if first != EOS and gen_budget > 0:
                    st.emitted.append(first)
                    record_emit(st, 1, now())
                if first == EOS or gen_budget <= 1:
                    retire(c.slot)
                else:
                    tok[c.slot] = first
                    lens[c.slot] = plen
                    rem[c.slot] = gen_budget - 1
                    act[c.slot] = True
            emit_iteration(budget_used=int(pb.n_tokens),
                           decode_lanes=len(plan.decode_slots),
                           chunk_segments=len(plan.chunks),
                           chunk_tokens=int(real), width_bucket=int(W),
                           padded_lanes=int(W - pb.n_tokens), idle=False)

        while incoming or sched.has_work():
            if tr is not None:
                it_acc["t0"] = now()
            # -- release arrived requests into the FCFS queue -------------
            while incoming and incoming[0][0] <= now():
                due, req = incoming.pop(0)
                if self.prune_maps is not None:
                    req.tokens = [int(t) for t in PR.remap_tokens(
                        np.asarray([req.tokens], np.int32),
                        self.prune_maps)[0]]
                if faults is not None and req.uid in faults.oversize_uids:
                    # inflate past the whole pool: the truncate-or-reject
                    # machinery below must absorb it, never raise
                    target = max(self.max_len,
                                 num_pages * page_size) + page_size
                    req.tokens = (req.tokens
                                  * (target // max(len(req.tokens), 1)
                                     + 1))[:target]
                if req.prompt_len > self.max_len:
                    # must cut: leave the truncated prompt room to
                    # actually generate (reserve its token budget, but
                    # keep at least half the context for the prompt)
                    limit = max(self.max_len - req.max_new_tokens,
                                self.max_len // 2)
                    req.tokens = truncate_prompt(req.tokens, limit,
                                                 uid=req.uid)
                    req.truncated = True
                sched.submit(req, now(), due=due if arrivals else None)

            # -- backpressure: cancel expired / unservable queued work ----
            for req in sched.cancel_expired(now()):
                count_outcome(req)

            # -- fault injection: pool-exhaustion squatter ----------------
            if faults is not None and faults.hold_pages and not fault_hold \
                    and metrics.admitted >= faults.hold_after_admits:
                fault_hold = sched.allocator.alloc(
                    min(faults.hold_pages,
                        sched.allocator.free_count)) or []

            # -- admit into free slots ------------------------------------
            if chunked:
                # unified scheduler: admission only CLAIMS a slot and its
                # pages; the prompt is prefilled in budgeted chunks by
                # the mixed iterations below, interleaved with decode
                while True:
                    adm = sched.try_admit(now())
                    if adm is not None:
                        slot, st = adm
                        block_tables[slot, :] = -1
                        block_tables[slot, :len(st.pages)] = st.pages
                        if st.restore_blob is not None:
                            # host-tier resume: scatter the snapshot back
                            # and rejoin decode exactly where it stopped
                            cache = KV.restore_pages(cache,
                                                     st.restore_blob,
                                                     st.pages)
                            st.restore_blob = None
                            metrics.restored_pages += len(st.pages)
                            metrics.resumed += 1
                            tok[slot] = st.resume_pending
                            lens[slot] = st.ctx_len
                            rem[slot] = st.resume_rem
                            act[slot] = True
                            st.last_token_at = now()
                            if tr is not None:
                                tr.emit_now("restore", uid=st.request.uid,
                                            slot=int(slot), mode="hostkv",
                                            n_pages=len(st.pages))
                        elif st.is_resume:
                            # host tier was full: re-prefill the context
                            # as ordinary chunks (recompute-resume)
                            metrics.resumed += 1
                            if tr is not None:
                                tr.emit_now("restore", uid=st.request.uid,
                                            slot=int(slot),
                                            mode="recompute",
                                            n_pages=len(st.pages))
                        else:
                            stats.prompt_tokens += st.request.prompt_len
                            metrics.admitted += 1
                            metrics.prefix_hits += st.matched_len > 0
                            metrics.prefix_matched_tokens += st.matched_len
                            metrics.pages_shared += st.shared_count
                            if tr is not None and st.matched_len > 0:
                                tr.emit_now(
                                    "prefix_hit", uid=st.request.uid,
                                    matched_tokens=int(st.matched_len),
                                    pages_shared=int(st.shared_count))
                        continue
                    # admission failed: preempt a decoding victim for the
                    # blocked head — only when a slot is FREE (pure pool
                    # pressure) and the head could actually fit after
                    # evicting every eligible victim (else preemption is
                    # churn that can never admit it)
                    if preemption == "off" or not sched.waiting \
                            or not sched.free_slots():
                        break
                    head = sched.waiting[0]
                    if sched.queued_pages_needed(head) \
                            > sched.preemptible_headroom(head):
                        break
                    victim = sched.pick_victim(head)
                    if victim is None:
                        break
                    n_pages = len(sched.slots[victim].pages)
                    vic_uid = sched.slots[victim].request.uid
                    _, offloaded = sched.preempt(
                        victim, pending=int(tok[victim]),
                        ctx_len=int(lens[victim]),
                        rem_tokens=int(rem[victim]))
                    act[victim] = False
                    block_tables[victim, :] = -1
                    metrics.preemptions += 1
                    if offloaded:
                        metrics.offloaded_pages += n_pages
                    if tr is not None:
                        tr.emit_now("preempt", uid=int(vic_uid),
                                    slot=int(victim), policy=preemption,
                                    n_pages=int(n_pages),
                                    offloaded=bool(offloaded))
                        if offloaded:
                            tr.emit_now("offload", uid=int(vic_uid),
                                        slot=int(victim),
                                        n_pages=int(n_pages))
                metrics.peak_pages_in_use = max(
                    metrics.peak_pages_in_use,
                    sched.allocator.allocated_count)
            # bucketed fallback: consecutive FCFS admissions sharing a
            # prompt bucket run as ONE batched whole-prompt prefill
            # dispatch (per-request prefills would serialize 1-row model
            # calls against the decode loop)
            pending_adm: List[tuple] = []      # [(slot, SlotState, bucket)]

            def flush_admissions():
                # power-of-two admission chunks: group size would otherwise
                # depend on scheduling timing, making the set of traced
                # (B, bucket) prefill shapes unbounded/nondeterministic
                while pending_adm:
                    B = 1 << (len(pending_adm).bit_length() - 1)
                    _flush_chunk([pending_adm.pop(0) for _ in range(B)])

            def _flush_chunk(chunk):
                nonlocal cache, rng
                bucket = chunk[0][2]
                B = len(chunk)
                toks = np.zeros((B, bucket), np.int32)
                plens = np.zeros((B,), np.int32)     # computed suffix lens
                starts = np.zeros((B,), np.int32)    # = matched prefix lens
                slots_arr = np.zeros((B,), np.int32)
                rows = np.zeros((B, pages_per_slot), np.int32)
                pages_arr = np.full((B, pages_per_slot), dump, np.int32)
                cow_src = np.full((B,), dump, np.int32)
                cow_dst = np.full((B,), dump, np.int32)
                cow_keep = np.zeros((B,), np.int32)
                for i, (slot, st, _) in enumerate(chunk):
                    req = st.request
                    m = st.matched_len
                    plens[i] = req.prompt_len - m
                    starts[i] = m
                    toks[i, :req.prompt_len - m] = req.tokens[m:]
                    slots_arr[i] = slot
                    block_tables[slot, :] = -1
                    block_tables[slot, :len(st.pages)] = st.pages
                    rows[i] = block_tables[slot]
                    # only the request's OWN pages are reset: shared prefix
                    # pages are live for other readers and the trie
                    pages_arr[i, :len(st.fresh_pages)] = st.fresh_pages
                    if st.cow_src >= 0:
                        # COW invariant: the destination must be private
                        if sched.allocator.refcount(st.fresh_pages[0]) != 1:
                            raise AssertionError(
                                "COW write target is a shared page")
                        cow_src[i] = st.cow_src
                        cow_dst[i] = st.fresh_pages[0]
                        cow_keep[i] = m
                        metrics.cow_copies += 1
                with dev_span("admit_prefill", "prefill"):
                    if share:
                        first, cache, rng, moe = admit_prefix_fn(
                            self.params, jnp.asarray(toks),
                            jnp.asarray(plens),
                            jnp.asarray(starts), jnp.asarray(slots_arr),
                            jnp.asarray(rows), jnp.asarray(pages_arr),
                            jnp.asarray(cow_src), jnp.asarray(cow_dst),
                            jnp.asarray(cow_keep), cache, rng)
                    else:
                        first, cache, rng, moe = admit_fn(
                            self.params, jnp.asarray(toks),
                            jnp.asarray(plens),
                            jnp.asarray(slots_arr), jnp.asarray(rows),
                            jnp.asarray(pages_arr), cache, rng)
                    first = np.asarray(jax.block_until_ready(first))
                metrics.host_syncs += 1
                if tr is not None:
                    count_moe(moe)
                    count_attention(starts, plens)
                t_adm = now()
                for i, (slot, st, _) in enumerate(chunk):
                    req = st.request
                    plen = req.prompt_len
                    sched.release_cow_source(st)
                    st.needs_init = False
                    st.prefill_pos = plen        # whole prompt in one go
                    stats.prompt_tokens += plen
                    metrics.admitted += 1
                    metrics.prefill_tokens += plen - st.matched_len
                    metrics.prefill_padded += bucket
                    metrics.prefix_hits += st.matched_len > 0
                    metrics.prefix_matched_tokens += st.matched_len
                    metrics.pages_shared += st.shared_count
                    if tr is not None:
                        tr.emit("prefill_chunk", t=t_adm, uid=req.uid,
                                slot=int(slot), start=int(st.matched_len),
                                len=int(plen - st.matched_len))
                        if st.matched_len > 0:
                            tr.emit("prefix_hit", t=t_adm, uid=req.uid,
                                    matched_tokens=int(st.matched_len),
                                    pages_shared=int(st.shared_count))
                    # newly produced page-aligned prompt KV joins the trie
                    # now (the partial tail joins at retire, once decode
                    # can no longer write into it)
                    sched.insert_prefix(st, (plen // page_size) * page_size)
                    budget = min(req.max_new_tokens, self.max_len - plen)
                    if first[i] != EOS and budget > 0:
                        st.emitted.append(int(first[i]))
                        record_emit(st, 1, t_adm)
                    if first[i] == EOS or budget <= 1:
                        retire(slot)
                    else:
                        tok[slot] = first[i]
                        lens[slot] = plen
                        rem[slot] = budget - 1
                        act[slot] = True

            while not chunked:         # flush may retire (budget 0/1, EOS
                progress = False       # at admit) and free slots: retry
                while True:
                    adm = sched.try_admit(now())
                    if adm is None:
                        break
                    progress = True
                    slot, st = adm
                    # only the unmatched suffix is computed; bucket on it
                    suffix = st.request.prompt_len - st.matched_len
                    bucket = suffix if pad_sensitive \
                        else pick_bucket(suffix, buckets)
                    if pending_adm and pending_adm[0][2] != bucket:
                        flush_admissions()
                    pending_adm.append((slot, st, bucket))
                flush_admissions()
                metrics.peak_pages_in_use = max(
                    metrics.peak_pages_in_use,
                    sched.allocator.allocated_count)
                if not progress or not sched.waiting:
                    break

            if sched.waiting and sched.free_slots() and sched.slots:
                # a slot sits idle because the pool can't hold the head
                # request's pages — the capacity ceiling int8 KV raises
                metrics.admission_stalls += 1

            if debug_audit:
                # fault-injection harness: refcount + host accounting
                # invariants must hold on EVERY iteration, not just at
                # the end of the run
                sched.allocator.check()
                if host is not None:
                    host.check()

            if not sched.slots:
                if sched.waiting:
                    # head request can never fit (no slot is live and
                    # eviction already reclaimed every unpinned cached
                    # page): fail it with a structured outcome rather
                    # than spin forever, and keep serving the rest
                    head = sched.waiting[0]
                    detail = (f"needs {sched.queued_pages_needed(head)} "
                              f"pages but the pool holds "
                              f"{sched.allocator.num_pages} "
                              f"({sched.allocator.free_count} free after "
                              f"eviction)")
                    warnings.warn(
                        f"request {head.uid}: {detail}; rejecting")
                    req = sched.fail_head(detail)
                    count_outcome(req)
                    emit_iteration(budget_used=0, decode_lanes=0,
                                   chunk_segments=0, chunk_tokens=0,
                                   width_bucket=0, padded_lanes=0,
                                   idle=True)
                    continue
                if incoming:        # idle until the next arrival
                    time.sleep(max(0.0, min(incoming[0][0] - now(), 0.01)))
                emit_iteration(budget_used=0, decode_lanes=0,
                               chunk_segments=0, chunk_tokens=0,
                               width_bucket=0, padded_lanes=0, idle=True)
                continue

            # -- unified token-budget iteration ----------------------------
            # any admitting slot -> one mixed iteration: every decoding
            # slot advances one token (single fused dispatch), then the
            # FCFS prefill chunks run packed (budget-bounded compute).
            # Pure-decode iterations fall through to the fused
            # steps_per_sync scan below.
            if chunked:
                plan = sched.next_batch(budget)
                if plan.chunks:
                    if packed_on:
                        # token-packed ragged: the WHOLE iteration is
                        # one (1, T) dispatch (accounted inside)
                        run_packed(plan)
                        continue
                    metrics.mixed_iters += 1
                    metrics.mixed_dispatches += len(plan.chunks)
                    if plan.decode_slots:
                        metrics.mixed_dispatches += 1
                    padded = run_mixed(plan)
                    emit_iteration(
                        budget_used=int(plan.total_tokens),
                        decode_lanes=len(plan.decode_slots),
                        chunk_segments=len(plan.chunks),
                        chunk_tokens=int(sum(c.length
                                             for c in plan.chunks)),
                        width_bucket=0, padded_lanes=int(padded),
                        idle=False)
                    continue

            # -- fused decode steps ---------------------------------------
            n_lanes = int(act.sum())   # lanes entering this dispatch
            if spec_on:
                # draft (host) -> one batched verify forward -> accept
                # the longest valid prefix per slot -> rewind rejected
                # KV.  One host sync per verify window.  Host-side
                # drafting stays inside the span, exactly like the
                # inline timer it replaced.
                with dev_span("verify", "decode"):
                    contexts: List[Optional[list]] = [None] * slots
                    for slot, st in sched.slots.items():
                        if act[slot]:
                            contexts[slot] = st.request.tokens + st.emitted
                    drafts = drafter.propose_slots(contexts)
                    (tok_d, lens_d, rem_d, act_d, cache, rng, emits,
                     accepted, moe) = verify_fn(
                        self.params, jnp.asarray(tok), jnp.asarray(lens),
                        jnp.asarray(rem), jnp.asarray(act),
                        jnp.asarray(drafts), jnp.asarray(block_tables),
                        cache, rng)
                    emits = np.asarray(jax.block_until_ready(emits))
                metrics.host_syncs += 1
                if tr is not None:
                    count_moe(moe)
                    # each live lane verifies k drafts after its pending
                    # token: a segment of k + 1 queries from lens
                    count_attention(lens[act], drafter.k + 1)
                    count_pages(row_qpos(lens + drafter.k))
                metrics.steps += 1
                metrics.slot_steps_total += slots
                metrics.slot_steps_active += n_lanes
                metrics.drafted_tokens += drafter.k * n_lanes
                metrics.accepted_tokens += int(np.asarray(accepted).sum())
                budget_used = n_lanes * (drafter.k + 1)
            else:
                with dev_span("decode", "decode"):
                    (tok_d, lens_d, rem_d, act_d, cache, rng, emits,
                     acts, moe) = step_fn(self.params, jnp.asarray(tok),
                                          jnp.asarray(lens),
                                          jnp.asarray(rem),
                                          jnp.asarray(act),
                                          jnp.asarray(block_tables), cache,
                                          rng)
                    emits = np.asarray(jax.block_until_ready(emits))
                metrics.host_syncs += 1
                acts = np.asarray(acts)
                if tr is not None:
                    count_moe(moe)
                    count_decode_attention(lens, acts)
                metrics.steps += steps_per_sync
                metrics.slot_steps_total += slots * steps_per_sync
                metrics.slot_steps_active += int(acts.sum())
                budget_used = n_lanes * steps_per_sync
            apply_decode_results(tok_d, lens_d, rem_d, act_d, emits)
            emit_iteration(budget_used=int(budget_used),
                           decode_lanes=n_lanes, chunk_segments=0,
                           chunk_tokens=0, width_bucket=0, padded_lanes=0,
                           idle=False)

        # host/device wall-time split for the whole run: device_s is the
        # time spent inside (blocking) device dispatches, host_s is
        # everything else — scheduling, packing, bookkeeping, idling for
        # arrivals.  Mid-prompt chunk dispatches are async, so their
        # device time books against whichever later dispatch blocks.
        metrics.device_s = stats.prefill_s + stats.decode_s
        metrics.host_s = max(0.0, now() - metrics.device_s)
        self.rng = rng
        ctx["cache"] = cache           # pool persists across serve calls
        if fault_hold:                 # release the injected squatter
            sched.allocator.free(fault_hold)
        metrics.prefix_evicted_pages = trie.evicted_pages - trie_base
        # trie spills count as offloads, promotions as restores; the trie
        # outlives this call but its device closure must not (the next
        # serve rebinds a fresh cache) — spills pause between calls
        metrics.offloaded_pages += trie.spilled_pages - spill_base
        metrics.restored_pages += sched.promoted_pages - promote_base
        trie.offload_fn = None
        trie.trace = None
        if host is not None:
            host.trace = None
            host.check()
            metrics.host_bytes_used = host.used_bytes
            metrics.host_bytes_peak = host.peak_bytes
        if self.prune_maps is not None:
            for r in requests:
                if r.result:
                    r.result = [int(t) for t in PR.unmap_tokens(
                        np.asarray([r.result]), self.prune_maps)[0]]
        stats.generated_tokens = metrics.generated_tokens
        self.stats.merge(stats)
        # pool accounting must balance: every page is free or cached, and
        # nothing a retired request held leaked (alloc == free + resident)
        sched.allocator.check()
        resident = trie.resident_pages
        if sorted(set(resident)) != sorted(
                p for p in range(num_pages)
                if sched.allocator.refcount(p) > 0) \
                or any(sched.allocator.refcount(p) != 1 for p in resident):
            raise AssertionError("page leak: allocated pages != pages "
                                 "resident in the prefix cache")
        return requests, metrics

    # -- request-level API (P4 dynamic batching) -------------------------
    def serve(self, requests: List[Request],
              sp: SamplingParams = SamplingParams()) -> List[Request]:
        batcher = DynamicBatcher(max_batch=self.max_batch,
                                 buckets=self.prompt_buckets())
        for r in requests:
            batcher.add(r)
        while True:
            batch = batcher.next_batch()
            if batch is None:
                break
            toks, lens = pad_batch(batch)
            max_new = max(r.max_new_tokens for r in batch.requests)
            gen = self.generate_batch(toks, lens, max_new, sp)
            for i, r in enumerate(batch.requests):
                row = gen[i]
                r.result = [int(t) for t in row[row >= 0]][:r.max_new_tokens]
        return requests
