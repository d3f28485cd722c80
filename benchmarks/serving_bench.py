"""Serving benchmark: bucket vs continuous batching vs prefix-cached.

Drives one request trace through the request-level paths of the engine
and reports tokens/s, per-request completion latency (p50/p99), and
padding/idle/prefill waste:

  * bucket:      DynamicBatcher -> generate_batch per bucket, every request
                 in a batch decodes until the batch's longest one finishes
  * continuous:  persistent decode slots + paged KV pool; admit on free
                 slot, retire at EOS (engine.serve_continuous)
  * continuous+prefix: the radix prefix cache maps shared prompt-prefix
                 pages zero-copy and prefills only each request's suffix

Two trace shapes:
  * mixed:  short-head/long-tail prompt lengths (the paper's Fig.-3
            observation), no intentional sharing
  * shared: N requests over --prefix-groups distinct system prompts —
            the multi-tenant workload prefix caching targets

``--kv-sweep`` additionally serves the same trace at kv_dtype bf16 and
int8 under an equal-bytes pool budget (int8 gets 2x the pages) and
records tokens/s, p50/p99, admission stalls and prefix evictions per
leg, plus greedy-output parity against a full-precision reference.

``--spec {ngram,draft}`` adds a speculative-decoding leg: the same trace
served with draft-verify decoding (prompt-lookup n-gram drafter, or the
model self-drafting for the "draft" smoke), recording acceptance rate,
tokens per forward, tokens/s — and greedy parity vs the non-speculative
continuous run, which must be bit-exact.

``--trace longprompt`` stresses the unified token-budget scheduler: one
``--long-prompt-len`` prompt arrives while short requests decode.  The
A/B leg serves it with chunked prefill OFF (whole-prompt admission — the
prompt's forward stalls every decode slot) and ON (budgeted chunks
interleaved with decode) and records TTFT / inter-token-latency
percentiles each way; greedy outputs must be bit-identical.

``--trace overload`` stresses the overload ladder: long prompts arrive
in three bursts against a page pool sized to ~1/3 of aggregate demand,
with LRU preemption and a host KV tier enabled.  The A/B leg compares
an uncontended run (pool = full demand) against the contended one:
every request must still complete with a terminal outcome, at least
one preemption must fire, greedy outputs must be bit-identical, and
the per-iteration allocator/host audit runs throughout.

Results are also written as machine-readable JSON (--out, default
``BENCH_serving.json``) so the perf trajectory is tracked across PRs.

Usage:
    PYTHONPATH=src python benchmarks/serving_bench.py \
        --arch unimo-text --requests 64 --max-batch 8 [--poisson 20] \
        [--trace shared --prefix-groups 8 --prefix-len 64]

CPU-friendly by default (reduced config, small trace); the same trace
shapes run unchanged on TPU.
"""
from __future__ import annotations

import argparse
import copy
import json
import time

import jax
import numpy as np

from repro.configs.registry import get_reduced, list_archs
from repro.core.engine import InferenceEngine
from repro.core.sampling import SamplingParams
from repro.core.scheduler import DynamicBatcher, Request, pad_batch
from repro.launch.serve import enable_compile_cache, serving_policy


def build_trace(n: int, seed: int, vocab: int, max_prompt: int,
                max_new: int):
    """Mixed-length trace: short-head/long-tail prompt lengths (the
    paper's Fig.-3 observation) and per-request generation budgets."""
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.lognormal(mean=2.5, sigma=0.8, size=n).astype(int) + 2,
                   3, max_prompt)
    news = rng.integers(max(2, max_new // 4), max_new + 1, size=n)
    reqs = [Request(uid=i,
                    tokens=[2] + list(map(int, rng.integers(
                        4, vocab, size=int(lens[i]) - 1))),
                    max_new_tokens=int(news[i]))
            for i in range(n)]
    return reqs


def build_shared_trace(n: int, seed: int, vocab: int, groups: int,
                       prefix_len: int, suffix_max: int, max_new: int):
    """Shared-prefix trace: ``n`` requests over ``groups`` distinct
    system prompts of ``prefix_len`` tokens, each with its own short
    suffix — the multi-tenant serving shape where cross-request KV reuse
    pays."""
    rng = np.random.default_rng(seed)
    prefixes = [[2] + list(map(int, rng.integers(4, vocab,
                                                 size=prefix_len - 1)))
                for _ in range(groups)]
    reqs = []
    for i in range(n):
        g = int(rng.integers(0, groups))
        suffix = list(map(int, rng.integers(
            4, vocab, size=int(rng.integers(2, suffix_max + 1)))))
        reqs.append(Request(uid=i, tokens=prefixes[g] + suffix,
                            max_new_tokens=int(rng.integers(
                                max(2, max_new // 2), max_new + 1))))
    return reqs


def build_longprompt_trace(n_short: int, seed: int, vocab: int,
                           long_len: int, max_new: int):
    """Adversarial chunked-prefill trace: ``n_short`` short prompts
    arrive at t=0 and decode steadily; ONE ``long_len``-token prompt
    arrives mid-decode.  Without chunked prefill its whole-prompt
    admission forward stalls every decoding slot at once — the
    inter-token-latency p99 spike this PR's unified scheduler removes.
    Returns (requests, arrivals)."""
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i,
                    tokens=[2] + list(map(int, rng.integers(
                        4, vocab, size=int(rng.integers(5, 12))))),
                    max_new_tokens=max_new)
            for i in range(n_short)]
    reqs.append(Request(uid=n_short,
                        tokens=[2] + list(map(int, rng.integers(
                            4, vocab, size=long_len - 1))),
                        max_new_tokens=max(4, max_new // 4)))
    arrivals = [0.0] * n_short + [0.2]
    return reqs, arrivals


def build_overload_trace(n: int, seed: int, vocab: int, max_prompt: int,
                         max_new: int):
    """Adversarial burst trace for the overload ladder: ``n`` requests
    with deliberately long prompts (upper half of the length range, so
    aggregate page demand is high) arriving in three tight waves.
    Returns (requests, arrivals)."""
    rng = np.random.default_rng(seed)
    reqs, arrivals = [], []
    waves = [0.0, 0.15, 0.3]
    for i in range(n):
        ln = int(rng.integers(max(3, max_prompt // 2), max_prompt + 1))
        reqs.append(Request(uid=i,
                            tokens=[2] + list(map(int, rng.integers(
                                4, vocab, size=ln - 1))),
                            max_new_tokens=max_new))
        arrivals.append(waves[(len(waves) * i) // n])
    return reqs, arrivals


def run_overload_ab(args, engine_factory, trace, sp, arrivals,
                    tracer=None):
    """Serve the burst trace uncontended (pool = aggregate demand) and
    contended (pool ~1/3 of demand, LRU preemption + host KV tier,
    per-iteration audit on) and compare: the contended run must preempt
    at least once yet finish every request with a terminal outcome and
    bit-identical greedy output — overload degrades latency, never
    results."""
    from repro.core.scheduler import TERMINAL_STATUSES
    ps = args.page_size
    pages_per_slot = -(-args.max_len // ps)
    demand = sum(min(-(-(len(r.tokens) + r.max_new_tokens) // ps),
                     pages_per_slot) for r in trace)
    contended_pool = args.num_pages or max(pages_per_slot + 2, demand // 3)
    legs, outs, outcomes = {}, {}, {}
    for name, kw in (
            ("uncontended", dict(num_pages=demand)),
            ("contended", dict(num_pages=contended_pool, preemption="lru",
                               host_kv_bytes=1 << 30, debug_audit=True))):
        eng = engine_factory()
        run_continuous(eng, copy.deepcopy(trace), sp,       # warm compile
                       page_size=ps, steps_per_sync=args.steps_per_sync,
                       max_batched_tokens=args.max_batched_tokens,
                       chunked_prefill=True, **kw)
        reqs = copy.deepcopy(trace)
        # trace only the contended leg — the run the timeline is FOR
        # (preempt/offload/restore events live there)
        legs[name] = run_continuous(
            eng, reqs, sp, page_size=ps,
            steps_per_sync=args.steps_per_sync, arrivals=arrivals,
            max_batched_tokens=args.max_batched_tokens,
            chunked_prefill=True,
            tracer=tracer if name == "contended" else None, **kw)
        legs[name]["num_pages"] = kw["num_pages"]
        outs[name] = [r.result for r in reqs]
        outcomes[name] = [r.outcome for r in reqs]
    contended = outcomes["contended"]
    return {
        "demand_pages": demand,
        "contended_pool_frac": round(contended_pool / demand, 3),
        "uncontended": legs["uncontended"],
        "contended": legs["contended"],
        "all_terminal": all(oc is not None
                            and oc.status in TERMINAL_STATUSES
                            for oc in contended),
        "all_completed": all(oc is not None and oc.status == "completed"
                             for oc in contended),
        "outputs_identical_contended":
            outs["contended"] == outs["uncontended"],
    }


def run_longprompt_ab(args, engine_factory, trace, sp, arrivals,
                      tracer=None):
    """Serve the longprompt trace with chunking OFF (bucketed
    whole-prompt admission) and ON (unified token-budget scheduler) and
    record the inter-token-latency tail each way — plus greedy parity,
    which must be bit-exact."""
    from repro.core.engine import (DEFAULT_MAX_BATCHED_TOKENS,
                                   packed_width_buckets)
    legs = {}
    outs = {}
    for name, on in (("chunked_off", False), ("chunked_on", True)):
        eng = engine_factory()
        run_continuous(eng, copy.deepcopy(trace), sp,       # warm compile
                       page_size=args.page_size, num_pages=args.num_pages,
                       steps_per_sync=args.steps_per_sync,
                       max_batched_tokens=args.max_batched_tokens,
                       chunked_prefill=on)
        if on:
            # stream widths depend on how many slots were decoding when
            # each chunk was cut — i.e. on arrival timing — so the trace
            # warm-up above may miss width buckets the measured run
            # hits.  Touch every packed stream width once (one lone
            # request per bucket packs as a single that-wide stream)
            # so the measured run never pays a mid-trace XLA compile.
            budget = args.max_batched_tokens or DEFAULT_MAX_BATCHED_TOKENS
            for i, w in enumerate(packed_width_buckets(budget)):
                if w > args.max_len - 4:
                    break
                # prefix matching must be off here: a warm request would
                # otherwise match the previous warm's cached context and
                # chunk only the suffix, skipping the width it exists
                # to compile
                eng.serve_continuous(
                    [Request(uid=10_000 + i, tokens=[2] * w,
                             max_new_tokens=2)],
                    sp, page_size=args.page_size,
                    num_pages=args.num_pages,
                    steps_per_sync=args.steps_per_sync,
                    max_batched_tokens=args.max_batched_tokens,
                    chunked_prefill=True, prefix_cache=False)
        eng.reset_prefix_cache()
        reqs = copy.deepcopy(trace)
        # trace only the measured chunked_on leg (the configuration the
        # timeline describes), never the warm-ups above
        legs[name] = run_continuous(
            eng, reqs, sp, page_size=args.page_size,
            num_pages=args.num_pages, steps_per_sync=args.steps_per_sync,
            arrivals=arrivals, max_batched_tokens=args.max_batched_tokens,
            chunked_prefill=on, tracer=tracer if on else None)
        outs[name] = [r.result for r in reqs]
    off_p99, on_p99 = (legs["chunked_off"]["itl_p99_s"],
                       legs["chunked_on"]["itl_p99_s"])
    return {
        **legs,
        "itl_p99_improvement": round(off_p99 / on_p99, 3)
        if on_p99 else float("nan"),
        "outputs_identical_chunked_on_off":
            outs["chunked_on"] == outs["chunked_off"],
    }


def run_packed_ab(args, engine_factory, trace, sp, arrivals):
    """Serve the trace on the unified scheduler with token-packed
    execution OFF (decode micro-step + one (1, W) dispatch per prefill
    chunk) and ON (the whole mixed iteration as ONE (1, T) ragged
    dispatch) — greedy parity must be bit-exact; the packed leg must
    make exactly one dispatch per mixed iteration with near-zero padded
    FLOPs."""
    from repro.core.engine import (DEFAULT_MAX_BATCHED_TOKENS,
                                   mixed_width_buckets,
                                   packed_width_buckets)
    legs = {}
    outs = {}
    for name, on in (("packed_off", False), ("packed_on", True)):
        eng = engine_factory()
        run_continuous(eng, copy.deepcopy(trace), sp,       # warm compile
                       page_size=args.page_size, num_pages=args.num_pages,
                       steps_per_sync=args.steps_per_sync,
                       max_batched_tokens=args.max_batched_tokens,
                       chunked_prefill=True, packed=on)
        # chunk widths (bucketed leg) and stream widths (packed leg)
        # both depend on arrival timing; touch every width bucket of
        # the leg's own ladder once so the measured run never pays a
        # mid-trace XLA compile
        budget = args.max_batched_tokens or DEFAULT_MAX_BATCHED_TOKENS
        ladder = (packed_width_buckets if on else mixed_width_buckets)
        for i, w in enumerate(ladder(budget)):
            if w > args.max_len - 4:
                break
            eng.serve_continuous(
                [Request(uid=20_000 + i, tokens=[2] * w,
                         max_new_tokens=2)],
                sp, page_size=args.page_size, num_pages=args.num_pages,
                steps_per_sync=args.steps_per_sync,
                max_batched_tokens=args.max_batched_tokens,
                chunked_prefill=True, packed=on, prefix_cache=False)
        eng.reset_prefix_cache()
        reqs = copy.deepcopy(trace)
        legs[name] = run_continuous(
            eng, reqs, sp, page_size=args.page_size,
            num_pages=args.num_pages, steps_per_sync=args.steps_per_sync,
            arrivals=arrivals, max_batched_tokens=args.max_batched_tokens,
            chunked_prefill=True, packed=on)
        outs[name] = [r.result for r in reqs]
    off, on = legs["packed_off"], legs["packed_on"]
    return {
        **legs,
        "tokens_per_s_ratio": round(
            on["tokens_per_s"] / off["tokens_per_s"], 3)
        if off["tokens_per_s"] else float("nan"),
        "itl_p99_improvement": round(
            off["itl_p99_s"] / on["itl_p99_s"], 3)
        if on["itl_p99_s"] else float("nan"),
        "outputs_identical_packed_on_off":
            outs["packed_on"] == outs["packed_off"],
    }


def run_bucket(engine: InferenceEngine, reqs, sp, arrivals=None) -> dict:
    """engine.serve semantics, instrumented per batch for latencies and
    padding accounting.  With ``arrivals``, requests join the batcher
    open-loop as they arrive (same workload the continuous path sees)."""
    batcher = DynamicBatcher(max_batch=engine.max_batch)
    incoming = sorted(zip(arrivals, reqs),
                      key=lambda p: p[0]) if arrivals else None
    if incoming is None:
        for r in reqs:
            batcher.add(r)
    arrival_of = dict(zip((r.uid for r in reqs), arrivals)) \
        if arrivals else {}
    t0 = time.perf_counter()
    lat, gen_tokens = {}, 0
    prompt_real = prompt_padded = 0
    decode_slot_steps = decode_live_steps = 0
    while True:
        if incoming:
            now = time.perf_counter() - t0
            while incoming and incoming[0][0] <= now:
                batcher.add(incoming.pop(0)[1])
        batch = batcher.next_batch()
        if batch is None:
            if not incoming:
                break
            time.sleep(min(0.01, max(0.0, incoming[0][0]
                                     - (time.perf_counter() - t0))))
            continue
        toks, lens = pad_batch(batch)
        max_new = max(r.max_new_tokens for r in batch.requests)
        gen = engine.generate_batch(toks, lens, max_new, sp)
        done_t = time.perf_counter() - t0
        prompt_real += int(lens.sum())
        prompt_padded += toks.size
        for i, r in enumerate(batch.requests):
            row = gen[i]
            r.result = [int(t) for t in row[row >= 0]][:r.max_new_tokens]
            # whole batch completes together; latency is arrival->done
            lat[r.uid] = done_t - arrival_of.get(r.uid, 0.0)
            gen_tokens += len(r.result)
            decode_live_steps += len(r.result)
        # every slot runs as many steps as the batch's longest request
        steps = int((gen >= 0).sum(axis=1).max(initial=0))
        decode_slot_steps += steps * batch.size
    wall = time.perf_counter() - t0
    lats = np.asarray([lat[r.uid] for r in reqs])
    return {
        "wall_s": round(wall, 3),
        "generated_tokens": gen_tokens,
        "tokens_per_s": round(gen_tokens / wall, 2),
        "p50_latency_s": round(float(np.percentile(lats, 50)), 3),
        "p99_latency_s": round(float(np.percentile(lats, 99)), 3),
        "prefill_pad_frac": round(1 - prompt_real / prompt_padded, 3)
        if prompt_padded else 0.0,
        "decode_idle_frac": round(
            1 - decode_live_steps / decode_slot_steps, 3)
        if decode_slot_steps else 0.0,
    }


def run_continuous(engine: InferenceEngine, reqs, sp, *, page_size,
                   steps_per_sync, arrivals=None, prefix_cache=False,
                   num_pages=None, spec=None, max_batched_tokens=None,
                   chunked_prefill=None, packed=None, preemption="off",
                   host_kv_bytes=None, debug_audit=False,
                   tracer=None) -> dict:
    t0 = time.perf_counter()
    _, m = engine.serve_continuous(reqs, sp, page_size=page_size,
                                   num_pages=num_pages,
                                   steps_per_sync=steps_per_sync,
                                   arrivals=arrivals,
                                   prefix_cache=prefix_cache, spec=spec,
                                   max_batched_tokens=max_batched_tokens,
                                   chunked_prefill=chunked_prefill,
                                   packed=packed, preemption=preemption,
                                   host_kv_bytes=host_kv_bytes,
                                   debug_audit=debug_audit, trace=tracer)
    wall = time.perf_counter() - t0
    out = {
        "wall_s": round(wall, 3),
        "generated_tokens": m.generated_tokens,
        "tokens_per_s": round(m.generated_tokens / wall, 2),
        "p50_latency_s": round(m.percentile_latency(50), 3),
        "p99_latency_s": round(m.percentile_latency(99), 3),
        "ttft_p50_s": round(m.ttft_p50, 4),
        "ttft_p99_s": round(m.ttft_p99, 4),
        "itl_p50_s": round(m.itl_p50, 4),
        "itl_p99_s": round(m.itl_p99, 4),
        "scheduler": m.scheduler,
        "max_batched_tokens": m.max_batched_tokens,
        "prefill_chunks": m.prefill_chunks,
        "prefill_pad_frac": round(m.prefill_pad_frac, 3),
        "decode_idle_frac": round(m.decode_idle_frac, 3),
        "mixed_iters": m.mixed_iters,
        "dispatches_per_iter": round(m.dispatches_per_iter, 3),
        "padded_token_frac": round(m.padded_token_frac, 3),
        "host_s": round(m.host_s, 3),
        "device_s": round(m.device_s, 3),
        "host_frac": round(m.host_frac, 3),
        "prefill_tokens": m.prefill_tokens,
        "prefix_hit_rate": round(m.prefix_hit_rate, 3),
        "prefix_matched_tokens": m.prefix_matched_tokens,
        "pages_shared": m.pages_shared,
        "cow_copies": m.cow_copies,
        "prefix_evicted_pages": m.prefix_evicted_pages,
        "kv_dtype": m.kv_dtype,
        "kv_pool_bytes": m.kv_pool_bytes,
        "kv_bytes_per_token": round(m.kv_bytes_per_token, 1),
        "weight_dtype": m.weight_dtype,
        "weight_bytes": m.weight_bytes,
        "weight_bytes_saved": m.weight_bytes_saved,
        "host_syncs": m.host_syncs,
        "peak_pages_in_use": m.peak_pages_in_use,
        "admission_stalls": m.admission_stalls,
        "rejected": m.rejected,
        "preemptions": m.preemptions,
        "resumed": m.resumed,
        "offloaded_pages": m.offloaded_pages,
        "restored_pages": m.restored_pages,
        "host_bytes_peak": m.host_bytes_peak,
        "timed_out": m.timed_out,
        "deadline_misses": m.deadline_misses,
        "outcomes": dict(sorted(m.outcome_counts.items())),
        "spec_mode": m.spec_mode,
        "spec_k": m.spec_k,
        "drafted_tokens": m.drafted_tokens,
        "accepted_tokens": m.accepted_tokens,
        "acceptance_rate": round(m.acceptance_rate, 3),
        "tokens_per_forward": round(m.tokens_per_forward, 3),
    }
    if tracer is not None:
        # reconcile the per-iteration timeline against the end-of-run
        # accounting: iteration device_s sums should match exactly (both
        # sides sum the same spans); host_s misses only pre/post-loop
        # setup, so its ratio is the acceptance gate's 5% check
        it = [e for e in tracer.events if e["kind"] == "iteration"]
        dev = sum(e["device_s"] for e in it)
        hst = sum(e["host_s"] for e in it)
        out["trace_iterations"] = len(it)
        out["trace_events"] = len(tracer.events)
        out["trace_device_span_s"] = round(dev, 4)
        out["trace_host_span_s"] = round(hst, 4)
        out["trace_device_recon"] = round(dev / m.device_s, 4) \
            if m.device_s else 1.0
        out["trace_host_recon"] = round(hst / m.host_s, 4) \
            if m.host_s else 1.0
    return out


def run_kv_sweep(args, cfg, params, base_policy, trace, sp, arrivals):
    """Same trace at kv_dtype bf16 vs int8 under an *equal-bytes* pool
    budget: bf16 gets ``budget`` pages, int8 gets 2x (half the bytes per
    K/V element; the small per-entry scale overhead is visible in the
    recorded kv_pool_bytes).  More pages means more concurrent slots and
    fewer prefix evictions, which is where the int8 throughput win comes
    from.  A full-precision (kv auto) leg provides the greedy-output
    reference."""
    import dataclasses
    slots = args.max_batch
    pages_per_slot = -(-args.max_len // args.page_size)
    # headroom above one slot's worth: a head-of-line request may need
    # the full pages_per_slot while its COW source page is pinned, and a
    # rejected request would make the output-parity comparison unfair
    budget = args.kv_budget_pages or max(pages_per_slot + 2,
                                         (slots * pages_per_slot) // 2)
    legs, outs = {}, {}
    for name, kv, pages in (("fp", "auto", budget),
                            ("bf16", "bf16", budget),
                            ("int8", "int8", 2 * budget)):
        pol = dataclasses.replace(base_policy, kv_dtype=kv)
        eng = InferenceEngine(cfg, params, policy=pol, max_batch=slots,
                              max_len=args.max_len)
        run_continuous(eng, copy.deepcopy(trace), sp,       # warm compile
                       page_size=args.page_size, num_pages=pages,
                       steps_per_sync=args.steps_per_sync,
                       prefix_cache=True)
        eng.reset_prefix_cache()                            # cold trie
        reqs = copy.deepcopy(trace)
        legs[name] = run_continuous(eng, reqs, sp,
                                    page_size=args.page_size,
                                    num_pages=pages,
                                    steps_per_sync=args.steps_per_sync,
                                    arrivals=arrivals, prefix_cache=True)
        legs[name]["num_pages"] = pages
        outs[name] = [r.result for r in reqs]
    speedup = (legs["int8"]["tokens_per_s"] / legs["bf16"]["tokens_per_s"]
               if legs["bf16"]["tokens_per_s"] else float("nan"))
    n = len(outs["fp"]) or 1
    return {
        "equal_bytes_budget_pages_bf16": budget,
        "fp_reference": legs["fp"],
        "bf16": legs["bf16"],
        "int8": legs["int8"],
        "int8_speedup_tokens_per_s": round(speedup, 3),
        "int8_outputs_match_fp": outs["int8"] == outs["fp"],
        # per-request greedy agreement with full precision — int8 KV
        # perturbs logits by ~absmax/254 per element, so requests whose
        # greedy margin sits below that can flip (see README precision)
        "int8_greedy_match_frac": round(sum(
            a == b for a, b in zip(outs["int8"], outs["fp"])) / n, 3),
        "int8_outputs_match_bf16": outs["int8"] == outs["bf16"],
    }


def run_weight_sweep(args, cfg, params, base_policy, trace, sp, arrivals):
    """Same trace at weights_dtype bf16 vs int8 (identical pool, slots
    and arrivals — weight storage is the only variable): int8 reads
    roughly half the weight bytes per matmul, which is where the
    decode-side win comes from on weight-bound hardware.  A
    full-precision (weights auto) leg provides the greedy-output
    reference; per-request agreement is recorded as a fraction, never
    asserted away — requests whose greedy margin sits below the
    per-channel quantization noise can flip (see README precision)."""
    import dataclasses
    legs, outs = {}, {}
    for name, wd in (("fp", "auto"), ("bf16", "bf16"), ("int8", "int8")):
        pol = dataclasses.replace(base_policy, weights_dtype=wd)
        eng = InferenceEngine(cfg, params, policy=pol,
                              max_batch=args.max_batch,
                              max_len=args.max_len)
        run_continuous(eng, copy.deepcopy(trace), sp,       # warm compile
                       page_size=args.page_size, num_pages=args.num_pages,
                       steps_per_sync=args.steps_per_sync,
                       prefix_cache=True)
        eng.reset_prefix_cache()                            # cold trie
        reqs = copy.deepcopy(trace)
        legs[name] = run_continuous(eng, reqs, sp,
                                    page_size=args.page_size,
                                    num_pages=args.num_pages,
                                    steps_per_sync=args.steps_per_sync,
                                    arrivals=arrivals, prefix_cache=True)
        outs[name] = [r.result for r in reqs]
    speedup = (legs["int8"]["tokens_per_s"] / legs["bf16"]["tokens_per_s"]
               if legs["bf16"]["tokens_per_s"] else float("nan"))
    bf16_bytes = legs["bf16"]["weight_bytes"]
    n = len(outs["fp"]) or 1
    return {
        "fp_reference": legs["fp"],
        "bf16": legs["bf16"],
        "int8": legs["int8"],
        "int8_speedup_tokens_per_s": round(speedup, 3),
        # codes + fp32 scales vs the same tensors at 2 bytes/element
        "int8_weight_bytes_ratio_vs_bf16": round(
            legs["int8"]["weight_bytes"] / bf16_bytes, 3)
        if bf16_bytes else float("nan"),
        "int8_outputs_match_fp": outs["int8"] == outs["fp"],
        "int8_greedy_match_frac": round(sum(
            a == b for a, b in zip(outs["int8"], outs["fp"])) / n, 3),
        "int8_outputs_match_bf16": outs["int8"] == outs["bf16"],
    }


def run_spec_leg(args, engine_factory, trace, sp, arrivals, baseline_reqs):
    """Serve the trace with draft-verify decoding and compare against the
    non-speculative continuous outputs: greedy parity must be bit-exact
    (the rejection sampler's guarantee), and the acceptance rate /
    tokens-per-forward quantify how much forward-count the drafter
    saved."""
    from repro.core.speculative import SpecConfig
    spec = SpecConfig(k=args.spec_k,
                      drafter=("ngram" if args.spec == "ngram"
                               else "draft_model"),
                      max_ngram=args.spec_ngram)
    eng = engine_factory()
    run_continuous(eng, copy.deepcopy(trace), sp,          # warm compile
                   page_size=args.page_size, num_pages=args.num_pages,
                   steps_per_sync=args.steps_per_sync,
                   prefix_cache=True, spec=spec)
    eng.reset_prefix_cache()
    reqs = copy.deepcopy(trace)
    leg = run_continuous(eng, reqs, sp, page_size=args.page_size,
                         num_pages=args.num_pages,
                         steps_per_sync=args.steps_per_sync,
                         arrivals=arrivals, prefix_cache=True, spec=spec)
    leg["outputs_match_nonspec"] = all(
        a.result == b.result for a, b in zip(reqs, baseline_reqs))
    return leg


def finish_tracing(report, tracer, out_path, fmt):
    """Export + schema-validate the measured run's trace and record the
    verdict under report['tracing'] ('trace' already names the workload
    shape)."""
    from repro.core.trace import export, validate_events
    errors = validate_events(tracer.events)
    paths = export(tracer, out_path, fmt)
    report["tracing"] = {
        "events": len(tracer.events),
        "dropped": tracer.dropped,
        "schema_valid": not errors,
        "errors": errors[:5],
        "paths": paths,
    }
    for p in paths:
        print(f"trace: {p}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="unimo-text", choices=list_archs())
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="bucket batch size == continuous decode slots")
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool size (default: slots * pages-per-slot"
                         "; give the radix cache headroom to retain "
                         "prefixes by sizing above the slot minimum)")
    ap.add_argument("--steps-per-sync", type=int, default=8)
    ap.add_argument("--max-batched-tokens", type=int, default=None,
                    help="per-iteration token budget of the unified "
                         "scheduler (decode + chunked-prefill tokens); "
                         "default: engine default (256)")
    ap.add_argument("--long-prompt-len", type=int, default=1024,
                    help="prompt length of the adversarial request in "
                         "--trace longprompt (max-len grows to fit)")
    ap.add_argument("--policy", default="fp32",
                    choices=["fp32", "bf16", "fp16"])
    ap.add_argument("--kv-dtype", default="auto",
                    choices=["auto", "bf16", "fp16", "int8"],
                    help="KV-pool storage dtype for the main runs")
    ap.add_argument("--kv-sweep", action="store_true",
                    help="also run the same trace at kv bf16 vs int8 "
                         "under an equal-bytes pool budget (int8 gets 2x "
                         "pages) and record the comparison")
    ap.add_argument("--kv-budget-pages", type=int, default=None,
                    help="bf16 page budget for --kv-sweep (int8 gets 2x); "
                         "default: half the slots' worth of pages")
    ap.add_argument("--weights-dtype", default="auto",
                    choices=["auto", "bf16", "fp16", "int8"],
                    help="serve-path weight storage dtype for the main "
                         "runs (int8 = quantized codes + per-channel "
                         "scales with fused-dequant matmuls)")
    ap.add_argument("--weight-sweep", action="store_true",
                    help="also run the same trace at weights bf16 vs "
                         "int8 (equal trace, pool and arrivals) and "
                         "record tokens/s, ITL p99, weight bytes and "
                         "greedy parity vs a full-precision reference")
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "draft"],
                    help="add a speculative-decoding leg: ngram = "
                         "prompt-lookup drafter (no extra weights); "
                         "draft = draft-model drafter (self-drafting "
                         "smoke: the target model drafts for itself, so "
                         "greedy acceptance is ~100%%)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per slot per verify step")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest trailing n-gram the lookup drafter "
                         "matches")
    ap.add_argument("--poisson", type=float, default=None,
                    help="arrival rate (req/s) for an open-loop trace; "
                         "default: all requests arrive at t=0")
    ap.add_argument("--trace", default="mixed",
                    choices=["mixed", "shared", "longprompt", "overload"],
                    help="mixed: lognormal lengths; shared: N requests "
                         "over --prefix-groups shared system prompts; "
                         "longprompt: one --long-prompt-len prompt "
                         "arriving mid-decode (chunked-prefill A/B: ITL "
                         "p99 with the unified scheduler on vs off); "
                         "overload: bursty long prompts vs a pool ~1/3 "
                         "of demand (preemption + host-offload A/B: all "
                         "requests must complete bit-identically)")
    ap.add_argument("--prefix-groups", type=int, default=8)
    ap.add_argument("--prefix-len", type=int, default=64)
    ap.add_argument("--suffix-max", type=int, default=12)
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="machine-readable results path ('' to skip)")
    ap.add_argument("--trace-out", default="",
                    help="write a serve-loop trace of the final measured "
                         "run of this shape (mixed/shared: the prefix "
                         "leg; longprompt: the chunked_on leg; overload: "
                         "the contended leg); '' = no tracing")
    ap.add_argument("--trace-format", default="both",
                    choices=["jsonl", "perfetto", "both"],
                    help="trace export format (both = <base>.jsonl + "
                         "<base>.perfetto.json)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    tracer = None
    if args.trace_out:
        from repro.core.trace import ServeTracer
        tracer = ServeTracer()

    cfg = get_reduced(args.arch)
    policy = serving_policy(args.policy, args.kv_dtype, args.weights_dtype)
    from repro.models import transformer as T
    params = T.init_params(jax.random.PRNGKey(0), cfg, policy)
    sp = SamplingParams()                                 # greedy

    def fresh_engine():
        return InferenceEngine(cfg, params, policy=policy,
                               max_batch=args.max_batch,
                               max_len=args.max_len)

    vocab = min(cfg.vocab_size, 800)
    if args.trace == "overload":
        # focused A/B: the standard bucket/continuous/prefix legs say
        # nothing about overload, so the gate runs only the ladder
        trace, ov_arrivals = build_overload_trace(
            args.requests, args.seed, vocab,
            args.max_len - args.max_new_tokens, args.max_new_tokens)
        report = {
            "arch": args.arch, "requests": args.requests,
            "slots": args.max_batch, "max_new": args.max_new_tokens,
            "trace": args.trace,
            "overload": run_overload_ab(args, fresh_engine, trace, sp,
                                        ov_arrivals, tracer=tracer),
        }
        if tracer is not None:
            finish_tracing(report, tracer, args.trace_out,
                           args.trace_format)
        print(json.dumps(report, indent=2))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=2)
            print(f"wrote {args.out}")
        return

    if args.trace == "shared":
        trace = build_shared_trace(
            args.requests, args.seed, vocab, args.prefix_groups,
            min(args.prefix_len, args.max_len - args.max_new_tokens
                - args.suffix_max),
            args.suffix_max, args.max_new_tokens)
    elif args.trace == "longprompt":
        # context must hold the adversarial prompt plus its budget
        args.max_len = max(args.max_len,
                           args.long_prompt_len + args.max_new_tokens)
        trace, lp_arrivals = build_longprompt_trace(
            args.requests, args.seed, vocab, args.long_prompt_len,
            args.max_new_tokens)
    else:
        trace = build_trace(args.requests, args.seed, vocab,
                            args.max_len - args.max_new_tokens,
                            args.max_new_tokens)
    arrivals = None
    if args.trace == "longprompt":
        arrivals = lp_arrivals
    elif args.poisson:
        rng = np.random.default_rng(args.seed + 1)
        arrivals = list(np.cumsum(
            rng.exponential(1.0 / args.poisson, size=len(trace))))

    # warm up compilation on every path with the full trace shape set so
    # the numbers compare steady-state serving, not tracing time
    eng = fresh_engine()
    run_bucket(eng, copy.deepcopy(trace), sp)
    bucket = run_bucket(eng, copy.deepcopy(trace), sp, arrivals=arrivals)

    eng = fresh_engine()
    run_continuous(eng, copy.deepcopy(trace), sp, page_size=args.page_size, num_pages=args.num_pages,
                   steps_per_sync=args.steps_per_sync)
    cont_reqs = copy.deepcopy(trace)
    cont = run_continuous(eng, cont_reqs, sp,
                          page_size=args.page_size, num_pages=args.num_pages,
                          steps_per_sync=args.steps_per_sync,
                          arrivals=arrivals)

    eng = fresh_engine()
    run_continuous(eng, copy.deepcopy(trace), sp, page_size=args.page_size, num_pages=args.num_pages,
                   steps_per_sync=args.steps_per_sync, prefix_cache=True)
    # measured run starts from a COLD radix trie (warm compilation): all
    # sharing observed below happens within the measured trace itself
    eng.reset_prefix_cache()
    pfx_reqs = copy.deepcopy(trace)
    # the prefix leg is this shape's final measured full-stack run; on
    # longprompt shapes the timeline belongs to the chunked_on A/B leg
    pfx = run_continuous(eng, pfx_reqs, sp, page_size=args.page_size, num_pages=args.num_pages,
                         steps_per_sync=args.steps_per_sync,
                         arrivals=arrivals, prefix_cache=True,
                         tracer=tracer if args.trace != "longprompt"
                         else None)

    identical = all(a.result == b.result
                    for a, b in zip(cont_reqs, pfx_reqs))
    speedup = (cont["tokens_per_s"] / bucket["tokens_per_s"]
               if bucket["tokens_per_s"] else float("nan"))
    pfx_speedup = (pfx["tokens_per_s"] / cont["tokens_per_s"]
                   if cont["tokens_per_s"] else float("nan"))
    report = {
        "arch": args.arch, "requests": args.requests,
        "slots": args.max_batch, "max_new": args.max_new_tokens,
        "trace": args.trace, "poisson_rate": args.poisson,
        "prefix_groups": args.prefix_groups if args.trace == "shared"
        else None,
        "bucket": bucket, "continuous": cont,
        "continuous_prefix": pfx,
        "continuous_speedup_tokens_per_s": round(speedup, 3),
        "prefix_speedup_tokens_per_s": round(pfx_speedup, 3),
        "prefill_tokens_saved": cont["prefill_tokens"]
        - pfx["prefill_tokens"],
        "outputs_identical_prefix_on_off": identical,
    }
    # packed-vs-bucketed execution A/B on the unified scheduler: one
    # (1, T) dispatch per iteration vs decode micro-step + per-chunk
    # dispatches — bit-identical outputs, fewer dispatches, ~zero pad
    report["packed"] = run_packed_ab(args, fresh_engine, trace, sp,
                                     arrivals)
    if args.trace == "longprompt":
        report["longprompt"] = run_longprompt_ab(args, fresh_engine, trace,
                                                 sp, arrivals,
                                                 tracer=tracer)
    if args.spec != "off":
        leg = run_spec_leg(args, fresh_engine, trace, sp, arrivals,
                           cont_reqs)
        report["speculative"] = leg
        # like-for-like: the spec leg runs with the prefix cache on, so
        # its throughput baseline is the prefix leg, not the bare
        # continuous leg (outputs are bit-identical to both regardless)
        report["spec_speedup_tokens_per_s"] = round(
            leg["tokens_per_s"] / pfx["tokens_per_s"], 3) \
            if pfx["tokens_per_s"] else float("nan")
    if args.kv_sweep:
        report["kv_sweep"] = run_kv_sweep(args, cfg, params, policy,
                                          trace, sp, arrivals)
    if args.weight_sweep:
        report["weight_sweep"] = run_weight_sweep(args, cfg, params,
                                                  policy, trace, sp,
                                                  arrivals)
    if tracer is not None:
        finish_tracing(report, tracer, args.trace_out, args.trace_format)
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
