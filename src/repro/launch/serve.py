"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the paper's full optimized stack — KV-cache engine, half-precision,
optional embedding pruning, dynamic batching and the staged pipeline — over
a synthetic request stream, printing latency/throughput stats.

:func:`serving_policy`, ``T.init_params``, ``InferenceEngine`` and
:func:`run_continuous` are the calls ``--continuous`` makes;
``chip_smoke.py`` makes the same ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Optional

import jax

from repro.configs.registry import get_config, get_reduced, list_archs
from repro.core import pruning as PR
from repro.core.engine import InferenceEngine
from repro.core.pipeline import run_pipelined, run_sequential
from repro.core.precision import Policy, get_policy
from repro.core.sampling import SamplingParams
from repro.core.tokenizer import FastTokenizer
from repro.data.pipeline import synthetic_corpus
from repro.models import transformer as T

# compile-cache home when JAX_COMPILATION_CACHE_DIR is unset: one fixed
# path in the checkout (the path is part of the cache key), gitignored
COMPILE_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry point; call
    before the first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already caches there and nothing else is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def serving_policy(name: str = "bf16", kv_dtype: str = "auto",
                   weights_dtype: str = "auto") -> Policy:
    """The precision policy plus the KV-pool and weight storage axes."""
    policy = get_policy(name)
    if kv_dtype != "auto":
        policy = dataclasses.replace(policy, kv_dtype=kv_dtype)
    if weights_dtype != "auto":
        policy = dataclasses.replace(policy, weights_dtype=weights_dtype)
    return policy


def run_continuous(engine: InferenceEngine, reqs, sp: SamplingParams, *,
                   page_size: int = 16, steps_per_sync: int = 4,
                   max_batched_tokens: Optional[int] = None, **kw):
    """``engine.serve_continuous`` timed on the host clock; returns
    (requests, ServeMetrics, wall seconds).  The wall time includes any
    compilation the first call of each shape pays."""
    t0 = time.perf_counter()
    done, metrics = engine.serve_continuous(
        reqs, sp, page_size=page_size, steps_per_sync=steps_per_sync,
        max_batched_tokens=max_batched_tokens, **kw)
    return done, metrics, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="unimo-text", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--policy", default="bf16",
                    choices=["fp32", "bf16", "fp16"])
    ap.add_argument("--kv-dtype", default="auto",
                    choices=["auto", "bf16", "fp16", "int8"],
                    help="KV-cache storage dtype (continuous paged pool): "
                         "auto = compute dtype; int8 stores quantized "
                         "pages + per-entry scales, halving KV bytes per "
                         "token (dense-state layer families keep full "
                         "precision)")
    ap.add_argument("--weights-dtype", default="auto",
                    choices=["auto", "bf16", "fp16", "int8"],
                    help="serve-path weight storage dtype: auto = compute "
                         "dtype; int8 quantizes dense matmul weights "
                         "(attention qkv/out, dense FFN, unembed) to int8 "
                         "codes + per-output-channel scales at load, "
                         "roughly halving bf16 weight bytes read per "
                         "decode step (fused-dequant Pallas matmul on "
                         "TPU; exact jnp fallback elsewhere)")
    ap.add_argument("--no-kv-cache", action="store_true",
                    help="paper baseline mode")
    ap.add_argument("--no-pipeline", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a paged KV cache "
                         "instead of bucket batches")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--steps-per-sync", type=int, default=4)
    ap.add_argument("--max-batched-tokens", type=int, default=None,
                    help="per-iteration token budget of the unified "
                         "scheduler: decode tokens (one per live slot) "
                         "plus chunked-prefill tokens never exceed it, "
                         "so a long prompt cannot stall decode "
                         "(default: engine default, 256)")
    ap.add_argument("--chunked-prefill", default="auto",
                    choices=["auto", "on", "off"],
                    help="unified token-budget iteration with chunked "
                         "prefill (auto = on for layer families that "
                         "support it; off = bucketed whole-prompt "
                         "admission)")
    ap.add_argument("--prefix-cache", default="auto",
                    choices=["auto", "on", "off"],
                    help="radix prefix cache on the continuous path: "
                         "share identical prompt-prefix KV pages across "
                         "requests (auto = on when every layer family "
                         "supports sharing)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend one shared N-token system prompt to "
                         "every request (demonstrates the prefix cache)")
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "draft"],
                    help="speculative decoding on the continuous path: "
                         "ngram = prompt-lookup drafter (no weights), "
                         "draft = draft-model drafter (self-drafting "
                         "demo).  Distribution preserving; greedy "
                         "streams are bit-identical to --spec off")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per slot per verify step")
    ap.add_argument("--preemption", default="off",
                    choices=["off", "lru", "priority"],
                    help="overload survivability on the continuous path: "
                         "when admission fails for pages while a slot is "
                         "free, evict a decoding victim (lru = most "
                         "recently admitted, priority = lowest "
                         "Request.priority), offload its KV to the host "
                         "tier and re-queue it — generated tokens "
                         "preserved, greedy streams bit-identical")
    ap.add_argument("--host-kv-bytes", type=int, default=None,
                    help="host-memory KV tier capacity in bytes: holds "
                         "preempted slots' page snapshots and spilled "
                         "prefix-cache leaves (default: no host tier; "
                         "preemption then resumes by re-prefilling)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds on the serve "
                         "clock: requests still queued past it are "
                         "cancelled with a timed_out outcome")
    ap.add_argument("--debug-audit", action="store_true",
                    help="audit allocator refcounts + host-tier byte "
                         "accounting every serve iteration")
    ap.add_argument("--prune-coverage", type=float, default=None,
                    help="e.g. 0.999 -> prune vocab to that corpus coverage")
    ap.add_argument("--prune-vocab", type=int, default=None, metavar="N",
                    help="prune the embedding/unembedding to the N most "
                         "frequent corpus tokens (hard budget; mutually "
                         "exclusive with --prune-coverage).  The engine "
                         "remaps prompts at admission and unmaps results "
                         "at emit, so callers see original token ids")
    ap.add_argument("--packed", default="auto",
                    choices=["auto", "on", "off"],
                    help="token-packed ragged execution of mixed "
                         "iterations: the whole iteration (decode tokens "
                         "+ prefill chunks) runs as ONE (1, T) dispatch "
                         "(auto = on whenever chunked prefill is on; "
                         "off = legacy decode-micro-step + per-chunk "
                         "dispatches)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a structured serve-loop trace of the "
                         "continuous run: per-iteration timeline, request "
                         "lifecycle spans and scheduler decisions "
                         "(continuous mode only)")
    ap.add_argument("--trace-format", default="jsonl",
                    choices=["jsonl", "perfetto", "both"],
                    help="trace export format: jsonl = one schema-"
                         "versioned event per line; perfetto = Chrome "
                         "trace-event JSON loadable at ui.perfetto.dev; "
                         "both = write <PATH>.jsonl + <PATH>.perfetto.json")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the COMPLETE ServeMetrics — every raw "
                         "counter plus every derived property (host_frac, "
                         "dispatches_per_iter, padded_token_frac, "
                         "prefix_hit_rate, acceptance_rate, ...) — as one "
                         "JSON object (continuous mode only)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args()
    if (args.trace_out or args.metrics_json) and not args.continuous:
        raise SystemExit("--trace-out/--metrics-json require --continuous")

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if cfg.num_codebooks or cfg.num_prefix_embeds:
        raise SystemExit("serve.py drives text archs; audio/VLM backbones "
                         "are exercised via dryrun + smoke tests")
    enable_compile_cache()
    policy = serving_policy(args.policy, args.kv_dtype, args.weights_dtype)
    params = T.init_params(jax.random.PRNGKey(0), cfg, policy)

    corpus = synthetic_corpus(600)
    tok = FastTokenizer.train(corpus, min(cfg.vocab_size, 4000))
    texts = synthetic_corpus(args.requests, seed=7, min_len=4, max_len=40)

    maps = None
    if args.prune_coverage and args.prune_vocab:
        raise SystemExit("--prune-coverage and --prune-vocab are mutually "
                         "exclusive")
    if args.prune_coverage or args.prune_vocab:
        freqs = tok.count_frequencies(corpus)
        params, cfg, maps = PR.prune_model(params, cfg, dict(freqs),
                                           coverage=args.prune_coverage,
                                           max_vocab=args.prune_vocab)
        print(f"pruned vocab -> {cfg.vocab_size}")

    engine = InferenceEngine(cfg, params, policy=policy,
                             max_batch=args.max_batch, max_len=args.max_len,
                             use_kv_cache=not args.no_kv_cache,
                             prune_maps=maps)
    del params                 # the engine holds the only served copy
    sp = SamplingParams(temperature=args.temperature,
                        top_k=40 if args.temperature > 0 else 0)

    if args.continuous:
        from repro.core.scheduler import Request
        shared = tok.encode(" ".join(synthetic_corpus(
            3, seed=11)))[:args.shared_prefix] if args.shared_prefix else []
        reqs = [Request(uid=i, tokens=shared + tok.encode(t),
                        max_new_tokens=args.max_new_tokens,
                        deadline=args.deadline)
                for i, t in enumerate(texts)]
        prefix = {"auto": None, "on": True, "off": False}[args.prefix_cache]
        chunked = {"auto": None, "on": True,
                   "off": False}[args.chunked_prefill]
        packed = {"auto": None, "on": True, "off": False}[args.packed]
        spec = None
        if args.spec != "off":
            from repro.core.speculative import SpecConfig
            spec = SpecConfig(k=args.spec_k,
                              drafter=("ngram" if args.spec == "ngram"
                                       else "draft_model"))
        tracer = None
        if args.trace_out:
            from repro.core.trace import ServeTracer
            tracer = ServeTracer()
        done, metrics, dt = run_continuous(
            engine, reqs, sp, page_size=args.page_size,
            steps_per_sync=args.steps_per_sync, prefix_cache=prefix,
            spec=spec, max_batched_tokens=args.max_batched_tokens,
            chunked_prefill=chunked, packed=packed,
            preemption=args.preemption,
            host_kv_bytes=args.host_kv_bytes,
            debug_audit=args.debug_audit, trace=tracer)
        for r in done[:3]:
            print(f"[{r.uid}] {tok.decode(r.result or [])[:70]!r}")
        if tracer is not None:
            from repro.core.trace import export as trace_export
            for p in trace_export(tracer, args.trace_out,
                                  args.trace_format):
                print(f"trace: {p} ({len(tracer.events)} events, "
                      f"{tracer.dropped} dropped)")
        if args.metrics_json:
            with open(args.metrics_json, "w") as f:
                json.dump({"requests": len(done), "wall_s": round(dt, 3),
                           "tokens_per_s": round(
                               metrics.generated_tokens / dt, 1),
                           "mode": "continuous-paged",
                           **metrics.to_dict()}, f, indent=1)
            print(f"metrics: {args.metrics_json}")
        print(json.dumps({
            "requests": len(done), "wall_s": round(dt, 3),
            "generated_tokens": metrics.generated_tokens,
            "tokens_per_s": round(metrics.generated_tokens / dt, 1),
            "p50_latency_s": round(metrics.percentile_latency(50), 3),
            "p99_latency_s": round(metrics.percentile_latency(99), 3),
            "ttft_p50_s": round(metrics.ttft_p50, 4),
            "ttft_p99_s": round(metrics.ttft_p99, 4),
            "itl_p50_s": round(metrics.itl_p50, 4),
            "itl_p99_s": round(metrics.itl_p99, 4),
            "scheduler": metrics.scheduler,
            "max_batched_tokens": metrics.max_batched_tokens,
            "prefill_chunks": metrics.prefill_chunks,
            "decode_idle_frac": round(metrics.decode_idle_frac, 3),
            "prefill_pad_frac": round(metrics.prefill_pad_frac, 3),
            "dispatches_per_iter": round(metrics.dispatches_per_iter, 3),
            "padded_token_frac": round(metrics.padded_token_frac, 3),
            "host_frac": round(metrics.host_frac, 3),
            "host_s": round(metrics.host_s, 3),
            "device_s": round(metrics.device_s, 3),
            "prefix_hit_rate": round(metrics.prefix_hit_rate, 3),
            "prefix_matched_tokens": metrics.prefix_matched_tokens,
            "pages_shared": metrics.pages_shared,
            "cow_copies": metrics.cow_copies,
            "kv_dtype": metrics.kv_dtype,
            "kv_pool_bytes": metrics.kv_pool_bytes,
            "kv_bytes_per_token": round(metrics.kv_bytes_per_token, 1),
            "weight_dtype": metrics.weight_dtype,
            "weight_bytes": metrics.weight_bytes,
            "weight_bytes_saved": metrics.weight_bytes_saved,
            "host_syncs": metrics.host_syncs,
            "peak_pages_in_use": metrics.peak_pages_in_use,
            "admission_stalls": metrics.admission_stalls,
            "preemptions": metrics.preemptions,
            "resumed": metrics.resumed,
            "offloaded_pages": metrics.offloaded_pages,
            "restored_pages": metrics.restored_pages,
            "host_bytes_peak": metrics.host_bytes_peak,
            "timed_out": metrics.timed_out,
            "deadline_misses": metrics.deadline_misses,
            "outcomes": dict(sorted(metrics.outcome_counts.items())),
            "spec_mode": metrics.spec_mode,
            "acceptance_rate": round(metrics.acceptance_rate, 3),
            "tokens_per_forward": round(metrics.tokens_per_forward, 3),
            "mode": "continuous-paged"}))
        return

    runner = run_sequential if args.no_pipeline else run_pipelined
    t0 = time.time()
    results = runner(texts, tok, engine, max_new_tokens=args.max_new_tokens,
                     sp=sp, max_batch=args.max_batch)
    dt = time.time() - t0

    for r in results[:3]:
        print(f"[{r.uid}] {r.text[:70]!r}")
    st = engine.stats
    print(json.dumps({
        "requests": len(results), "wall_s": round(dt, 3),
        "requests_per_s": round(len(results) / dt, 3),
        "generated_tokens": st.generated_tokens,
        "decode_tok_per_s": round(
            st.generated_tokens / st.decode_s, 1) if st.decode_s else None,
        "prefill_s": round(st.prefill_s, 3),
        "mode": "baseline-nocache" if args.no_kv_cache else "kv-cache",
        "pipelined": not args.no_pipeline}))


if __name__ == "__main__":
    main()
