"""On-chip smoke test: the continuous serving path at full width.

Run from the repository root on a machine with one TPU:

    python chip_smoke.py

One process, no children.  Phases:

  a. qwen3-4b, full config, bf16: 8 requests with 100-400-token prompts
     and 32 new tokens, max_batch 4, page_size 16, default token budget.
     This runs chunked prefill, packed iterations and fused decode
     iterations on compiled Pallas kernels.
  b. unimo-text, full config, int8 KV pool and int8 weights: head_dim
     64, the int8-pool paged kernels and ``quant_matmul``.
  c. every main-path kernel at both models' widths against its
     ``kernels/ref.py`` oracle (the oracle at highest matmul precision),
     within ``ORACLE_TOL``.

Phases a and b build and serve through the calls
``python -m repro.launch.serve --continuous --full`` makes.  Each checks
that every request completed with tokens and that the path ran its
kernels with no fallback to the jnp reference.  Each also records the
share of greedy streams equal to a kernels-off engine's; that share is
not asserted, since bf16 margins on random weights flip.

Each phase prints one JSON line.  The script exits non-zero, without a
result line, when JAX finds no TPU or any phase fails.  Otherwise the
last line of stdout is ``{"ok": true, "device": {...}}``.  Tokens per
second printed here are for information, not a benchmark result.
"""
from __future__ import annotations

import copy
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
# attention outputs are bf16 and O(1); quant_matmul is compared after
# dividing by the reference's largest magnitude
ORACLE_TOL = 2e-2

# (arch, kv_dtype, weights_dtype, kernels the path must run)
SERVE_PHASES = {
    "a": ("qwen3-4b", "auto", "auto",
          ("paged_packed_attention", "paged_decode_attention")),
    "b": ("unimo-text", "int8", "int8",
          ("paged_packed_attention_q8", "paged_decode_attention_q8",
           "quant_matmul")),
}
# (Hq, Hkv, head_dim) of the served models
WIDTHS = {"qwen3-4b": (32, 8, 128), "unimo-text": (16, 16, 64)}


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, read from its
    monitoring events, while the clock is entered."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


def make_requests(vocab: int, n: int, prompt_range, max_new: int, seed):
    """Prompts of random ids (above the special tokens) from ``seed``."""
    import numpy as np
    from repro.core.scheduler import Request
    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    return [Request(uid=i, tokens=[int(t) for t in rng.integers(
                4, min(vocab, 32000), size=int(rng.integers(lo, hi + 1)))],
                    max_new_tokens=max_new)
            for i in range(n)]


def serve_phase(arch, kv_dtype, weights_dtype, required, *, reduced=False,
                kernel_mode=None, n_requests=8, prompt_range=(100, 400),
                max_new=32, max_batch=4, max_len=512, page_size=16):
    """Serve ``n_requests`` on ``arch`` from one set of weights: twice
    with kernels on (``kernel_mode`` None follows the backend), cold and
    then with every shape compiled, and once with a fresh kernels-off
    engine for the greedy comparison."""
    import jax
    from repro.configs.registry import get_config, get_reduced
    from repro.core.engine import InferenceEngine
    from repro.core.sampling import SamplingParams
    from repro.kernels import ops as KOPS
    from repro.launch.serve import run_continuous, serving_policy
    from repro.models import transformer as T

    cfg = get_reduced(arch) if reduced else get_config(arch)
    policy = serving_policy("bf16", kv_dtype, weights_dtype)
    reqs = make_requests(cfg.vocab_size, n_requests, prompt_range, max_new,
                         SEED)
    sp = SamplingParams()                                   # greedy
    rec = {"arch": arch, "kv_dtype": kv_dtype,
           "weights_dtype": weights_dtype, "requests": n_requests,
           "prompt_tokens": sum(len(r.tokens) for r in reqs)}

    t0 = time.perf_counter()
    with CompileClock() as clock:
        params = T.init_params(jax.random.PRNGKey(SEED), cfg, policy)
        jax.block_until_ready(params)
    rec["init_s"] = time.perf_counter() - t0
    rec["init_compile_s"] = clock.seconds

    with KOPS.kernel_mode_ctx(kernel_mode):
        rec["kernel_mode"] = KOPS.kernel_mode()
        if rec["kernel_mode"] == "off":
            raise RuntimeError("kernels are off: no TPU backend and no "
                               "kernel mode given")
        KOPS.reset_counts()
        engine = InferenceEngine(cfg, params, policy=policy,
                                 max_batch=max_batch, max_len=max_len)
        with CompileClock() as clock:
            done, metrics, wall = run_continuous(
                engine, copy.deepcopy(reqs), sp, page_size=page_size)
        rec["compile_s"] = clock.seconds
        rec["wall_s"] = wall
        rec["kernels"] = KOPS.kernel_counts()
        rec["fallbacks"] = KOPS.fallback_counts()
        # the same requests again on a cold pool, every shape compiled
        engine.reset_prefix_cache()
        with CompileClock() as clock:
            warm, _, rec["warm_wall_s"] = run_continuous(
                engine, copy.deepcopy(reqs), sp, page_size=page_size)
        rec["warm_compile_s"] = clock.seconds
    rec["generated_tokens"] = metrics.generated_tokens
    rec["tokens_per_s_info"] = metrics.generated_tokens / wall
    rec["warm_tokens_per_s_info"] = \
        metrics.generated_tokens / rec["warm_wall_s"]
    rec["warm_outputs_identical"] = all(
        a.result == b.result for a, b in zip(done, warm))
    rec["mixed_iters"] = metrics.mixed_iters
    rec["decode_steps"] = metrics.steps - metrics.mixed_iters
    rec["weight_bytes"] = metrics.weight_bytes
    rec["kv_pool_bytes"] = metrics.kv_pool_bytes
    del engine
    gc.collect()

    with KOPS.kernel_mode_ctx("off"):
        ref = InferenceEngine(cfg, params, policy=policy,
                              max_batch=max_batch, max_len=max_len)
        ref_done, _, rec["ref_wall_s"] = run_continuous(
            ref, copy.deepcopy(reqs), sp, page_size=page_size)
    del ref, params
    gc.collect()
    jax.clear_caches()

    rec["greedy_match_share"] = sum(
        a.result == b.result for a, b in zip(done, ref_done)) / len(done)
    rec["first_token_match_share"] = sum(
        (a.result or [])[:1] == (b.result or [])[:1]
        for a, b in zip(done, ref_done)) \
        / len(done)
    rec["full_length_share"] = sum(
        len(r.result or []) == max_new for r in done) / len(done)

    bad = [r.uid for r in done if r.outcome is None
           or r.outcome.status != "completed" or not r.result
           or len(r.result) > max_new]
    if bad:
        raise AssertionError(f"requests without a completed result: {bad}")
    if rec["fallbacks"]:
        raise AssertionError(f"kernel fallbacks on the path: "
                             f"{rec['fallbacks']}")
    missing = [k for k in required if not rec["kernels"].get(k)]
    if missing:
        raise AssertionError(f"kernels never dispatched: {missing}")
    return rec


def _paged_inputs(rng, Hq, Hkv, D, pool, windows, *, P=33, page=16,
                  npages=8):
    """Paged pools with one slot per entry of ``windows``: slot b holds
    a random context whose last ``windows[b]`` tokens are its queries."""
    import jax.numpy as jnp
    import numpy as np
    B = len(windows)
    if pool == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, (P, page, Hkv, D)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (P, page, Hkv, D)),
                         jnp.int8)
        scales = {"k_scale": jnp.asarray(rng.uniform(0.005, 0.02,
                                                     (P, page, Hkv)),
                                         jnp.float32),
                  "v_scale": jnp.asarray(rng.uniform(0.005, 0.02,
                                                     (P, page, Hkv)),
                                         jnp.float32)}
    else:
        kp = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(P, page, Hkv, D)), jnp.bfloat16)
        scales = {}
    ppos = np.full((P, page), -1, np.int32)
    bt = np.full((B, npages), -1, np.int32)
    perm = rng.permutation(P - 1)                 # page P-1 is the dump
    lens, nxt = [], 0
    for b, w in enumerate(windows):
        n = int(rng.integers(w, npages * page + 1))
        used = -(-n // page)
        bt[b, :used] = perm[nxt:nxt + used]
        nxt += used
        for t in range(n):
            ppos[bt[b, t // page], t % page] = t
        lens.append(n)
    return kp, vp, scales, jnp.asarray(ppos), jnp.asarray(bt), lens


def oracle_phase(*, widths=WIDTHS, interpret=False):
    """Max normalized error of every main-path kernel against its
    oracle, at each model's widths, with bf16 and int8 pools."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.precision import quantize_weights
    from repro.kernels import decode_attention as DA
    from repro.kernels import flash_attention as FA
    from repro.kernels import quant_matmul as QM
    from repro.kernels import ref as R

    rng = np.random.default_rng(SEED)
    errs = {}

    def check(name, out, ref_fn):
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(ref_fn(), np.float32)
        out = np.asarray(out, np.float32)
        err = float(np.max(np.abs(out - ref)) / max(1.0, np.max(np.abs(ref))))
        errs[name] = err

    for arch, (Hq, Hkv, D) in widths.items():
        scale = D ** -0.5
        kw = dict(window=None, scale=scale)
        for pool in ("bf16", "int8"):
            sfx = "_q8" if pool == "int8" else ""
            # fused decode: one query per slot
            kp, vp, sc, ppos, bt, lens = _paged_inputs(rng, Hq, Hkv, D, pool,
                                                       [1] * 4)
            q = jnp.asarray(rng.normal(size=(4, 1, Hq, D)), jnp.bfloat16)
            qpos = jnp.asarray([[n - 1] for n in lens], jnp.int32)
            pools = ((kp, sc["k_scale"], vp, sc["v_scale"]) if sc
                     else (kp, vp))
            fn = DA.paged_decode_attention_q8 if sc \
                else DA.paged_decode_attention
            check(f"paged_decode_attention{sfx} {arch}",
                  fn(q, *pools, ppos, bt, qpos, interpret=interpret, **kw),
                  lambda: R.paged_decode_attention_ref(
                      q, kp, vp, ppos, bt, qpos, **sc, **kw))
            # mixed: per-slot query windows, padded with -1 positions
            W, wins = 8, [1, 3, 8, 5]
            kp, vp, sc, ppos, bt, lens = _paged_inputs(rng, Hq, Hkv, D, pool,
                                                       wins)
            q = jnp.asarray(rng.normal(size=(4, W, Hq, D)), jnp.bfloat16)
            qp = np.full((4, W), -1, np.int32)
            for b, (w, n) in enumerate(zip(wins, lens)):
                qp[b, :w] = np.arange(n - w, n)
            qpos = jnp.asarray(qp)
            pools = ((kp, sc["k_scale"], vp, sc["v_scale"]) if sc
                     else (kp, vp))
            fn = DA.paged_mixed_attention_q8 if sc \
                else DA.paged_mixed_attention
            check(f"paged_mixed_attention{sfx} {arch}",
                  fn(q, *pools, ppos, bt, qpos, interpret=interpret, **kw),
                  lambda: R.paged_mixed_attention_ref(
                      q, kp, vp, ppos, bt, qpos, **sc, **kw))
            # packed: two decode lanes and two chunks in one stream
            wins = [1, 1, 37, 20]
            kp, vp, sc, ppos, bt, lens = _paged_inputs(rng, Hq, Hkv, D, pool,
                                                       wins)
            T_ = 64
            sid = np.full(T_, -1, np.int32)
            qp = np.full(T_, -1, np.int32)
            starts, t = [], 0
            for b, (w, n) in enumerate(zip(wins, lens)):
                starts.append(t)
                sid[t:t + w] = b
                qp[t:t + w] = np.arange(n - w, n)
                t += w
            meta = jnp.asarray(DA.packed_meta_table(
                np.asarray(starts), np.asarray(wins), np.arange(4), T_,
                T_ // DA.PACKED_BLOCK_Q + 4))
            q = jnp.asarray(rng.normal(size=(1, T_, Hq, D)), jnp.bfloat16)
            qpos = jnp.asarray(qp[None])
            pools = ((kp, sc["k_scale"], vp, sc["v_scale"]) if sc
                     else (kp, vp))
            fn = DA.paged_packed_attention_q8 if sc \
                else DA.paged_packed_attention
            check(f"paged_packed_attention{sfx} {arch}",
                  fn(q, *pools, ppos, bt, qpos, meta, interpret=interpret,
                     **kw),
                  lambda: R.paged_packed_attention_ref(
                      q, kp, vp, ppos, bt, qpos, jnp.asarray(sid), **sc,
                      **kw))
        # prefill flash attention on a right-padded batch
        S = 256
        q = jnp.asarray(rng.normal(size=(2, S, Hq, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(2, S, Hkv, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(2, S, Hkv, D)), jnp.bfloat16)
        pos = np.tile(np.arange(S, dtype=np.int32), (2, 1))
        pos[1, 200:] = -1
        pos = jnp.asarray(pos)
        check(f"flash_attention {arch}",
              FA.flash_attention(q, k, v, pos, pos, interpret=interpret,
                                 **kw),
              lambda: R.flash_attention_ref(q, k, v, pos, pos, **kw))
        # int8 weight matmul: the FFN input projection of the model
        d_model, d_ff = {"qwen3-4b": (2560, 9728),
                         "unimo-text": (1024, 4096)}.get(arch, (D * 4, D * 8))
        w = quantize_weights(jnp.asarray(rng.normal(size=(d_model, d_ff)),
                                         jnp.float32) * d_model ** -0.5)
        x = jnp.asarray(rng.normal(size=(24, d_model)), jnp.bfloat16)
        check(f"quant_matmul {arch}",
              QM.quant_matmul(x, w["q"], w["s"], interpret=interpret),
              lambda: R.quant_matmul_ref(x, w["q"], w["s"]))
    worst = max(errs, key=errs.get)
    rec = {"oracle_tol": ORACLE_TOL, "oracle_errors": errs,
           "oracle_max_error": errs[worst], "oracle_worst": worst}
    if errs[worst] > ORACLE_TOL:
        raise AssertionError(f"{worst}: normalized error {errs[worst]} > "
                             f"{ORACLE_TOL}")
    return rec


def main() -> int:
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']}); "
              f"nothing was run", file=sys.stderr)
        return 1
    from repro.launch.serve import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(json.dumps({"device": dev, "compile_cache": cache_dir}), flush=True)

    phases = [(name, serve_phase, spec) for name, spec in
              SERVE_PHASES.items()] + [("c", oracle_phase, ())]
    failed = []
    for name, fn, args in phases:
        t0 = time.perf_counter()
        with CompileClock() as clock:
            try:
                rec = {"phase": name, "ok": True, **fn(*args)}
            except Exception as e:          # report, run the next phase
                traceback.print_exc()
                failed.append(name)
                rec = {"phase": name, "ok": False,
                       "error": f"{type(e).__name__}: {e}"[:2000]}
        rec.update(device=dev, phase_wall_s=time.perf_counter() - t0,
                   phase_compile_s=clock.seconds)
        print(json.dumps(rec), flush=True)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
