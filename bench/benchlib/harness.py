"""One run of one cell: set-up, the measured window, the readings, the check.

The program is driven only through its public serving calls: the
configuration it registers, ``InferenceEngine`` with the weights this
benchmark makes, ``serve_continuous`` with a ``ServeTracer`` attached,
and the kernel dispatch counters.  The window is one
``serve_continuous`` call with the engine's default serving options
(chunked prefill through the packed forward, fused decode, prefix
cache on where the model allows it).
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from benchlib import arith, check, readers
from benchlib import profile as P
from benchlib.cell import REPO_DIR, Cell
from benchlib.readers import STEPS_PER_SYNC, nearest_rank
from benchlib.traffic import generate, rng_for


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileLog:
    """JAX's compile events while entered: seconds tracing, lowering and
    in the backend (a compile, or a load from the persistent cache), and
    how many programs reached the backend, hit or missed the cache."""

    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
                 "/jax/core/compile/backend_compile_duration": "backend_s"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        self.totals = {k: 0.0 for k in self.DURATIONS.values()}
        self.totals.update({k: 0 for k in self.COUNTS.values()})
        self.totals["programs"] = 0

    def _duration(self, event, seconds, **_):
        key = self.DURATIONS.get(event)
        if key is not None:
            self.totals[key] += seconds
            if key == "backend_s":
                self.totals["programs"] += 1

    def _event(self, event, **_):
        key = self.COUNTS.get(event)
        if key is not None:
            self.totals[key] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


@dataclass
class Run:
    """What one run measured; the metric readers read it."""
    cell: Cell
    dims: dict
    engine: dict
    items: list
    requests: list
    metrics: object                 # the program's ServeMetrics
    tracer_events: List[dict]
    wall_s: float
    setup_s: float
    peak: dict
    origin_pc: float = 0.0          # perf_counter when the serve clock began
    reduction: Optional[P.Reduction] = None

    def events(self, kind: str) -> List[dict]:
        return [e for e in self.tracer_events if e["kind"] == kind]


def log(obj, device: dict) -> None:
    """One earlier line of the run's output; each names the device."""
    print(json.dumps({**obj, "device": device}), flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is
    cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO_DIR, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_record(cell: Cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell.chips):
        raise NoDevice(f"{cell.name} needs {cell.chips} TPU chip(s); JAX "
                       f"found {len(devs)} {devs[0].platform} device(s)")
    return devs[:cell.chips], {"platform": devs[0].platform,
                               "kind": devs[0].device_kind,
                               "count": cell.chips}


def program_config(cell: Cell, dims: dict, reduced: bool):
    """The program's registered configuration, checked against the
    sizes this cell states (a mismatch would run another model)."""
    from repro.configs.registry import get_config, get_reduced
    arch = cell.config["program"]["arch"]
    cfg = get_reduced(arch) if reduced else get_config(arch)
    have = dict(d=cfg.d_model, L=cfg.num_layers, Hq=cfg.num_heads,
                Hkv=cfg.num_kv_heads, D=cfg.resolved_head_dim, ff=cfg.d_ff,
                V=cfg.vocab_size)
    wrong = {k: (v, dims[k]) for k, v in have.items() if dims[k] != v}
    if wrong:
        raise ValueError(f"program config {arch} differs from "
                         f"bench/configs/{cell.config_name}.json: {wrong}")
    return cfg


def weights_key(seed: int):
    import jax
    bits = np.random.SeedSequence([seed, *b"weights"]).generate_state(2)
    return jax.random.wrap_key_data(bits.astype(np.uint32))


def make_weights(cell: Cell, model_json: dict, seed: int):
    import functools
    import jax
    fn = jax.jit(functools.partial(cell.model.make_weights, c=model_json))
    return jax.block_until_ready(fn(weights_key(seed)))


def warm_up(engine, sp, ecfg: dict, vocab: int) -> None:
    """Compile every program the window can use: one packed forward per
    stream width the token budget allows (a lone prompt of exactly that
    width), and the fused decode step.  Each width gets a serve call of
    its own, so no two prompts share an iteration."""
    from repro.core.engine import packed_width_buckets
    from repro.core.scheduler import Request
    rng = rng_for(0, "warm-up")
    for w in packed_width_buckets(ecfg["max_batched_tokens"]):
        req = Request(uid=0, tokens=[int(t) for t in rng.integers(4, vocab, w)],
                      max_new_tokens=2)
        engine.serve_continuous(
            [req], sp, page_size=ecfg["page_size"],
            num_pages=ecfg["num_pages"], slots=ecfg["slots"],
            steps_per_sync=STEPS_PER_SYNC,
            max_batched_tokens=ecfg["max_batched_tokens"])
    engine.reset_prefix_cache()


def to_requests(items):
    from repro.core.scheduler import Request
    return [Request(uid=it.uid, tokens=[int(t) for t in it.prompt],
                    max_new_tokens=it.max_new, deadline=it.deadline)
            for it in items]


def window(engine, cell: Cell, ecfg: dict, sp, seconds: float, seed: int,
           dims: dict, peak: dict, rate, stretch, label: dict,
           device: dict) -> Run:
    """Serve one window of the cell's traffic and log what it did: the
    requests and their outcomes, how late the serve loop took up the
    arrivals, compiles inside the window (the bar is zero), and whether
    the queue grew (requests due in the window still queued at its end;
    queue wait of the first and last third of the arrivals)."""
    from repro.core.trace import ServeTracer
    items = generate(cell.mix, seconds, seed, dims["V"], rate=rate)
    reqs = to_requests(items)
    tracer = ServeTracer()
    with CompileLog() as wlog:
        if stretch is not None:
            stretch.begin()
        t0 = time.perf_counter()
        _, metrics = engine.serve_continuous(
            reqs, sp, page_size=ecfg["page_size"], num_pages=ecfg["num_pages"],
            slots=ecfg["slots"], steps_per_sync=STEPS_PER_SYNC,
            arrivals=[it.arrival for it in items],
            max_batched_tokens=ecfg["max_batched_tokens"], trace=tracer)
        wall = time.perf_counter() - t0
        origin_pc = tracer.clock() - tracer.now()
        if stretch is not None:
            stretch.join()
    run = Run(cell=cell, dims=dims, engine=ecfg, items=items, requests=reqs,
              metrics=metrics, tracer_events=list(tracer.events),
              wall_s=wall, setup_s=0.0, peak=peak,
              origin_pc=origin_pc)
    statuses = {}
    for r in reqs:
        s = r.outcome.status if r.outcome is not None else "none"
        statuses[s] = statuses.get(s, 0) + 1
    due = {it.uid: it.arrival for it in items}
    lag = [e["t"] - due[e["uid"]] for e in run.events("enqueue")]
    admit = {e["uid"]: e["t"] for e in run.events("admit")}
    third = max(1, len(items) // 3)
    wait = [admit.get(it.uid, float("inf")) - it.arrival for it in items]
    log({"window": {
        **label, "requests": len(items),
        "cancelled_in_queue": sum(1 for r in reqs if r.uid not in admit),
        "statuses": statuses, "serve_wall_s": wall,
        "generated_tokens": metrics.generated_tokens,
        "tokens_per_s": metrics.generated_tokens / wall,
        "ttft_p95_s": nearest_rank([t for t in readers.ttft(run)], 0.95),
        "itl_p95_s": nearest_rank(metrics.itl_s, 0.95),
        "queued_at_window_end": sum(
            1 for it in items if it.arrival <= seconds
            and admit.get(it.uid, float("inf")) > seconds),
        "queue_wait_p50_first_third_s": nearest_rank(wait[:third], 0.5),
        "queue_wait_p50_last_third_s": nearest_rank(wait[-third:], 0.5),
        "arrival_lag_p50_s": nearest_rank(lag, 0.5),
        "arrival_lag_max_s": max(lag) if lag else None,
        "compiles_in_window": wlog.totals["programs"],
        "tracer_dropped": tracer.dropped}}, device)
    return run


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, reduced: bool = False,
             control: bool = False,
             sweep: Optional[List[float]] = None,
             model_json: Optional[dict] = None,
             engine_json: Optional[dict] = None,
             dump: Optional[str] = None) -> dict:
    """One run; returns the result line.  ``reduced``, ``model_json`` and
    ``engine_json`` let the tests run a cell at a size a CPU holds;
    ``control`` puts the correctness control in the program's place, so
    that its gap, not the program's, decides ``correct``;
    each Poisson rate of ``sweep`` gets a window of its own before the
    measured one (the knee); ``dump``
    names a directory for the stretch's device and tracer events."""
    import jax
    devices, dev_rec = device_record(cell, require_tpu)
    peak = arith.peaks(dev_rec["kind"]) if require_tpu else {}
    cache_dir = enable_compile_cache()
    model_json = model_json or cell.config
    ecfg = dict(engine_json or cell.config["engine"])
    dims = cell.model.dims(model_json)
    setup = {}

    from repro.core.engine import InferenceEngine
    from repro.core.sampling import SamplingParams
    from repro.kernels import ops as KOPS
    from repro.launch.serve import serving_policy

    prog = cell.config["program"]
    policy =serving_policy(prog["policy"], prog["kv_dtype"],
                            prog["weights_dtype"])
    sp = SamplingParams()                                   # greedy
    with CompileLog() as clog:
        t0 = time.perf_counter()
        mcfg = program_config(cell, dims, reduced)
        params = make_weights(cell, model_json, seed)
        setup["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        engine = InferenceEngine(mcfg, params, policy=policy,
                                 max_batch=ecfg["slots"],
                                 max_len=ecfg["max_len"])
        del params
        setup["engine_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        KOPS.reset_counts()
        warm_up(engine, sp, ecfg, dims["V"])
        setup["warm_serve_s"] = time.perf_counter() - t0
    setup.update(clog.totals)
    setup["kernels"] = KOPS.kernel_counts()
    setup["fallbacks"] = KOPS.fallback_counts()

    setup_s = time.perf_counter() - t_start
    setup["total_s"] = setup_s
    mem = devices[0].memory_stats() or {}
    log({"setup": setup, "compile_cache": cache_dir,
         "pool_pages": ecfg["num_pages"],
         "memory": {k: mem[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                        "bytes_limit") if k in mem}},
        dev_rec)
    for r in sweep or ():
        window(engine, cell, ecfg, sp, seconds, seed, dims, peak, r,
               None, {"sweep_rate": r}, dev_rec)
        engine.reset_prefix_cache()
    stretch = None
    if trace:
        # long enough to hold fused decode calls even where arrivals
        # make most iterations packed ones
        stretch = P.Stretch(tempfile.mkdtemp(prefix="bench_trace_"),
                            start_after=seconds / 3,
                            length=min(10.0, seconds / 3))
    run = window(engine, cell, ecfg, sp, seconds, seed, dims, peak, None,
                 stretch, {}, dev_rec)
    run.setup_s = setup_s
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    dev_rec["memory_peak_bytes"] = int(max(mem))
    if stretch is not None:
        events = P.device_events(stretch.xplane(), stretch.anchor_pc,
                                 run.origin_pc,
                                 [d.id for d in devices])
        w0 = stretch.anchor_pc - run.origin_pc
        w1 = stretch.t_end - run.origin_pc
        run.reduction = P.reduce(events, w0, w1)
        if dump:
            os.makedirs(dump, exist_ok=True)
            with open(os.path.join(dump, cell.name + ".json"), "w") as f:
                json.dump({"w0": w0, "w1": w1, "events": events,
                           "tracer": [e for e in run.tracer_events
                                      if w0 - 1 <= e["t"] <= w1 + 1]}, f)
        shutil.rmtree(stretch.out_dir, ignore_errors=True)
    reqs, metrics, items = run.requests, run.metrics, run.items
    started = {e["uid"] for e in run.events("admit")}

    names = cell.per_layer if trace else cell.end_to_end
    out_metrics = {}
    for m in names:
        v = m.read(run)
        if v is not None:
            out_metrics[m.name] = {"value": v, "unit": m.unit}

    breakdown = None
    if run.reduction is not None:
        dev_rec["busy_s"] = run.reduction.busy_s
        dev_rec["window_s"] = run.reduction.window_s
        breakdown = P.breakdown(run.reduction, run.tracer_events)

    # the check: the program's state goes first, then the reference
    attempted = len(started) if cell.mix["arrival"] == "backlog" \
        else len(reqs)
    failed = attempted - len(check.finished(reqs))
    sample = check.sample(reqs, seed, int(cell.check["sample_requests"]))
    del engine, metrics, run
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    params = make_weights(cell, model_json, seed)
    bits = cell.config["control"]["reference_bits"] if control else None
    g = check.gaps(cell.model, model_json, params, sample,
                   control_bits=tuple(bits) if bits else None)
    del params
    limit = float(cell.check["logit_gap_max"])
    rec = {"reference_s": time.perf_counter() - t0, "sampled": len(sample),
           "program_gap": check.summary(g["program"])}
    checks = [("logit_gap_max", rec["program_gap"]["max"], limit),
              ("failed_requests", failed, 0)]
    if bits:
        # the control stands in the program's place: its gap is judged
        rec["control_gap"] = check.summary(g["control"])
        checks[0] = ("control_gap_max", rec["control_gap"]["max"], limit)
    log({"check": rec}, dev_rec)

    result = {"correct": check.verdict(checks), "attempted": attempted,
              "failed": failed, "metrics": out_metrics, "device": dev_rec}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    on = f"{dev_rec['platform']} {dev_rec['kind']} x{dev_rec['count']}"
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim}) on {on}", file=sys.stderr)
    return result
