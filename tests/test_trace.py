"""Serve-loop tracing: determinism, schema, zero-perturbation, exporters.

Contracts under test:

  * a ``ServeTracer`` driven by a fake monotonic clock produces
    byte-identical JSONL across two fresh serve runs — every serve-loop
    timestamp flows through the injected clock, and the exporter writes
    canonical (sorted-key, fixed-separator) JSON;
  * every event the engine/scheduler/prefix-cache/host-tier emits
    validates against ``EVENT_SCHEMAS``, and ``validate_event`` rejects
    unknown kinds, missing/extra fields and type mismatches;
  * tracing is observation only: greedy outputs are bit-identical with
    the tracer on vs. off across plain, shared-prefix, int8,
    speculative and preemption/resume serving;
  * trace-derived host/device totals reconcile with ``ServeMetrics``:
    device span time matches ``device_s`` exactly (same timer reads),
    iteration ``host_s`` is bounded by the metrics' host share;
  * the Perfetto exporter emits structurally sound Chrome trace-event
    JSON (balanced B/E per track, counter samples, named threads);
  * ``ServeMetrics.percentile`` matches numpy on non-empty input and is
    zero on empty; ``to_dict`` carries every derived property;
  * ``bench_diff`` passes a baseline against itself, fails on
    regressions and invariant breaks, and skips baseline-relative
    checks on config mismatch.
"""
import copy
import io
import json

import jax
import numpy as np
import pytest

from benchmarks import bench_diff
from repro.configs.registry import get_reduced
from repro.core.continuous import ServeMetrics
from repro.core.engine import InferenceEngine
from repro.core.precision import FP32
from repro.core.scheduler import Request
from repro.core import trace as TR
from repro.core.trace import (EVENT_SCHEMAS, ServeTracer, to_perfetto_dict,
                              validate_event, validate_events)
from repro.models import transformer as T


class FakeClock:
    """Deterministic monotonic clock: advances a fixed step per read."""

    def __init__(self, step=0.001):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def _requests(rng, cfg, lens_new, prefix=None):
    prefix = prefix or []
    return [Request(uid=i,
                    tokens=[2] + prefix + list(map(int, rng.integers(
                        4, min(cfg.vocab_size, 400), size=ln))),
                    max_new_tokens=mn)
            for i, (ln, mn) in enumerate(lens_new)]


def _serve(eng, reqs, **kw):
    done, m = eng.serve_continuous(copy.deepcopy(reqs), page_size=8,
                                   max_batched_tokens=16,
                                   chunked_prefill=True, **kw)
    return {r.uid: r.result for r in done}, m


def _engine(cfg, params, policy=FP32):
    return InferenceEngine(cfg, params, policy=policy, max_len=64,
                           max_batch=3)


@pytest.fixture(scope="module")
def model():
    cfg = get_reduced("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# Determinism: fake clock -> byte-identical JSONL
# ---------------------------------------------------------------------------


def _traced_run(cfg, params, reqs, **kw):
    tr = ServeTracer(clock=FakeClock())
    done, m = _serve(_engine(cfg, params), reqs, trace=tr, **kw)
    return tr, done, m


def test_fake_clock_jsonl_byte_identical(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(14, 4), (22, 4), (9, 3)])
    bufs = []
    for _ in range(2):
        tr, _, _ = _traced_run(cfg, params, reqs)
        buf = io.StringIO()
        tr.to_jsonl(buf)
        bufs.append(buf.getvalue())
    assert bufs[0] == bufs[1]
    lines = bufs[0].splitlines()
    assert json.loads(lines[0])["kind"] == "trace_header"
    kinds = {json.loads(l)["kind"] for l in lines[1:]}
    assert {"enqueue", "admit", "prefill_chunk", "first_token", "span",
            "iteration", "retire"} <= kinds


def test_timestamps_serve_relative_and_monotone_origin(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(11, 3)])
    tr, _, _ = _traced_run(cfg, params, reqs)
    ts = [e["t"] for e in tr.events]
    assert ts and min(ts) >= 0.0
    # iteration records carry a strictly increasing index from 0
    its = [e["iter"] for e in tr.iter_events("iteration")]
    assert its == list(range(len(its)))


# ---------------------------------------------------------------------------
# Schema: every emitted event validates; validator rejects bad events
# ---------------------------------------------------------------------------


def test_emitted_events_schema_valid_plain(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(14, 4), (22, 4), (9, 3)])
    tr, _, _ = _traced_run(cfg, params, reqs)
    assert validate_events(tr.events) == []


def test_emitted_events_schema_valid_preempt(rng, model):
    """The contended path exercises the decision/host-tier kinds."""
    cfg, params = model
    reqs = _requests(rng, cfg, [(30, 6), (28, 6), (26, 5), (22, 6), (9, 5)])
    tr, _, m = _traced_run(cfg, params, reqs, num_pages=11, preemption="lru",
                           host_kv_bytes=1 << 30, debug_audit=True)
    assert validate_events(tr.events) == []
    assert m.preemptions >= 1
    kinds = {e["kind"] for e in tr.events}
    assert {"preempt", "offload", "restore", "admission_denied"} <= kinds
    # every emitted kind is a known schema kind
    assert kinds <= set(EVENT_SCHEMAS)


def test_validate_event_rejects_bad_events():
    ok = {"kind": "first_token", "t": 0.5, "uid": 1, "ttft_s": 0.5}
    assert validate_event(ok) == []
    assert validate_event({"kind": "nope", "t": 0.0})      # unknown kind
    assert validate_event({"kind": "first_token", "t": 0.5, "uid": 1})
    assert validate_event({**ok, "ttft_s": "fast"})         # wrong type
    assert validate_event({**ok, "bogus": 1})               # extra field
    assert validate_event({**ok, "t": "now"})               # bad timestamp
    assert validate_event({"kind": "trace_header", "v": 999})
    # bools are not ints/nums
    assert validate_event({"kind": "host_evict", "t": 0.0, "bytes": True})


def test_optional_fields_allowed_absent_or_null():
    base = {"kind": "admission_denied", "t": 0.0, "uid": 3,
            "reason": "no_free_slot"}
    assert validate_event(base) == []
    assert validate_event({**base, "pages_needed": None}) == []
    assert validate_event({**base, "pages_needed": 7}) == []


def test_validate_jsonl_roundtrip(tmp_path):
    tr = ServeTracer(clock=FakeClock())
    tr.emit("enqueue", 0.0, uid=0, prompt_len=5, max_new=4)
    tr.emit("host_evict", 0.1, bytes=4096)
    p = str(tmp_path / "t.jsonl")
    tr.to_jsonl(p)
    n, errs = TR.validate_jsonl(p)
    assert (n, errs) == (2, [])
    # the command line (python -m repro.core.trace) gates on the same
    assert TR._main(["validate", p]) == 0
    assert TR._main(["summary", p]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "nope", "t": 0.0}) + "\n")
    assert TR._main(["validate", str(bad)]) == 1


# ---------------------------------------------------------------------------
# Zero perturbation: traced == untraced, bit-identical, across modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["plain", "prefix", "int8", "spec",
                                  "preempt"])
def test_traced_outputs_bit_identical(rng, model, mode):
    cfg, params = model
    import dataclasses
    policy = dataclasses.replace(FP32, kv_dtype="int8") \
        if mode == "int8" else FP32
    prefix = list(map(int, rng.integers(4, 400, size=16))) \
        if mode == "prefix" else None
    shapes = [(14, 5), (25, 5), (9, 4)] if prefix \
        else [(30, 5), (26, 5), (9, 4), (22, 5)]
    reqs = _requests(rng, cfg, shapes, prefix=prefix)
    kw = {}
    if mode == "spec":
        from repro.core.speculative import SpecConfig
        kw["spec"] = SpecConfig(k=3, drafter="ngram")
    if mode == "preempt":
        kw.update(num_pages=11, preemption="lru", host_kv_bytes=1 << 30,
                  debug_audit=True)

    base, _ = _serve(_engine(cfg, params, policy), reqs, **kw)
    tr = ServeTracer()
    done, _ = _serve(_engine(cfg, params, policy), reqs, trace=tr, **kw)
    for uid, out in done.items():
        assert out == base[uid], f"tracing perturbed outputs ({mode})"
    assert validate_events(tr.events) == []


# ---------------------------------------------------------------------------
# Reconciliation: trace totals vs ServeMetrics
# ---------------------------------------------------------------------------


def test_trace_reconciles_with_metrics(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(18, 5), (24, 5), (11, 4)])
    tr = ServeTracer()
    _, m = _serve(_engine(cfg, params), reqs, trace=tr)
    span_dev = sum(e["dur"] for e in tr.iter_events("span")
                   if e["track"] == "device")
    it_dev = sum(e["device_s"] for e in tr.iter_events("iteration"))
    it_host = sum(e["host_s"] for e in tr.iter_events("iteration"))
    # device spans use the same clock reads that feed prefill_s/decode_s
    assert span_dev == pytest.approx(m.device_s, rel=1e-9, abs=1e-9)
    assert it_dev == pytest.approx(m.device_s, rel=1e-9, abs=1e-9)
    # iteration host time excludes pre/post-loop overhead, so it can only
    # undershoot the metrics' host share
    assert 0.0 <= it_host <= m.host_s + 1e-6
    # lifecycle accounting closes: every request enqueued, admitted, retired
    uids = {r.uid for r in reqs}
    for kind in ("enqueue", "admit", "retire"):
        assert {e["uid"] for e in tr.iter_events(kind)} == uids
    # iteration budget fields respect the configured ceiling
    for e in tr.iter_events("iteration"):
        assert e["budget"] == 16
        assert 0 <= e["budget_used"] <= 16


def test_first_token_matches_metrics_ttft(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(13, 4), (21, 4)])
    tr = ServeTracer()
    _, m = _serve(_engine(cfg, params), reqs, trace=tr)
    ttfts = sorted(e["ttft_s"] for e in tr.iter_events("first_token"))
    assert len(ttfts) == len(reqs)
    np.testing.assert_allclose(ttfts, sorted(m.ttft_s), rtol=1e-9)


# ---------------------------------------------------------------------------
# Attention-work counters and due times
# ---------------------------------------------------------------------------

PAGE = 8        # _serve's page size


def _visible(block_row, pos):
    """Brute force: KV entries of one slot's block-table row at absolute
    positions <= ``pos`` (what a query at ``pos`` attends)."""
    return sum(1 for j, page in enumerate(block_row) if page >= 0
               for o in range(PAGE) if j * PAGE + o <= pos)


def _brute_packed(args, _out):
    slot_ids, positions = np.asarray(args[2]), np.asarray(args[3])
    bt = np.asarray(args[6])
    kv = pairs = 0
    lanes = [t for t in range(len(slot_ids)) if slot_ids[t] >= 0]
    for i, t in enumerate(lanes):
        s = slot_ids[t]
        pairs += _visible(bt[s], positions[t])
        # a segment is a run of one slot's lanes: it reads its slot's
        # context once, through its last query
        if i + 1 == len(lanes) or slot_ids[lanes[i + 1]] != s:
            kv += _visible(bt[s], positions[t])
    return kv, pairs


def _brute_step(args, out):
    lens, bt = np.asarray(args[2]), np.asarray(args[5])
    acts = np.asarray(out[7])
    n = sum(_visible(bt[s], lens[s] + k)
            for s in range(acts.shape[0]) for k in range(acts.shape[1])
            if acts[s, k])
    return n, n


def _brute_mixed(args, _out):
    start, n_q = int(np.asarray(args[2])[0]), int(np.asarray(args[3])[0])
    row = np.asarray(args[4])[0]
    return (_visible(row, start + n_q - 1),
            sum(_visible(row, p) for p in range(start, start + n_q)))


def _brute_verify(args, _out):
    lens, act, bt = (np.asarray(args[i]) for i in (2, 4, 6))
    k1 = np.shape(args[5])[1] + 1          # pending token + the drafts
    kv = pairs = 0
    for s in np.flatnonzero(act):
        kv += _visible(bt[s], lens[s] + k1 - 1)
        pairs += sum(_visible(bt[s], p) for p in range(lens[s],
                                                       lens[s] + k1))
    return kv, pairs


def _live(block_row, qmax):
    """Hand count: the pages of a block-table row a paged kernel computes
    for a work item whose largest query is at ``qmax`` — every page that
    starts at or before it; none without a query or a first page."""
    if qmax < 0 or block_row[0] < 0:
        return 0
    return sum(1 for j in range(len(block_row)) if j * PAGE <= qmax)


def _pages_packed(args, _out):
    # the packed kernel steps once per block of BP live pages of a row;
    # the test model's pages are small enough that a block is a whole
    # row of the block table (test_page_counters_match_hand_count)
    positions, meta, bt = (np.asarray(args[i]) for i in (3, 4, 6))
    live = [_live(bt[slot], positions[ws:we].max())
            for slot, _, ws, we in meta if slot >= 0]
    bp = bt.shape[1]
    return sum(live), sum(-(-n // bp) for n in live)


def _pages_step(args, out):
    lens, bt = np.asarray(args[2]), np.asarray(args[5])
    acts = np.asarray(out[7])
    live = sum(_live(bt[s], lens[s] + acts[s, :k].sum())
               for s in range(acts.shape[0]) for k in range(acts.shape[1]))
    return live, acts.size * bt.shape[1]


def _pages_mixed(args, _out):
    start, n_q = int(np.asarray(args[2])[0]), int(np.asarray(args[3])[0])
    row = np.asarray(args[4])[0]
    return _live(row, start + n_q - 1), len(row)


def _pages_verify(args, _out):
    # every slot is a work item, its window through lens + k
    lens, bt = np.asarray(args[2]), np.asarray(args[6])
    k = np.shape(args[5])[1]
    live = sum(_live(bt[s], lens[s] + k) for s in range(len(lens)))
    return live, bt.size


BRUTE_WORK = {"packed": _brute_packed, "step": _brute_step,
              "mixed": _brute_mixed, "verify": _brute_verify}
BRUTE_PAGES = {"packed": _pages_packed, "step": _pages_step,
               "mixed": _pages_mixed, "verify": _pages_verify}
SPEC_K = 3
DISPATCHES = ("packed", "step", "mixed", "verify")


def _record_dispatches(eng, tr, steps_per_sync=4, brute=BRUTE_WORK):
    """Wrap the engine's cached step programs: each call appends (the
    iteration it belongs to, its ``brute`` count, and the abstract
    arguments it was called with) to the returned dict."""
    from repro.core.sampling import SamplingParams
    sp = SamplingParams()
    calls = {name: [] for name in DISPATCHES}
    calls["lowered"] = {}

    def wrap(name, fn, brute):
        def run(*args):
            avals = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args)
            host = [np.asarray(a) if i not in (0, len(args) - 2) else None
                    for i, a in enumerate(args)]
            out = fn(*args)
            it = sum(1 for e in tr.events if e["kind"] == "iteration")
            calls[name].append((it, brute(host, out)))
            calls["lowered"].setdefault(name, (fn, avals))
            return out
        return run

    eng._packed_fns(sp)
    eng._mixed_fns(sp)
    eng._spec_fns(sp, SPEC_K)
    for steps in {1, steps_per_sync}:
        eng._continuous_fns(sp, steps)
    c = eng._cont_cache
    c[("packed", sp)] = wrap("packed", c[("packed", sp)], brute["packed"])
    c[("mixed", sp)] = wrap("mixed", c[("mixed", sp)], brute["mixed"])
    c[("spec", sp, SPEC_K)] = wrap("verify", c[("spec", sp, SPEC_K)],
                                   brute["verify"])
    for steps in {1, steps_per_sync}:
        a, ap, step = c[(sp, steps)]
        c[(sp, steps)] = (a, ap, wrap("step", step, brute["step"]))
    return calls


def _mode_kw(packed):
    """Serve options of a counter test's case: packed (True), per-chunk
    mixed (False), or packed with ngram speculation ("spec")."""
    if packed != "spec":
        return {"packed": packed}
    from repro.core.speculative import SpecConfig
    return {"packed": True, "spec": SpecConfig(k=SPEC_K, drafter="ngram")}


def _decode_dispatch(packed):
    """The dispatch a decode-only iteration runs."""
    return "verify" if packed == "spec" else "step"


@pytest.mark.parametrize("packed", [True, False, "spec"])
def test_attention_counters_match_brute_force(rng, model, packed):
    """Each iteration's attn_kv_tokens / attn_pairs equal a brute-force
    count over its dispatches' block tables, lengths and plan: packed
    iterations, mixed ones (decode micro-step plus per-chunk forwards),
    fused decode scans and (with speculation) verify windows."""
    cfg, params = model
    reqs = _requests(rng, cfg, [(30, 3), (26, 9), (9, 6), (22, 7)])
    tr = ServeTracer(clock=FakeClock())
    eng = _engine(cfg, params)
    calls = _record_dispatches(eng, tr)
    _serve(eng, reqs, trace=tr, **_mode_kw(packed))
    assert validate_events(tr.events) == []
    its = list(tr.iter_events("iteration"))
    want = {}
    for name in DISPATCHES:
        for it, (kv, pairs) in calls[name]:
            w = want.setdefault(it, [0, 0])
            w[0] += kv
            w[1] += pairs
    got = {e["iter"]: [e["attn_kv_tokens"], e["attn_pairs"]] for e in its
           if e["attn_kv_tokens"] or e["attn_pairs"]}
    assert got == want
    assert all(e["attn_kv_tokens"] == e["attn_pairs"] == 0
               for e in its if e["idle"])
    assert calls["packed" if packed else "mixed"]
    dec = _decode_dispatch(packed)
    assert any(its[it]["chunk_segments"] == 0 for it, _ in calls[dec]), \
        f"no {dec} iteration ran"


@pytest.mark.parametrize("packed", [True, False, "spec"])
def test_page_counters_match_hand_count(rng, model, packed):
    """Each iteration's attn_pages equals a hand count of the live pages
    of its kernel dispatches' work items (packed query windows, fused
    decode lanes at every step, chunk rows, verify windows), and
    attn_page_steps equals the kernels' steps: work items x pages per
    slot, and for a packed dispatch one per block of live pages."""
    from repro.kernels import decode_attention as DA
    cfg, params = model
    reqs = _requests(rng, cfg, [(30, 3), (26, 9), (9, 6), (22, 7)])
    tr = ServeTracer(clock=FakeClock())
    eng = _engine(cfg, params)
    # a packed block holds a whole block-table row here (_pages_packed)
    pool = eng._paged_context(PAGE, None, None)["cache"]["layers"][0][0]
    npages = -(-eng.max_len // PAGE)
    assert DA.packed_block_pages(pool["pk"], pool["pv"], None,
                                 npages) == npages
    calls = _record_dispatches(eng, tr, brute=BRUTE_PAGES)
    _serve(eng, reqs, trace=tr, **_mode_kw(packed))
    assert validate_events(tr.events) == []
    want = {}
    for name in DISPATCHES:
        for it, (live, steps) in calls[name]:
            w = want.setdefault(it, [0, 0])
            w[0] += live
            w[1] += steps
    its = list(tr.iter_events("iteration"))
    got = {e["iter"]: [e["attn_pages"], e["attn_page_steps"]] for e in its
           if e["attn_page_steps"]}
    assert got == want
    live, steps = np.asarray(list(want.values())).sum(0)
    assert 0 < live < steps
    assert calls["packed" if packed else "mixed"]
    assert calls[_decode_dispatch(packed)]


def _moe_tokens_packed(args, _out):
    return (int((np.asarray(args[2]) >= 0).sum()),)


def _moe_tokens_step(args, out):
    return (int(np.asarray(out[7]).sum()),)


def _moe_tokens_mixed(args, _out):
    return (int(np.asarray(args[3]).sum()),)


def _moe_tokens_verify(args, _out):
    # each active slot's window: its pending token and the drafts
    return (int(np.asarray(args[4]).sum()) * (np.shape(args[5])[1] + 1),)


BRUTE_MOE = {"packed": _moe_tokens_packed, "step": _moe_tokens_step,
             "mixed": _moe_tokens_mixed, "verify": _moe_tokens_verify}


@pytest.fixture(scope="module")
def moe_model():
    cfg = get_reduced("qwen3-30b-a3b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("packed", [True, False, "spec"])
def test_moe_counters_count_real_tokens_only(rng, moe_model, model, packed):
    """Each iteration's moe_rows is top_k x expert layers x the real
    tokens of its dispatches (packed lanes with a slot, active decode
    lanes at every fused step, chunk tokens, active slots' verify
    windows): padding lanes and inactive slots add nothing.  Experts hit and the largest expert's rows lie
    within what those rows allow; a dense model's records read 0."""
    cfg, params = moe_model
    k, layers, E = cfg.moe.top_k, cfg.num_layers, cfg.moe.num_experts
    reqs = _requests(rng, cfg, [(30, 3), (26, 9), (9, 6), (22, 7)])
    tr = ServeTracer(clock=FakeClock())
    eng = _engine(cfg, params)
    calls = _record_dispatches(eng, tr, brute=BRUTE_MOE)
    _serve(eng, reqs, trace=tr, **_mode_kw(packed))
    assert validate_events(tr.events) == []
    tokens = {}
    for name in DISPATCHES:
        for it, (n,) in calls[name]:
            tokens[it] = tokens.get(it, 0) + n
    its = list(tr.iter_events("iteration"))
    for e in its:
        n = tokens.get(e["iter"], 0)
        assert e["moe_rows"] == n * k * layers, e
        # per forward and layer at most E experts, and one row per token
        # of one expert; a dispatch runs at most 4 forwards (fused steps)
        assert (e["moe_experts_hit"] > 0) == (n > 0)
        assert e["moe_experts_hit"] <= E * layers * 4
        assert (e["moe_rows_max"] > 0) == (n > 0)
        assert e["moe_rows_max"] <= n
    assert any(e["padded_lanes"] > 0 for e in its if e["moe_rows"]) \
        or not packed
    fused = [e for e in its if not e["idle"] and e["chunk_segments"] == 0]
    assert fused and all(e["moe_rows"] > 0 for e in fused)
    # a model without experts stamps zeros
    dcfg, dparams = model
    tr2 = ServeTracer(clock=FakeClock())
    _serve(_engine(dcfg, dparams), _requests(rng, dcfg, [(9, 4)]), trace=tr2)
    assert all(e["moe_rows"] == e["moe_experts_hit"] == e["moe_rows_max"]
               == 0 for e in tr2.iter_events("iteration"))


def test_attention_work_rule():
    from repro.core.continuous import attention_work
    # decode lanes at lens 5 and 0: one query each over lens + 1 tokens
    assert attention_work([5, 0], 1) == (7, 7)
    # a 4-token chunk from position 3: reads 7 tokens, 4+5+6+7 pairs
    assert attention_work([3], [4]) == (7, 22)
    assert attention_work([], []) == (0, 0)


def test_a_lane_retires_mid_scan_and_counts_only_its_live_steps(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(12, 3), (10, 12)])
    tr = ServeTracer(clock=FakeClock())
    eng = _engine(cfg, params)
    seen = []
    from repro.core.sampling import SamplingParams
    sp = SamplingParams()
    a, ap, step = eng._continuous_fns(sp, 4)

    def spy(*args):
        lens = np.asarray(args[2]).copy()
        out = step(*args)
        seen.append((lens, np.asarray(out[7])))
        return out
    eng._cont_cache[(sp, 4)] = (a, ap, spy)
    _serve(eng, reqs, trace=tr)
    partial = [(l, acts) for l, acts in seen
               if any(r.any() and not r.all() for r in acts)]
    assert partial, "no lane retired inside a fused scan"
    fused = [e for e in tr.iter_events("iteration")
             if not e["idle"] and e["chunk_segments"] == 0]
    assert len(fused) == len(seen)
    for e, (lens, acts) in zip(fused, seen):
        want = sum(int(lens[s]) + k + 1 for s in range(acts.shape[0])
                   for k in range(acts.shape[1]) if acts[s, k])
        assert e["attn_kv_tokens"] == e["attn_pairs"] == want


def test_step_program_jit_names(rng, model):
    """The benchmark finds the step programs' executions in the device
    trace by their jit names: lowering them must keep jit_packed_fn and
    jit_step_fn as the module names."""
    cfg, params = model
    reqs = _requests(rng, cfg, [(20, 6), (9, 6)])
    tr = ServeTracer()
    eng = _engine(cfg, params)
    calls = _record_dispatches(eng, tr)
    _serve(eng, reqs, trace=tr)
    for name, module in (("packed", "jit_packed_fn"), ("step", "jit_step_fn")):
        fn, avals = calls["lowered"][name]
        text = fn.lower(*avals).as_text()
        assert f"module @{module} " in text, text[:200]


def test_enqueue_due_and_latencies_count_from_it(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(13, 4), (21, 4), (9, 3)])
    arrivals = [0.0, 0.05, 0.3]
    tr = ServeTracer(clock=FakeClock())
    _, m = _serve(_engine(cfg, params), reqs, trace=tr, arrivals=arrivals)
    assert validate_events(tr.events) == []
    enq = {e["uid"]: e for e in tr.iter_events("enqueue")}
    due = {r.uid: a for r, a in zip(reqs, arrivals)}
    assert {u: e["due"] for u, e in enq.items()} == due
    assert all(e["t"] >= e["due"] for e in enq.values())
    first = list(tr.iter_events("first_token"))
    for e in first:
        assert e["ttft_s"] == pytest.approx(e["t"] - due[e["uid"]],
                                            abs=1e-12)
    np.testing.assert_allclose(sorted(e["ttft_s"] for e in first),
                               sorted(m.ttft_s), rtol=1e-12)
    for e in tr.iter_events("retire"):
        assert e["latency_s"] == pytest.approx(e["t"] - due[e["uid"]],
                                               abs=1e-12)
    # without arrivals the request is due when submitted: no field, and
    # TTFT counts from the enqueue as before
    tr2 = ServeTracer(clock=FakeClock())
    _serve(_engine(cfg, params), reqs, trace=tr2)
    enq2 = {e["uid"]: e for e in tr2.iter_events("enqueue")}
    assert not any("due" in e for e in enq2.values())
    for e in tr2.iter_events("first_token"):
        assert e["ttft_s"] == pytest.approx(e["t"] - enq2[e["uid"]]["t"],
                                            abs=1e-12)


def test_schema_v2_fields_validate():
    it = {"kind": "iteration", "t": 0.0, "iter": 0, "dur": 0.1,
          "host_s": 0.01, "device_s": 0.09, "budget": 16, "budget_used": 9,
          "decode_lanes": 2, "chunk_segments": 1, "chunk_tokens": 7,
          "width_bucket": 16, "padded_lanes": 7, "idle": False,
          "pages_in_use": 3, "host_bytes": 0, "trie_nodes": 1,
          "attn_kv_tokens": 40, "attn_pairs": 90, "attn_pages": 12,
          "attn_page_steps": 96, "moe_rows": 0, "moe_experts_hit": 0,
          "moe_rows_max": 0}
    assert validate_event(it) == []
    for f in ("attn_kv_tokens", "attn_pairs"):
        assert validate_event({k: v for k, v in it.items() if k != f})
        assert validate_event({**it, f: 1.5})
    enq = {"kind": "enqueue", "t": 0.2, "uid": 1, "prompt_len": 5,
           "max_new": 4}
    assert validate_event(enq) == []
    assert validate_event({**enq, "due": 0.15}) == []
    assert validate_event({**enq, "due": "soon"})
    w = {"kind": "weights", "t": 0.0, "dtype": "int8", "weight_bytes": 1,
         "weight_bytes_dense": 2, "quantized_tensors": 1}
    assert validate_event(w) == [] == validate_event({**w, "quantize_s": .5})


def test_schema_v3_fields_validate():
    it = {"kind": "iteration", "t": 0.0, "iter": 0, "dur": 0.1,
          "host_s": 0.01, "device_s": 0.09, "budget": 16, "budget_used": 9,
          "decode_lanes": 2, "chunk_segments": 1, "chunk_tokens": 7,
          "width_bucket": 16, "padded_lanes": 7, "idle": False,
          "pages_in_use": 3, "host_bytes": 0, "trie_nodes": 1,
          "attn_kv_tokens": 40, "attn_pairs": 90, "attn_pages": 12,
          "attn_page_steps": 96, "moe_rows": 0, "moe_experts_hit": 0,
          "moe_rows_max": 0}
    assert validate_event(it) == []
    for f in ("attn_pages", "attn_page_steps"):
        assert validate_event({k: v for k, v in it.items() if k != f})
        assert validate_event({**it, f: 1.5})
    assert TR.TRACE_SCHEMA_VERSION == 4
    assert validate_event({"kind": "trace_header", "v": 4}) == []
    for old in (1, 2, 3):
        assert validate_event({"kind": "trace_header", "v": old})


def test_schema_v4_fields_validate():
    it = {"kind": "iteration", "t": 0.0, "iter": 0, "dur": 0.1,
          "host_s": 0.01, "device_s": 0.09, "budget": 16, "budget_used": 9,
          "decode_lanes": 2, "chunk_segments": 1, "chunk_tokens": 7,
          "width_bucket": 16, "padded_lanes": 7, "idle": False,
          "pages_in_use": 3, "host_bytes": 0, "trie_nodes": 1,
          "attn_kv_tokens": 40, "attn_pairs": 90, "attn_pages": 12,
          "attn_page_steps": 96, "moe_rows": 36, "moe_experts_hit": 15,
          "moe_rows_max": 5}
    assert validate_event(it) == []
    for f in ("moe_rows", "moe_experts_hit", "moe_rows_max"):
        assert validate_event({k: v for k, v in it.items() if k != f})
        assert validate_event({**it, f: 1.5})
        assert validate_event({**it, f: True})


# ---------------------------------------------------------------------------
# Perfetto exporter
# ---------------------------------------------------------------------------


def test_perfetto_structure(rng, model):
    cfg, params = model
    reqs = _requests(rng, cfg, [(14, 4), (22, 4)])
    tr, _, _ = _traced_run(cfg, params, reqs)
    doc = to_perfetto_dict(list(tr.events), dropped=tr.dropped)
    te = doc["traceEvents"]
    assert isinstance(te, list) and te
    assert doc["otherData"]["schema_version"] == TR.TRACE_SCHEMA_VERSION
    names = {e.get("args", {}).get("name") for e in te if e["ph"] == "M"}
    assert {"repro-serve", "scheduler", "device"} <= names
    # balanced B/E nesting per tid (slot occupancy slices)
    depth = {}
    for e in te:
        if e["ph"] == "B":
            depth[e["tid"]] = depth.get(e["tid"], 0) + 1
        elif e["ph"] == "E":
            depth[e["tid"]] = depth.get(e["tid"], 0) - 1
            assert depth[e["tid"]] >= 0
    assert all(v == 0 for v in depth.values())
    # gauges exported as counter tracks, timestamps in microseconds
    counters = {e["name"] for e in te if e["ph"] == "C"}
    assert {"pages_in_use", "host_bytes", "trie_nodes"} <= counters
    # iteration slices carry the attention counters in their args
    its = [e for e in te if e["ph"] == "X" and e["name"] == "iteration"]
    assert its and all({"attn_kv_tokens", "attn_pairs", "attn_pages",
                        "attn_page_steps", "moe_rows", "moe_experts_hit",
                        "moe_rows_max"} <= set(e["args"]) for e in its)
    assert all(isinstance(e["ts"], (int, float))
               for e in te if "ts" in e)


def test_perfetto_closes_dangling_slices():
    """A preempt without slot (lost record) must not corrupt nesting:
    an admit with no matching end is closed at trace end."""
    evs = [{"kind": "admit", "t": 0.1, "uid": 7, "slot": 0,
            "matched_tokens": 0, "pages": 2, "resume": "no"}]
    doc = to_perfetto_dict(evs)
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert phases.count("B") == phases.count("E") == 1


def test_ring_buffer_drops_and_counts():
    tr = ServeTracer(clock=FakeClock(), ring_size=5)
    for i in range(8):
        tr.emit("host_evict", float(i), bytes=i)
    assert len(tr.events) == 5
    assert tr.dropped == 3
    assert [e["t"] for e in tr.events] == [3.0, 4.0, 5.0, 6.0, 7.0]
    assert tr.header()["dropped"] == 3


# ---------------------------------------------------------------------------
# Shared percentile helper + metrics dump
# ---------------------------------------------------------------------------


def test_percentile_matches_numpy(rng):
    vals = list(rng.uniform(0.0, 10.0, size=37))
    for q in (50, 90, 99):
        assert ServeMetrics.percentile(vals, q) == pytest.approx(
            float(np.percentile(np.asarray(vals), q)))
    assert ServeMetrics.percentile([], 50) == 0.0
    assert ServeMetrics.percentile([3.5], 99) == 3.5


def test_metrics_to_dict_derived_keys():
    m = ServeMetrics(host_s=1.0, device_s=3.0, mixed_iters=4,
                     mixed_dispatches=4, packed_tokens_real=90,
                     packed_tokens_padded=100,
                     latency_s=[1.0, 2.0], ttft_s=[0.1, 0.2],
                     itl_s=[0.01, 0.02])
    d = m.to_dict()
    for k in ("latency_p50", "latency_p99", "ttft_p50", "ttft_p99",
              "itl_p50", "itl_p99", "host_frac", "dispatches_per_iter",
              "padded_token_frac", "decode_idle_frac", "acceptance_rate",
              "tokens_per_forward", "prefix_hit_rate"):
        assert k in d, k
    assert d["host_frac"] == pytest.approx(0.25)
    assert "latency_s" not in d                   # raw lists opt-in only
    assert "latency_s" in m.to_dict(include_raw=True)
    json.dumps(d)                                 # JSON-serializable


# ---------------------------------------------------------------------------
# bench_diff regression gate
# ---------------------------------------------------------------------------


def _synthetic_overload(**over):
    rep = {
        "arch": "qwen3-4b", "requests": 8, "slots": 3, "max_new": 8,
        "trace": "overload",
        "overload": {
            "all_terminal": True, "all_completed": True,
            "outputs_identical_contended": True,
            "contended": {"preemptions": 10, "offloaded_pages": 72,
                          "restored_pages": 72},
        },
    }
    for path, v in over.items():
        cur = rep
        parts = path.split(".")
        for p in parts[:-1]:
            cur = cur[p]
        cur[parts[-1]] = v
    return rep


def test_bench_diff_baseline_vs_itself_passes():
    rep = _synthetic_overload()
    v = bench_diff.diff(rep, rep)
    assert v["kind"] == "overload"
    assert v["config_match"] and v["pass"] and v["n_fail"] == 0


def test_bench_diff_invariant_break_fails():
    base = _synthetic_overload()
    fresh = _synthetic_overload(**{
        "overload.outputs_identical_contended": False})
    v = bench_diff.diff(base, fresh)
    assert not v["pass"]
    bad = [c for c in v["checks"] if c["status"] == "FAIL"]
    assert any("outputs_identical_contended" in c["path"] for c in bad)


def test_bench_diff_relative_regression_fails_on_config_match():
    base = _synthetic_overload()
    fresh = _synthetic_overload(**{"overload.contended.preemptions": 500})
    v = bench_diff.diff(base, fresh)
    assert not v["pass"]          # preemptions ballooned beyond tolerance


def test_bench_diff_config_mismatch_skips_relative_checks():
    base = _synthetic_overload()
    fresh = _synthetic_overload(**{"overload.contended.preemptions": 500})
    fresh["requests"] = 99        # different run shape
    v = bench_diff.diff(base, fresh)
    assert not v["config_match"]
    assert v["pass"]              # invariants hold; relative checks skipped
    assert any(c["status"] == "SKIP" and c["mode"] == "rel"
               for c in v["checks"])


def test_bench_diff_if_present_semantics():
    base = {"arch": "a", "requests": 1, "slots": 1, "max_new": 1,
            "trace": "mixed",
            "outputs_identical_prefix_on_off": True,
            "packed": {"outputs_identical_packed_on_off": True,
                       "packed_on": {"dispatches_per_iter": 1.0,
                                     "padded_token_frac": 0.1,
                                     "prefill_pad_frac": 0.0}}}
    # absent from both baseline and fresh -> SKIP
    v = bench_diff.diff(base, copy.deepcopy(base), kind="serving")
    spec = [c for c in v["checks"]
            if c["path"] == "speculative.outputs_match_nonspec"][0]
    assert spec["status"] == "SKIP"
    # present in baseline, silently dropped from fresh -> FAIL
    base2 = copy.deepcopy(base)
    base2["speculative"] = {"outputs_match_nonspec": True}
    v2 = bench_diff.diff(base2, copy.deepcopy(base), kind="serving")
    spec2 = [c for c in v2["checks"]
             if c["path"] == "speculative.outputs_match_nonspec"][0]
    assert spec2["status"] == "FAIL" and not v2["pass"]


def test_bench_diff_kind_detection():
    assert bench_diff.detect_kind({"overload": {}}) == "overload"
    assert bench_diff.detect_kind({"longprompt": {}}) == "longprompt"
    assert bench_diff.detect_kind({"packed": {}}) == "serving"
