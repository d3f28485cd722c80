"""The paged attention kernels' rooflines and the serve loop's arrival lag,
read from the program's attention counters and due times: exact values
on a small synthetic stretch, nothing from a trace of a program without
those fields, and shares inside (0, 100] on a stretch recorded on a TPU
v5e (``bench/run.py --dump``)."""
import json
import os

import pytest

from bench_testkit import REPO, load_cell
from benchlib import attention as A
from benchlib import profile as P

FIXTURES = os.path.join(REPO, "tests", "bench", "fixtures")
OLD = os.path.join(FIXTURES, "unimo-text.offline-batch.json")
NEW = os.path.join(FIXTURES, "unimo-text.chat-short.json")

ROOFLINES = ["paged_packed_attention_roofline", "paged_decode_attention_roofline"]
DIMS = dict(d=8, L=2, Hq=2, Hkv=2, D=4, ff=16, ffn_mats=2, V=32, kvbits=8)
PEAK = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e4}


class Run:
    def __init__(self, events, tracer, dims=DIMS, peak=PEAK, w=(0.0, 4.0)):
        self.reduction = P.reduce(events, *w)
        self.tracer_events = tracer
        self.dims, self.peak = dims, peak

    def events(self, kind):
        return [e for e in self.tracer_events if e["kind"] == kind]


def iteration(t, kv=None, pairs=None):
    e = {"kind": "iteration", "t": t, "dur": 1.0, "idle": False}
    if kv is not None:
        e.update(attn_kv_tokens=kv, attn_pairs=pairs)
    return e


def synthetic(with_fields=True):
    """Three iterations of one second: a packed one (memory-bound), a
    fused decode one, and a packed one whose pairs make it compute-bound;
    each kernel is called once per layer (L = 2)."""
    f = (lambda kv, pairs: (kv, pairs)) if with_fields \
        else (lambda kv, pairs: (None, None))
    tracer = [iteration(0.0, *f(10, 30)), iteration(1.0, *f(20, 20)),
              iteration(2.0, *f(10, 5000)),
              {"kind": "span", "t": 0.1, "dur": 0.8, "name": "packed"},
              {"kind": "span", "t": 1.1, "dur": 0.8, "name": "decode"},
              {"kind": "span", "t": 2.1, "dur": 0.8, "name": "packed"}]
    modules = [["jit_packed_fn(1)", 0.2, 0.5, 0],
               ["jit_step_fn(2)", 1.2, 0.6, 0],
               ["jit_packed_fn(1)", 2.2, 0.6, 0]]
    ops = [["paged_packed_attention_q8.3", 0.25, 0.1, 0],
           ["quant_matmul.1", 0.40, 0.04, 0],
           ["paged_packed_attention_q8.3", 0.45, 0.1, 0]]
    ops += [["paged_decode_attention_q8.7", 1.25 + 0.1 * i, 0.05, 0]
            for i in range(4)]
    ops += [["paged_packed_attention_q8.3", 2.25, 0.2, 0],
            ["paged_packed_attention_q8.3", 2.5, 0.2, 0]]
    return {"ops": ops, "modules": modules}, tracer


def readers(workload):
    return {m.name: m.read for m in load_cell(workload).per_layer}


def test_kv_bytes_per_token_at_the_pool_dtype():
    cell = load_cell("unimo-text.chat-short")
    dims = cell.model.dims(cell.config)
    # 2 x 16 heads x 64 int8 codes + 2 x 16 float32 scales + a position
    assert A.kv_bytes_per_token(dims) == 2180
    assert A.kv_bytes_per_token({**dims, "kvbits": 16}) == 2 * 16 * 64 * 2 + 4
    # 4 operations per pair and head dimension, every layer
    assert A.attention_cost(dims, 3, 5) == (4.0 * 16 * 64 * 5 * 24,
                                            3 * 2180 * 24.0)


@pytest.mark.parametrize("suffix,workload", [
    ("batch", "unimo-text.offline-batch"), ("serve", "unimo-text.chat-short")])
def test_rooflines_on_a_synthetic_stretch(suffix, workload):
    run = Run(*synthetic())
    read = readers(workload)
    # per kv token 2*2*4 codes + 2*2*4 scale bytes + 4 = 36 B, x L = 2
    packed_ideal = max(4 * 2 * 4 * 30 * 2 / 1e6, 10 * 36 * 2 / 1e4) \
        + max(4 * 2 * 4 * 5000 * 2 / 1e6, 10 * 36 * 2 / 1e4)
    assert packed_ideal == pytest.approx(0.072 + 0.32)
    assert read[f"{ROOFLINES[0]}.{suffix}"](run) == \
        pytest.approx(100 * packed_ideal / (0.2 + 0.4))
    decode_ideal = max(4 * 2 * 4 * 20 * 2 / 1e6, 20 * 36 * 2 / 1e4)
    assert read[f"{ROOFLINES[1]}.{suffix}"](run) == \
        pytest.approx(100 * decode_ideal / 0.2)


def test_arrival_lag_reads_enqueue_against_due():
    tracer = [{"kind": "enqueue", "t": t, "uid": i, "due": d}
              for i, (t, d) in enumerate([(0.1, 0.0), (0.5, 0.2),
                                          (0.55, 0.5), (1.0, 0.8)])]
    read = readers("unimo-text.chat-short")["arrival_lag_p95_s"]
    run = Run({"ops": [], "modules": []}, tracer)
    assert read(run) == pytest.approx(0.3)
    # a request without a due time is not counted
    run.tracer_events = tracer + [{"kind": "enqueue", "t": 9.0, "uid": 9}]
    assert read(run) == pytest.approx(0.3)


def test_a_program_without_the_fields_reads_nothing():
    run = Run(*synthetic(with_fields=False))
    for workload, suffix in (("unimo-text.offline-batch", "batch"),
                             ("unimo-text.chat-short", "serve")):
        read = readers(workload)
        for name in ROOFLINES:
            assert read[f"{name}.{suffix}"](run) is None
    run.tracer_events = [{"kind": "enqueue", "t": 0.1, "uid": 0}]
    assert readers("unimo-text.chat-short")["arrival_lag_p95_s"](run) is None
    # and no execution at all: nothing either
    empty = Run({"ops": [], "modules": []}, synthetic()[1])
    assert A.packed_roofline_pct(empty) is None
    assert A.decode_roofline_pct(empty) is None


def recorded(path):
    cell = load_cell("unimo-text.offline-batch")
    with open(path) as f:
        dump = json.load(f)
    return Run(dump["events"], dump["tracer"],
               dims=cell.model.dims(cell.config),
               peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               w=(dump["w0"], dump["w1"]))


def test_the_older_recorded_stretch_reads_nothing():
    run = recorded(OLD)
    assert A.packed_roofline_pct(run) is None
    assert A.decode_roofline_pct(run) is None
    assert A.arrival_lag_p95_s(run) is None


def test_the_recorded_stretch_reads_both_rooflines():
    """A stretch of unimo-text.chat-short traced on a TPU v5e, from the
    program that stamps the counters: packed executions around a fused
    decode one, each matched to an iteration record that carries them."""
    run = recorded(NEW)
    for read in (A.packed_roofline_pct, A.decode_roofline_pct):
        share = read(run)
        assert share is not None and 0 < share <= 100
    its = run.events("iteration")
    assert its and all(e["attn_kv_tokens"] > 0 for e in its)
    # a fused decode call of 4 steps reads each live lane's context once
    # per step: at least 4 tokens per lane
    fused = [e for e in its if e["chunk_segments"] == 0]
    assert fused and all(e["attn_kv_tokens"] == e["attn_pairs"]
                         >= 4 * e["decode_lanes"] for e in fused)
    lag = A.arrival_lag_p95_s(run)
    assert lag is not None and 0 <= lag < 5
