"""The qwen3-30b-a3b cell on the CPU at its own small size: the harness
runs it and its reference passes sound runs and fails the control; the
operations and bytes its readers count, against hand-reckoned values."""
import copy
import time

import pytest

from bench_testkit import harness, load_cell
from benchlib import arith
from benchlib import attention as A
from benchlib import moe as M
from benchlib import profile as P

CELL = "qwen3-30b-a3b.chat-backlog"
# the program's reduced qwen3-30b-a3b, in the configuration file's keys
SMALL_MODEL = dict(hidden_size=128, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=32, num_experts=8,
                   num_experts_per_tok=2, moe_intermediate_size=128,
                   vocab_size=512, num_hidden_layers=2)
# at this size sound runs of the float32 program read widest gaps of 0
# (it picks the float32 reference's argmax everywhere), the [4, 8]
# control 3.2-4.4; the limit leaves room for summation-order ties
SMALL_GAP_LIMIT = 0.002


def run_small(seed, control=False, trace=False):
    cell = load_cell(CELL)
    model = {**cell.config, **SMALL_MODEL}
    engine = dict(cell.config["engine"], slots=4, max_batched_tokens=32,
                  max_len=128, num_pages=64)
    cell.config = copy.deepcopy(cell.config)
    cell.config["program"]["policy"] = "fp32"
    mix = copy.deepcopy(cell.mix)
    mix["prompt_tokens"].update(min=8, max=60)
    mix["output_tokens"].update(min=4, max=16)
    mix["backlog_per_s"] = 4
    cell.mix = mix
    cell.check = dict(cell.check, logit_gap_max=SMALL_GAP_LIMIT)
    keep = harness.enable_compile_cache
    harness.enable_compile_cache = lambda: None
    try:
        return harness.run_cell(cell, seed=seed, seconds=2.0, trace=trace,
                                t_start=time.perf_counter(),
                                require_tpu=False, reduced=True,
                                model_json=model, engine_json=engine,
                                control=control)
    finally:
        harness.enable_compile_cache = keep


def test_served_tokens_agree_with_the_reference():
    res = run_small(2 ** 33 + 17)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["logit_gap_max"]["value"] < SMALL_GAP_LIMIT
    assert {"output_tokens_per_s", "setup_s"} <= set(res["metrics"])


def test_control_is_not_correct():
    res = run_small(31, control=True)
    assert not res["correct"], res["checks"]
    assert res["checks"]["control_gap_max"]["value"] > SMALL_GAP_LIMIT


def stage_dims():
    cell = load_cell(CELL)
    return cell.model.dims(cell.config)


def test_matmul_flops_of_the_stage():
    m = stage_dims()
    # per token and layer: q 2048x4096, k and v 2048x512, o 4096x2048, and
    # the three 2048x768 matrices of each of its 8 experts; per sampled
    # row the 2048 x 151936 head
    per_token = (8_388_608 + 2 * 1_048_576 + 8_388_608 + 3 * 12_582_912) * 6
    assert per_token == 339_738_624
    assert arith.matmul_flops(m, 1, 0) == 2 * per_token
    assert arith.matmul_flops(m, 0, 1) == 2 * 311_164_928


def test_kv_bytes_per_token_of_the_bf16_pool():
    # 2 x 4 heads x 128 bf16 values + the int32 position entry
    assert A.kv_bytes_per_token(stage_dims()) == 2_052


DIMS = dict(d=8, f=4, L=2, E=4, top_k=2)
PEAK = {"bf16_flops_per_s": 4e4, "hbm_bytes_per_s": 1e4}


class Run:
    def __init__(self, events, tracer):
        self.reduction = P.reduce(events, 0.0, 3.0)
        self.tracer_events = tracer
        self.dims, self.peak = DIMS, PEAK

    def events(self, kind):
        return [e for e in self.tracer_events if e["kind"] == kind]


def synthetic(with_fields=True):
    """A packed iteration (bytes-bound) and a fused decode one whose rows
    make it compute-bound, each calling the kernel three times."""
    def it(t, rows, hit, idle=False):
        e = {"kind": "iteration", "t": t, "dur": 1.0, "idle": idle}
        if with_fields:
            e.update(moe_rows=rows, moe_experts_hit=hit, moe_rows_max=rows)
        return e
    tracer = [it(0.0, 10, 5), it(1.0, 2000, 8), it(2.0, 0, 0, idle=True),
              {"kind": "span", "t": 0.1, "dur": 0.8, "name": "packed"},
              {"kind": "span", "t": 1.1, "dur": 0.8, "name": "decode"}]
    modules = [["jit_packed_fn(1)", 0.2, 0.6, 0],
               ["jit_step_fn(2)", 1.2, 0.6, 0]]
    ops = [["expert_matmul.4", 0.25 + 0.1 * i, 0.05, 0] for i in range(3)]
    ops += [["expert_matmul.9", 1.25 + 0.15 * i, 0.1, 0] for i in range(3)]
    return {"ops": ops, "modules": modules}, tracer


def test_readers_on_a_synthetic_stretch():
    run = Run(*synthetic())
    read = {m.name: m.read for m in load_cell(CELL).per_layer}
    # packed: 2*10*3*8*4 = 1920 ops; 5*3*8*4*2 + 10*4*8 = 1280 bytes
    # decode: 2*2000*3*8*4 = 384000 ops; 8*192 + 2000*32 = 65536 bytes
    assert M.expert_matmul_cost(DIMS, 10, 5) == (1920.0, 1280.0)
    assert M.expert_matmul_cost(DIMS, 2000, 8) == (384_000.0, 65_536.0)
    ideal = max(1920 / 4e4, 1280 / 1e4) + max(384_000 / 4e4, 65_536 / 1e4)
    assert ideal == pytest.approx(0.128 + 9.6)      # bytes, then ops
    assert read["expert_matmul_roofline.batch"](run) == \
        pytest.approx(100 * ideal / (3 * 0.05 + 3 * 0.1))
    # (10 + 2000) rows over (5 + 8) experts hit; the idle one adds nothing
    assert read["rows_per_expert.batch"](run) == pytest.approx(2010 / 13)


def test_a_program_without_the_counters_reads_nothing():
    run = Run(*synthetic(with_fields=False))
    read = {m.name: m.read for m in load_cell(CELL).per_layer}
    assert read["expert_matmul_roofline.batch"](run) is None
    assert read["rows_per_expert.batch"](run) is None
