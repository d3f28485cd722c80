"""The reduction from a device trace to metrics, on a recorded stretch.

The fixture is a short piece of a traced run of unimo-text.offline-batch
on a TPU v5e: the device's operations and program executions, and the
serve loop's tracer events, on one clock (``bench/run.py --dump``).
"""
import json
import os

import pytest

from bench_testkit import REPO, load_cell
from benchlib import profile as P
from benchlib import readers

FIXTURE = os.path.join(REPO, "tests", "bench", "fixtures",
                       "unimo-text.offline-batch.json")


@pytest.fixture(scope="module")
def dump():
    with open(FIXTURE) as f:
        return json.load(f)


def naive_busy(ops, w0, w1, step=1e-6):
    """Busy seconds by walking the window in 1 us steps."""
    marks = {}
    for _, s, d, _ in ops:
        a, b = max(w0, s), min(w1, s + d)
        for i in range(int((a - w0) / step), int((b - w0) / step)):
            marks[i] = True
    return len(marks) * step


def test_busy_time_is_the_union_of_operations(dump):
    red = P.reduce(dump["events"], dump["w0"], dump["w1"])
    assert red.window_s == pytest.approx(dump["w1"] - dump["w0"])
    assert 0 < red.busy_s < red.window_s
    assert red.busy_s == pytest.approx(
        naive_busy(dump["events"]["ops"], dump["w0"], dump["w1"]),
        rel=0.02)
    assert sum(b - a for a, b in red.gaps) == \
        pytest.approx(red.window_s - red.busy_s)
    assert 0 < red.idle_share < 1


def test_time_per_operation_adds_up(dump):
    red = P.reduce(dump["events"], dump["w0"], dump["w1"])
    leaf = P.leaves(dump["events"]["ops"])
    total = sum(min(dump["w1"], s + d) - max(dump["w0"], s)
                for _, s, d, _ in leaf
                if s + d > dump["w0"] and s < dump["w1"])
    assert sum(red.op_time.values()) == pytest.approx(total)
    # leaf operations barely overlap, so their time is the busy time
    assert total == pytest.approx(red.busy_s, rel=1e-3)
    assert any(k.startswith("step_fn:paged_decode_attention_q8")
               for k in red.op_time)
    assert not any(":while" in k for k in red.op_time)
    br = P.breakdown(red, dump["tracer"])
    assert 0 < len(br["device_ops"]) <= 10 and len(br["idle_gaps"]) <= 10
    assert sum(s for _, s in br["idle_gaps"]) <= red.window_s - red.busy_s \
        + 1e-9
    assert all(s > 0 for _, s in br["device_ops"] + br["idle_gaps"])


def test_step_readers_on_the_recorded_stretch(dump):
    cell = load_cell("unimo-text.offline-batch")
    red = P.reduce(dump["events"], dump["w0"], dump["w1"])

    class Run:
        reduction = red
        dims = cell.model.dims(cell.config)
        engine = cell.config["engine"]
        peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

        def events(self, kind):
            return [e for e in dump["tracer"] if e["kind"] == kind]

    run = Run()
    assert readers.Executions(run).items, "no step program was matched"
    mfu = readers.mfu_pct(run)
    assert 0 < mfu < 100
    roof = readers.quant_matmul_roofline_pct(run)
    assert roof is None or 0 < roof <= 105
    assert 0 < readers.idle_share_pct(run) < 100


def test_a_loop_operation_is_not_a_leaf():
    ops = [["while.1", 0.0, 1.0, 0], ["fusion.2", 0.1, 0.2, 0],
           ["quant_matmul.3", 0.4, 0.5, 0], ["copy.4", 1.2, 0.1, 0],
           ["while.1", 0.0, 1.0, 1], ["fusion.2", 0.5, 0.1, 1]]
    assert [(o[0], o[3]) for o in P.leaves(ops)] == [
        ("fusion.2", 0), ("quant_matmul.3", 0), ("copy.4", 0),
        ("fusion.2", 1)]
    red = P.reduce({"ops": ops, "modules": [["jit_step_fn(7)", 0.0, 1.0,
                                              0]]}, 0.0, 2.0)
    assert red.op_time == pytest.approx({
        "step_fn:fusion": 0.2, "step_fn:quant_matmul": 0.5,
        "no program:copy": 0.1, "no program:fusion": 0.1})
    # busy time is averaged over the two devices: (1.1 + 1.0) / 2
    assert red.busy_s == pytest.approx(1.05)


def test_no_trace_reads_nothing():
    class Run:
        reduction = None

        def events(self, kind):
            return []

    assert readers.mfu_pct(Run()) is None
    assert readers.idle_share_pct(Run()) is None
    assert readers.quant_matmul_roofline_pct(Run()) is None
