"""Arithmetic the metric readers in ``bench/metrics/`` share.

A reader returns None where its run gives it nothing to read (no trace,
no execution of the program it reads, no kernel call), and the harness
then leaves its metric out of the result line.
"""
from __future__ import annotations

import bisect
import math
from typing import List, Optional, Tuple

from benchlib import arith

PACKED, DECODE = "packed_fn", "step_fn"   # the step programs' names
STEPS_PER_SYNC = 4                        # decode steps per fused call


def nearest_rank(values, q: float) -> Optional[float]:
    """The q-quantile by nearest rank (inf counts as a value)."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(0, math.ceil(round(q * len(v), 9)) - 1)])


def ttft(run) -> List[float]:
    """Seconds from each request's due time to its first token; inf for
    a request that never had one."""
    first = {e["uid"]: e["t"] for e in run.events("first_token")}
    return [first.get(it.uid, float("inf")) - it.arrival for it in run.items]


def busy_iterations(run) -> List[dict]:
    return [e for e in run.events("iteration") if not e["idle"]]


def host_ms_per_iter(run) -> Optional[float]:
    it = busy_iterations(run)
    return 1000.0 * sum(e["host_s"] for e in it) / len(it) if it else None


def idle_share_pct(run) -> Optional[float]:
    red = run.reduction
    return None if red is None else 100.0 * red.idle_share


class Executions:
    """Each execution of the step programs in the profiled stretch, with
    the serve-loop iteration it belongs to (the tracer's dispatch span
    that holds it, and the iteration record that holds the span)."""

    def __init__(self, run):
        spans = sorted((e["t"], e["t"] + e["dur"])
                       for e in run.events("span"))
        iters = sorted((e["t"], e["t"] + e["dur"], i) for i, e in
                       enumerate(run.events("iteration")))
        records = run.events("iteration")
        self.items: List[Tuple[list, dict]] = []
        red = run.reduction
        if red is None:
            return
        for m in red.modules:
            if PACKED not in m[0] and DECODE not in m[0]:
                continue
            mid = m[1] + 0.5 * m[2]
            i = bisect.bisect_right(spans, (mid, float("inf"))) - 1
            if i < 0 or not spans[i][0] <= mid <= spans[i][1]:
                continue
            j = bisect.bisect_right(iters, (spans[i][0], float("inf"))) - 1
            if j >= 0 and iters[j][0] <= spans[i][0] <= iters[j][1]:
                self.items.append((m, records[iters[j][2]]))


def step_shape(module: list, it: dict, slots: int, steps: int):
    """(real tokens, sampled rows, rows per kernel call, forwards) of one
    execution: a packed forward carries the iteration's decode lanes and
    chunk tokens in a stream of its width bucket and samples one row per
    slot; a fused decode step runs every slot ``steps`` times over."""
    if PACKED in module[0]:
        return (it["budget_used"], it["decode_lanes"] + it["chunk_segments"],
                it["width_bucket"], 1)
    return it["decode_lanes"] * steps, it["decode_lanes"] * steps, slots, steps


def mfu_pct(run) -> Optional[float]:
    """Weight-matmul operations of the real tokens the step programs
    processed in the stretch, over their device time at the bf16 peak."""
    ex = Executions(run)
    if not ex.items:
        return None
    flops = secs = 0.0
    for m, it in ex.items:
        tokens, sampled, _, _ = step_shape(m, it, run.engine["slots"],
                                           STEPS_PER_SYNC)
        flops += arith.matmul_flops(run.dims, tokens, sampled)
        secs += m[2]
    return 100.0 * flops / (secs * run.peak["bf16_flops_per_s"])


def quant_matmul_roofline_pct(run) -> Optional[float]:
    """The least time every int8-weight matmul call of the stretch's step
    programs could take, over the time the calls took."""
    ex = Executions(run)
    if not ex.items:
        return None
    ideal = took = 0.0
    slots = run.engine["slots"]
    for m, it in ex.items:
        calls = run.reduction.ops_within(m, "quant_matmul")
        if not calls:
            continue
        _, _, rows, forwards = step_shape(m, it, slots, STEPS_PER_SYNC)
        for M, K, N in arith.quant_matmul_calls(run.dims, rows, slots) \
                * forwards:
            ideal += arith.roofline_seconds(
                *arith.quant_matmul_cost(M, K, N), run.peak)
        took += sum(o[2] for o in calls)
    return 100.0 * ideal / took if took else None
