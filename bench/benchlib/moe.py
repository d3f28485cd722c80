"""Operations and bytes of the grouped expert matmul, from the serve
loop's per-iteration routing counters, and the readers that hold them
against the kernel's device time.

The program's tracer stamps every iteration record with ``moe_rows``
(token-expert assignments of real tokens, summed over the iteration's
forwards and expert layers), ``moe_experts_hit`` (experts given at
least one row, summed the same way) and ``moe_rows_max``.  A trace
without those fields, or a model without experts, gives these readers
nothing to read: they return None.
"""
from __future__ import annotations

from typing import Optional

from benchlib import arith
from benchlib.readers import Executions, busy_iterations

# the kernel, as its operation is named in the device trace
KERNEL = "expert_matmul"
MATS = 3                    # a SwiGLU expert: gate, up and down matrices


def expert_matmul_cost(dims: dict, rows: int, experts_hit: int):
    """(operations, bytes) of the expert matmuls of ``rows`` token-expert
    rows over ``experts_hit`` expert layers: each row meets the three
    d x f matrices of its expert; each expert hit streams its three
    bfloat16 matrices once, and each row reads its bfloat16 input and
    writes its bfloat16 output (4 d bytes)."""
    d, f = dims["d"], dims["f"]
    return (2.0 * rows * MATS * d * f,
            float(experts_hit * MATS * d * f * 2 + rows * 4 * d))


def expert_matmul_roofline_pct(run) -> Optional[float]:
    """The least time the expert matmul calls of every step-program
    execution in the stretch could take, from the routing counters of
    the iteration each belongs to, over the device time the calls
    took."""
    ideal = took = 0.0
    for m, it in Executions(run).items:
        if not it.get("moe_rows"):
            continue
        calls = run.reduction.ops_within(m, KERNEL)
        if not calls:
            continue
        ideal += arith.roofline_seconds(
            *expert_matmul_cost(run.dims, it["moe_rows"],
                                it["moe_experts_hit"]), run.peak)
        took += sum(o[2] for o in calls)
    return 100.0 * ideal / took if took else None


def rows_per_expert(run) -> Optional[float]:
    """Token-expert rows per expert hit over the window's busy
    iterations: how many rows share each streaming of an expert's
    weights."""
    it = busy_iterations(run)
    hit = sum(e.get("moe_experts_hit", 0) for e in it)
    return sum(e.get("moe_rows", 0) for e in it) / hit if hit else None
