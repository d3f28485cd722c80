"""Operations and bytes of the paged attention kernels, from the serve
loop's per-iteration attention counters, and the readers that hold them
against the kernels' device time; and the serve loop's arrival lag.

The program's tracer stamps every iteration record with, per layer,
``attn_kv_tokens`` (KV tokens its attention dispatches read: each
segment reads its slot's context once, through its last query) and
``attn_pairs`` (query-key pairs), and every ``enqueue`` event of an
open-loop call with ``due``, the time its arrival was scheduled for.  A
trace without those fields (an older program) gives these readers
nothing to read: they return None.
"""
from __future__ import annotations

from typing import Optional

from benchlib import arith
from benchlib.readers import DECODE, PACKED, Executions, nearest_rank

# the kernels, as their operations are named in the device trace (the _q8
# variants of the int8 pool included)
PACKED_KERNEL = "paged_packed_attention"
DECODE_KERNEL = "paged_decode_attention"


def kv_bytes_per_token(dims: dict) -> int:
    """Bytes one token occupies in one layer's KV pool at the pool dtype
    the configuration states: key and value codes of every KV head, with
    one float32 scale per head for each under an int8 pool, and the
    int32 position entry."""
    bits = dims.get("kvbits", 16)
    codes = 2 * dims["Hkv"] * dims["D"] * bits // 8
    scales = 2 * dims["Hkv"] * 4 if bits < 16 else 0
    return codes + scales + 4


def attention_cost(dims: dict, kv_tokens: int, pairs: int):
    """(operations, bytes) of one forward's paged attention through every
    layer: a score and a value product per query-key pair and head, and
    each read KV token's pool bytes."""
    L = dims["L"]
    return (4.0 * dims["Hq"] * dims["D"] * pairs * L,
            float(kv_tokens * kv_bytes_per_token(dims) * L))


def roofline_pct(run, program: str, kernel: str) -> Optional[float]:
    """The least time the ``kernel`` calls of every ``program`` execution
    in the stretch could take, from the attention counters of the
    iteration each belongs to, over the device time the calls took."""
    ideal = took = 0.0
    for m, it in Executions(run).items:
        if program not in m[0] or "attn_kv_tokens" not in it:
            continue
        calls = run.reduction.ops_within(m, kernel)
        if not calls:
            continue
        ideal += arith.roofline_seconds(
            *attention_cost(run.dims, it["attn_kv_tokens"], it["attn_pairs"]),
            run.peak)
        took += sum(o[2] for o in calls)
    return 100.0 * ideal / took if took else None


def packed_roofline_pct(run) -> Optional[float]:
    return roofline_pct(run, PACKED, PACKED_KERNEL)


def decode_roofline_pct(run) -> Optional[float]:
    return roofline_pct(run, DECODE, DECODE_KERNEL)


def arrival_lag_p95_s(run) -> Optional[float]:
    """95th percentile of the seconds between a request's due time and
    the serve loop's noticing it (its enqueue), over every request the
    program stamped with a due time."""
    return nearest_rank([e["t"] - e["due"] for e in run.events("enqueue")
                         if e.get("due") is not None], 0.95)
