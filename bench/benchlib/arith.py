"""Operations and bytes of the served model's matrix products, from shapes,
and the table of chip peaks they are held against.

``dims`` is what a configuration's ``dims()`` returns: d, L, Hq, Hkv, D,
ff, ffn_mats (3 for a gated feed-forward, 2 otherwise) and V.
"""
from __future__ import annotations

import os
from typing import List, Tuple

from benchlib.cell import BENCH_DIR, read_json


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in ``bench/peaks.json`` is an error, not a default."""
    table = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["chips"][device_kind]


def layer_matmuls(m: dict) -> List[Tuple[int, int]]:
    """(K, N) of every weight matrix one token meets in one layer."""
    q, kv = m["Hq"] * m["D"], m["Hkv"] * m["D"]
    ffn = [(m["d"], m["ff"])] * (m["ffn_mats"] - 1) + [(m["ff"], m["d"])]
    return [(m["d"], q), (m["d"], kv), (m["d"], kv), (q, m["d"])] + ffn


def matmul_flops(m: dict, tokens: int, sampled: int) -> float:
    """Operations of the weight matrix products for ``tokens`` tokens
    through every layer and ``sampled`` rows through the unembedding.
    Attention's score and value products are not counted."""
    per_token = sum(k * n for k, n in layer_matmuls(m)) * m["L"]
    return 2.0 * (tokens * per_token + sampled * m["d"] * m["V"])


def quant_matmul_calls(m: dict, rows: int, sampled: int):
    """(M, K, N) of every int8-weight matrix product one forward makes
    over ``rows`` rows, with ``sampled`` rows through the unembedding."""
    calls = [(rows, k, n) for k, n in layer_matmuls(m)] * m["L"]
    return calls + [(sampled, m["d"], m["V"])]


def quant_matmul_cost(M: int, K: int, N: int) -> Tuple[float, float]:
    """(operations, bytes) of one call as the kernel is handed it:
    float32 activations in and out, int8 codes, one float32 scale per
    output column."""
    return 2.0 * M * K * N, float(4 * M * K + K * N + 4 * N + 4 * M * N)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the bf16 peak and bytes over the memory bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
