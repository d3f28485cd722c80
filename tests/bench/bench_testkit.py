"""Helpers for the benchmark's CPU tests: a cell shrunk to a size a CPU
holds, run through the same harness the chip runs."""
from __future__ import annotations

import copy
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
for p in (os.path.join(REPO, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import harness  # noqa: E402
from benchlib.cell import load_cell  # noqa: E402

# the program's reduced configurations, in each configuration file's keys
SMALL_MODEL = {
    "unimo-text": dict(hidden_size=256, num_attention_heads=4,
                       intermediate_size=512, vocab_size=1600,
                       num_hidden_layers=2, max_position_embeddings=128),
}
# at this size sound runs read widest gaps of 0 to 0.004, the int4
# control 0.26
SMALL_GAP_LIMIT = 0.015


def small(cell):
    """Shrink a loaded cell in place: the program's reduced model, four
    slots, a 64-token budget, and short prompts and answers."""
    model = {**cell.config, **SMALL_MODEL[cell.config_name]}
    engine = dict(cell.config["engine"], slots=4, max_batched_tokens=64,
                  max_len=128, num_pages=64)
    mix = copy.deepcopy(cell.mix)
    mix["prompt_tokens"].update(min=8, max=100)
    mix["output_tokens"].update(min=4, max=20)
    mix["rate_per_s" if mix["arrival"] == "poisson" else "backlog_per_s"] = 4
    cell.mix = mix
    cell.check = dict(cell.check, logit_gap_max=SMALL_GAP_LIMIT)
    return model, engine


def run_small(name: str, seed: int = 12345678901, *, cell=None,
              seconds: float = 3.0, **kw) -> dict:
    """One run of cell ``name`` (or of ``cell``) at the small size on the
    CPU, without the persistent compile cache."""
    cell = cell or load_cell(name)
    model, engine = small(cell)
    keep = harness.enable_compile_cache
    harness.enable_compile_cache = lambda: None
    try:
        return harness.run_cell(cell, seed=seed, seconds=seconds,
                                trace=False, t_start=time.perf_counter(),
                                require_tpu=False, reduced=True,
                                model_json=model, engine_json=engine, **kw)
    finally:
        harness.enable_compile_cache = keep
