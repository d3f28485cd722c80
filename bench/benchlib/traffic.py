"""One generator for every traffic mix: a mix is a data file of parameters.

A mix file (``bench/traffic/<name>.json``) gives the arrival process and
the length distributions:

    {"arrival": "poisson", "rate_per_s": 2.0,
     "prompt_tokens": {"median": 1024, "sigma": 0.8, "min": 256, "max": 6144},
     "output_tokens": {"median": 128, "sigma": 0.7, "min": 32, "max": 512}}

``arrival`` is ``poisson`` (open loop at ``rate_per_s``) or ``backlog``
(``backlog_per_s`` requests per second of window, all queued at t=0 with
the window's end as their deadline).  Lengths are lognormal with the
given median and log-space ``sigma``, clipped to [min, max].

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps: they are the distributions' quantiles at
(i + 0.5) / n.  The seed only shuffles them (independently, so it also
changes which prompt goes with which output) and draws the token ids.
So runs on different seeds do the same amount of work, in another order.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

FIRST_ID = 4          # ids 0-3 are PAD, UNK, BOS, EOS; prompts use none


@dataclass
class Item:
    """One request as the benchmark sends it."""
    uid: int
    prompt: np.ndarray            # int32 token ids
    max_new: int
    arrival: float                # seconds after the serve call starts
    deadline: Optional[float]     # same clock; None = no deadline


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named use of the run's seed (any int >= 0)."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return np.random.default_rng(
        np.random.SeedSequence([seed, *stream.encode()]))


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of the clipped lognormal."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def request_count(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "poisson":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    if mix["arrival"] == "backlog":
        return max(1, int(round(mix["backlog_per_s"] * seconds)))
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


def generate(mix: dict, seconds: float, seed: int, vocab: int,
             rate: Optional[float] = None) -> List[Item]:
    """The requests of one run, ordered by arrival.  ``rate`` overrides
    the mix's Poisson rate (used only to sweep for the knee)."""
    if rate is not None:
        mix = {**mix, "rate_per_s": rate}
    n = request_count(mix, seconds)
    rng = rng_for(seed, "traffic")
    prompts = lognormal_quantiles(mix["prompt_tokens"], n)[rng.permutation(n)]
    outputs = lognormal_quantiles(mix["output_tokens"], n)[rng.permutation(n)]
    if mix["arrival"] == "poisson":
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / mix["rate_per_s"]
        arrivals = np.cumsum(gaps[rng.permutation(n)])
        deadline = None
    else:
        arrivals = np.zeros(n)
        deadline = float(seconds)
    ids = rng.integers(FIRST_ID, vocab, size=int(prompts.sum()),
                       dtype=np.int64).astype(np.int32)
    items, at = [], 0
    for i in range(n):
        p = int(prompts[i])
        items.append(Item(uid=i, prompt=ids[at:at + p],
                          max_new=int(outputs[i]),
                          arrival=float(arrivals[i]), deadline=deadline))
        at += p
    return items


def longest_context(mix: dict) -> int:
    """The longest prompt plus output the mix can send."""
    return int(mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"])

