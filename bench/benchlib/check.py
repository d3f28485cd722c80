"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
longest one, goes through the configuration's plain reference: each
prompt with the tokens the program served after it, in one forward
pass.  At every served token the gap is the reference's largest logit
minus the reference's logit of the served token: 0 where the program
chose the reference's best, larger the further below it the served
token lies.  The widest gap over the sample is compared with the cell's
limit.  Greedy decoding only: the traffic samples no tokens.

The control puts the reference itself, in the precision below the one
the configuration states, in the program's place: at each position of
the same sequences, the gap of the token the lower precision ranks
first.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import numpy as np

from benchlib import plainref
from benchlib.traffic import rng_for


def padded_len(n: int) -> int:
    """Sequence lengths compile per power of two from one row block up."""
    p = plainref.ROW_BLOCK
    while p < n:
        p *= 2
    return p


def finished(reqs) -> list:
    return [r for r in reqs if r.outcome is not None
            and r.outcome.status in ("completed", "truncated") and r.result]


def sample(reqs, seed: int, k: int) -> list:
    """``k`` finished requests: the one with the longest context and
    ``k - 1`` others drawn from the seed, in uid order."""
    done = sorted(finished(reqs), key=lambda r: r.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens) + len(r.result),
                                       -r.uid))
    rest = [r for r in done if r is not longest]
    pick = rng_for(seed, "sample").choice(len(rest), min(k - 1, len(rest)),
                                          replace=False)
    return sorted([longest] + [rest[i] for i in pick], key=lambda r: r.uid)


def _sequence(req) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(tokens padded to a compiled length, rows whose logits predicted a
    served token, the served tokens)."""
    seq = list(req.tokens) + list(req.result)
    toks = np.zeros(padded_len(len(seq)), np.int32)
    toks[:len(seq)] = seq
    p, n = len(req.tokens), len(req.result)
    return toks, np.arange(p - 1, p + n - 1), np.asarray(req.result, np.int32)


def gaps(model, cfg: dict, params, reqs, control_bits=None) -> dict:
    """Per served token of ``reqs``, the reference's gap; with
    ``control_bits`` (weights, kv) also the control's gap."""
    import jax
    import jax.numpy as jnp
    ref = jax.jit(functools.partial(model.reference_stats, c=cfg))
    low = None
    if control_bits is not None:
        low = jax.jit(functools.partial(model.reference_stats, c=cfg,
                                        wbits=control_bits[0],
                                        kvbits=control_bits[1]))
    out = {"program": [], "control": []}
    for r in reqs:
        toks, rows, served = _sequence(r)
        targets = np.zeros((toks.shape[0], 2), np.int32)
        targets[rows, 0] = served
        if low is not None:
            _, am, _ = low(params, jnp.asarray(toks), jnp.asarray(targets))
            targets[rows, 1] = np.asarray(am)[rows]
        mx, _, tl = (np.asarray(a) for a in
                     ref(params, jnp.asarray(toks), jnp.asarray(targets)))
        out["program"].append(mx[rows] - tl[rows, 0])
        if low is not None:
            out["control"].append(mx[rows] - tl[rows, 1])
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}


def summary(g: np.ndarray) -> dict:
    """The widest gap and, for the record, steadier readings beside it."""
    if g.size == 0:
        return {"max": None, "tokens": 0}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.quantile(g, 0.99)),
            "share_off_best": float(np.mean(g > 0)), "tokens": int(g.size)}


def verdict(checks: List[Tuple[str, float, float]]) -> bool:
    """Correct when every number compared is at or under its limit."""
    return all(v is not None and v <= lim for _, v, lim in checks)
