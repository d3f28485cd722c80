"""Pieces of the plain float32 references in ``bench/configs/<name>.py``.

Straightforward ``jax.numpy`` at highest matmul precision, one sequence
at a time, with no kernels, cache or batching.  Attention and the
unembedding run over blocks of query rows so that a full-width model
with a long sequence fits one chip; the blocks change what is held in
memory at once, not the arithmetic of any row.

Lower precisions, for the controls and for configurations that state
them, are round trips: symmetric absmax quantization to ``bits`` and
back, per output channel for a weight (in, out) and per token and head
for keys and values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 256


def mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def round_trip(x, bits, axis):
    """Quantize ``x`` to signed ``bits``-bit codes with one absmax scale
    per slice along ``axis`` (codes in [-qmax, qmax]) and dequantize."""
    if bits is None:
        return x
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-30)), -qmax, qmax)
    return q * scale


def weight(w, bits):
    """A stored weight (in, out) as float32, through ``bits`` if given."""
    return round_trip(w.astype(jnp.float32), bits, axis=-2)


def layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def causal_attention(q, k, v, scale):
    """q: (S, Hq, D), k/v: (S, Hkv, D) -> (S, Hq, D), computed over
    blocks of ROW_BLOCK query rows (S is a multiple of it)."""
    S, Hq, D = q.shape
    g = Hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * ROW_BLOCK, ROW_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        qpos = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // ROW_BLOCK))
    return out.reshape(S, Hq, D)


def logit_stats(h, head, targets):
    """Per row of the final hidden states h (S, d) against the unembedding
    head (d, V): the largest logit, its token, and the logits of
    ``targets`` (S, K)."""
    S = h.shape[0]

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * ROW_BLOCK, ROW_BLOCK, 0)
        tb = jax.lax.dynamic_slice_in_dim(targets, i * ROW_BLOCK, ROW_BLOCK, 0)
        lg = mm(hb, head)
        return (jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32),
                jnp.take_along_axis(lg, tb, axis=-1))

    mx, am, tl = jax.lax.map(block, jnp.arange(S // ROW_BLOCK))
    return (mx.reshape(S), am.reshape(S), tl.reshape(S, targets.shape[1]))
