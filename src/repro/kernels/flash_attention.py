"""Flash attention Pallas TPU kernel (prefill / train phase).

The paper's FasterTransformer fuses the attention phases to avoid HBM
round-trips of the S^2 score matrix; the TPU-native realization is the
flash algorithm with VMEM-resident running softmax state:

  grid = (B, Hq, num_q_blocks, num_k_blocks)   (k innermost, sequential)

Operands are laid out head-major, (B, H, S, D), inside the wrapper.
Each step streams one (block_q x D) query tile and one (block_k x D) KV
tile HBM->VMEM, updates the running (max, denom, accumulator) scratch, and
writes the output tile once on the last k step.  GQA is handled with zero
data movement: the k/v BlockSpec index_map folds the q-head index onto its
kv head (h // group).  block_q/block_k default 128.

Masking is position-driven (absolute q_pos/k_pos arrays, -1 = invalid),
covering causal, sliding-window, ragged right-padding and ring caches with
one code path — identical semantics to ``ref.flash_attention_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def shape_supported(q, k, block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K) -> bool:
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if Sq < 2:                       # decode shape -> decode kernel
        return False
    return (Hq % Hkv == 0
            and D % 8 == 0 and k.shape[3] % 8 == 0
            and Sq % min(block_q, Sq) == 0
            and Sk % min(block_k, Sk) == 0)


def _kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, attn_softcap, window, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, Dv)
    qp = qp_ref[0]                                         # (bq, 1)
    kp = kp_ref[0]                                         # (1, bk)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (bq, bk)
    if attn_softcap is not None:
        logits = jnp.tanh(logits / attn_softcap) * attn_softcap
    mask = (kp <= qp) & (kp >= 0)
    if window is not None:
        mask &= kp > (qp - window)
    logits = jnp.where(mask, logits, -jnp.inf)

    m_prev = m_scr[...]                                    # (bq, 1)
    m_blk = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.exp(logits - m_safe)
    p = jnp.where(mask, p, 0.0)

    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "attn_softcap", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, q_pos, k_pos, *, window: Optional[int],
                    scale: float, attn_softcap: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    g = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq, nk = Sq // bq, Sk // bk

    # head-major operands: each block is one head's (tokens, D) tile, so
    # its last two dims are (bq|bk, D) as the TPU tiling rule wants;
    # query positions sit on the sublane axis and key positions on the
    # lane axis, which is how the (bq, bk) mask broadcasts them
    grid = (B, Hq, nq, nk)
    kernel = functools.partial(_kernel, scale=scale,
                               attn_softcap=attn_softcap, window=window,
                               nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, iq, ik, g=g: (b, h // g, ik, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik: (b, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), q_pos.reshape(B, Sq, 1),
      k_pos.reshape(B, 1, Sk))
    return out.transpose(0, 2, 1, 3)
