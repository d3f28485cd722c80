"""Jit'd wrappers + runtime dispatch for the Pallas kernels.

Kernel modes:
  * "off"       — pure-jnp paths only (the default off the TPU; also the
                  dry-run lowering path so cost_analysis sees real HLO
                  FLOPs).
  * "interpret" — Pallas kernels in interpret mode (CPU correctness
                  runs).  Only ever set explicitly.
  * "tpu"       — compiled Pallas kernels (the default when JAX's
                  backend is the TPU).

The mode is read while a function is traced, so jitted functions keep
the mode they were first traced under: compare modes with freshly built
engines, not with one engine's cached functions.

With kernels on, every dispatch is counted while it is traced: a
kernel that ran in :func:`kernel_counts`, and a kernel that could not
take the shapes it was given — the jnp reference then runs instead,
with one warning per kernel and shape — in :func:`fallback_counts`.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Dict, Optional

MODES = ("off", "interpret", "tpu")

_MODE: Optional[str] = None          # None: follow the backend
_KERNELS: Dict[str, int] = {}        # kernel -> traced dispatches
_FALLBACKS: Dict[str, int] = {}      # kernel -> traced shape misses
_WARNED: set = set()                 # (kernel, shapes) already warned


def kernel_mode() -> str:
    if _MODE is not None:
        return _MODE
    import jax
    return "tpu" if jax.default_backend() == "tpu" else "off"


def set_kernel_mode(mode: Optional[str]) -> None:
    """Pin the kernel mode; ``None`` makes it follow the backend again."""
    global _MODE
    if mode is not None and mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; one of {MODES}")
    _MODE = mode


@contextlib.contextmanager
def kernel_mode_ctx(mode: Optional[str]):
    prev = _MODE
    set_kernel_mode(mode)
    try:
        yield
    finally:
        set_kernel_mode(prev)


def kernel_counts() -> Dict[str, int]:
    """Per-kernel count of traced calls that ran the Pallas kernel."""
    return dict(_KERNELS)


def fallback_counts() -> Dict[str, int]:
    """Per-kernel count of traced calls that fell back to the jnp
    reference with kernels on, because the kernel could not take the
    shapes (zero on a path that runs every kernel it should)."""
    return dict(_FALLBACKS)


def reset_counts() -> None:
    _KERNELS.clear()
    _FALLBACKS.clear()
    _WARNED.clear()


def _ran(kernel: str, out):
    _KERNELS[kernel] = _KERNELS.get(kernel, 0) + 1
    return out


def _fallback(kernel: str, *arrays) -> None:
    """Record a kernel's shape miss; the caller then returns None and
    its jnp reference runs instead."""
    _FALLBACKS[kernel] = _FALLBACKS.get(kernel, 0) + 1
    shapes = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
    if (kernel, shapes) not in _WARNED:
        _WARNED.add((kernel, shapes))
        warnings.warn(f"{kernel}: shapes {shapes} not supported by the "
                      f"Pallas kernel; running the jnp reference")
    return None


# ---------------------------------------------------------------------------
# Dispatchers (return None -> caller falls back to the jnp reference)
# ---------------------------------------------------------------------------


def maybe_flash_attention(q, k, v, q_pos, k_pos, *, window, scale,
                          attn_softcap=None):
    mode = kernel_mode()
    if mode == "off" or q.shape[1] < 2:     # one query: not a prefill shape
        return None
    from repro.kernels import flash_attention as FA
    if not FA.shape_supported(q, k):
        return _fallback("flash_attention", q, k)
    return _ran("flash_attention", FA.flash_attention(
        q, k, v, q_pos, k_pos, window=window, scale=scale,
        attn_softcap=attn_softcap, interpret=(mode == "interpret")))


def maybe_decode_attention(q, k, v, k_pos, q_pos, *, window, scale,
                           attn_softcap=None):
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import decode_attention as DA
    if not DA.shape_supported(q, k):
        return _fallback("decode_attention", q, k)
    return _ran("decode_attention", DA.decode_attention(
        q, k, v, k_pos, q_pos, window=window, scale=scale,
        attn_softcap=attn_softcap, interpret=(mode == "interpret")))


def maybe_paged_decode_attention(q, kpool, vpool, ppos, block_tables, q_pos,
                                 *, window, scale, attn_softcap=None,
                                 k_scale=None, v_scale=None, layer=None):
    """Single-query paged attention; with ``layer`` the pools are whole
    layer stacks read in place at that layer."""
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import decode_attention as DA
    if not DA.paged_shape_supported(q, kpool, block_tables):
        return _fallback("paged_decode_attention", q, kpool, block_tables)
    if k_scale is not None:
        # int8 pool: dequantization fused into the page stream
        return _ran("paged_decode_attention_q8",
                    DA.paged_decode_attention_q8(
                        q, kpool, k_scale, vpool, v_scale, ppos,
                        block_tables, q_pos, layer, window=window,
                        scale=scale, attn_softcap=attn_softcap,
                        interpret=(mode == "interpret")))
    return _ran("paged_decode_attention", DA.paged_decode_attention(
        q, kpool, vpool, ppos, block_tables, q_pos, layer, window=window,
        scale=scale, attn_softcap=attn_softcap,
        interpret=(mode == "interpret")))


def maybe_paged_mixed_attention(q, kpool, vpool, ppos, block_tables, q_pos,
                                *, window, scale, attn_softcap=None,
                                k_scale=None, v_scale=None, layer=None):
    """Multi-query paged attention with per-slot variable query counts:
    q (B, W, Hq, D) / q_pos (B, W) score a whole per-slot window —
    prefill chunk, speculation window, or a lone decode token — in one
    kernel pass; q_pos == -1 marks padding queries (zero outputs)."""
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import decode_attention as DA
    if not DA.paged_mixed_shape_supported(q, kpool, block_tables):
        return _fallback("paged_mixed_attention", q, kpool, block_tables)
    if k_scale is not None:
        return _ran("paged_mixed_attention_q8",
                    DA.paged_mixed_attention_q8(
                        q, kpool, k_scale, vpool, v_scale, ppos,
                        block_tables, q_pos, layer, window=window,
                        scale=scale, attn_softcap=attn_softcap,
                        interpret=(mode == "interpret")))
    return _ran("paged_mixed_attention", DA.paged_mixed_attention(
        q, kpool, vpool, ppos, block_tables, q_pos, layer, window=window,
        scale=scale, attn_softcap=attn_softcap,
        interpret=(mode == "interpret")))


# speculative verify = the mixed dispatch with every row's window full
maybe_paged_verify_attention = maybe_paged_mixed_attention


def maybe_paged_packed_attention(q, kpool, vpool, ppos, block_tables,
                                 q_pos, meta, *, window, scale,
                                 attn_softcap=None, k_scale=None,
                                 v_scale=None, layer=None):
    """Token-packed ragged paged attention: q (1, T, Hq, D) is one flat
    stream covering every slot's decode token and prefill-chunk tokens;
    ``meta`` is the (n_work, 4) query-window table from
    ``decode_attention.packed_meta_table``.  q_pos == -1 marks padding
    lanes (zero outputs)."""
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import decode_attention as DA
    if meta is None or not DA.paged_packed_shape_supported(
            q, kpool, block_tables):
        return _fallback("paged_packed_attention", q, kpool, block_tables)
    if k_scale is not None:
        return _ran("paged_packed_attention_q8",
                    DA.paged_packed_attention_q8(
                        q, kpool, k_scale, vpool, v_scale, ppos,
                        block_tables, q_pos, meta, layer, window=window,
                        scale=scale, attn_softcap=attn_softcap,
                        interpret=(mode == "interpret")))
    return _ran("paged_packed_attention", DA.paged_packed_attention(
        q, kpool, vpool, ppos, block_tables, q_pos, meta, layer,
        window=window, scale=scale, attn_softcap=attn_softcap,
        interpret=(mode == "interpret")))


def maybe_quant_matmul(x, q, s):
    """Weight-quantized matmul dispatch: x (..., K) float activations
    against int8 codes q (K, N) + per-output-channel fp32 scales s (N,)
    (see ``precision.quantize_weights``).  Returns fp32 (..., N), or
    None -> caller falls back to ``ref.quant_matmul_ref``."""
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import quant_matmul as QM
    if not QM.shape_supported(x, q, s):
        return _fallback("quant_matmul", x, q, s)
    return _ran("quant_matmul",
                QM.quant_matmul(x, q, s, interpret=(mode == "interpret")))


def maybe_expert_matmul(lhs, rhs, group_sizes, layer=None):
    """Grouped matmul of the expert layer: rows of lhs (m, K) sorted by
    group, times their group's matrix of rhs (G, K, N), or of layer
    ``layer`` of a whole stack rhs (L, G, K, N).  Returns fp32 (m, N),
    or None -> caller falls back to ``jax.lax.ragged_dot``."""
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import expert_matmul as EM
    if not EM.shape_supported(lhs, rhs):
        return _fallback(EM.KERNEL_NAME, lhs, rhs)
    return _ran(EM.KERNEL_NAME, EM.expert_matmul(
        lhs, rhs, group_sizes, layer, interpret=(mode == "interpret")))


def maybe_rmsnorm(x, w):
    mode = kernel_mode()
    if mode == "off":
        return None
    from repro.kernels import rmsnorm as RN
    if not RN.shape_supported(x):
        return _fallback("fused_rmsnorm", x)
    return _ran("fused_rmsnorm",
                RN.fused_rmsnorm(x, w, interpret=(mode == "interpret")))


def maybe_mlstm_chunked(q, k, v, i_pre, logf, state):
    mode = kernel_mode()
    if mode == "off":
        return None
    if mode == "tpu":
        raise NotImplementedError(
            "mlstm_chunk has no TPU lowering: its (1, 1, L) gate blocks "
            "break the TPU tiling rule.  Serve xLSTM models with "
            "kernel_mode_ctx('off').")
    from repro.kernels import mlstm_chunk as MC
    if not MC.shape_supported(q):
        return _fallback("mlstm_chunk", q)
    return _ran("mlstm_chunk", MC.mlstm_chunked_kernel(
        q, k, v, i_pre, logf, state, interpret=(mode == "interpret")))
