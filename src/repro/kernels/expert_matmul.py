"""Grouped matmul of the expert layer: Pallas ``megablox.gmm``.

The serving MoE dispatch sorts its token-expert assignments by expert,
so each expert's rows are one contiguous group of the (m, K) input.
``expert_matmul`` multiplies every group by its expert's (K, N) matrix
in one kernel: the grid walks only the row tiles that hold a group, and
each expert's matrix is streamed once per tile its group touches, so an
expert no row chose costs nothing.  Rows past ``sum(group_sizes)`` (the
padding lanes and inactive slots the dispatch routes to no expert) are
never computed; their output is undefined and the caller selects it
away.  Given a whole layer stack (L, G, K, N) and a layer index, the
kernel reads that layer's matrices in place: group ids run over every
layer's experts and the other layers' groups are empty, so a serving
layer scan copies no slice of the stack out for the kernel.

The kernel is the one JAX ships (``jax.experimental.pallas.ops.tpu.
megablox``), called here through one jitted function whose name, and so
the name of its operation in the device trace, is ``expert_matmul``
(``KERNEL_NAME``).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the kernel's module (the package re-exports a custom_vjp of the same
# name over it); its jitted gmm is unwrapped so that the kernel's trace
# operation takes the name of the jit below
_GMM = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__

KERNEL_NAME = "expert_matmul"
# tiles: 128 rows (rows pad up to a multiple), the whole contraction in
# one block, and the widest of 768/512/256/128 output columns that
# divides N.  On a v5e at qwen3-30b-a3b's shapes (128 experts, d 2048,
# width 768, 512-2048 rows) the gate/up matrices ran 0.60-0.67 ms this
# way, against 0.71-0.81 ms with 512-wide contraction blocks and
# 1.88-1.94 ms for lax.ragged_dot (PERF.md section 6).
TILE_M, MAX_TILE_K = 128, 2048


def _tiling(K: int, N: int):
    tk = K if K <= MAX_TILE_K else 512
    tn = next((t for t in (768, 512, 256, 128) if N % t == 0), N)
    return TILE_M, tk, tn


def shape_supported(lhs, rhs) -> bool:
    """Lane-aligned K and N (multiples of 128) with K tiling evenly, in
    a float dtype the kernel takes; rhs (G, K, N) or (L, G, K, N)."""
    K, N = rhs.shape[-2], rhs.shape[-1]
    return (lhs.ndim == 2 and rhs.ndim in (3, 4) and lhs.shape[1] == K
            and K % 128 == 0 and N % 128 == 0 and K % _tiling(K, N)[1] == 0
            and lhs.dtype in (jnp.bfloat16, jnp.float32)
            and rhs.dtype == lhs.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_matmul(lhs, rhs, group_sizes, layer=None, *,
                  interpret: bool = False):
    """lhs (m, K) rows sorted by group, rhs (G, K, N), group_sizes (G,)
    int32 -> (m, N) float32.  With ``layer``, rhs is the whole stack
    (L, G, K, N) and layer ``layer``'s G matrices are read in place."""
    m, K = lhs.shape
    N = rhs.shape[-1]
    group_sizes = group_sizes.astype(jnp.int32)
    if layer is not None:
        n_layers, G = rhs.shape[:2]
        rhs = rhs.reshape(n_layers * G, K, N)
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * G,), jnp.int32), group_sizes,
            (layer * G,))
    mp = -(-m // TILE_M) * TILE_M
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    out = _GMM(lhs, rhs, group_sizes,
               preferred_element_type=jnp.float32, tiling=_tiling(K, N),
               interpret=interpret)
    return out[:m]
