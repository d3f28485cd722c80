"""Mixture-of-Experts FFN (DeepSeek-V3 / Qwen3-MoE style).

Two dispatches share one router:

* serving (``moe_apply_dropless``): no capacity and no drops.  The
  token-expert assignments are sorted by expert and each expert matrix
  is one grouped matrix product over the sorted rows (the Pallas
  grouped matmul on the chip, ``lax.ragged_dot`` elsewhere), so a
  token's output is the same whatever else is in the batch.
* training (``moe_apply_capacity``): sort-based with static capacity
  (GShard-style), an expert-batched einsum ``ecd,edf->ecf`` whose
  expert axis shards over the ``model`` mesh axis (expert parallelism).

Router options: softmax top-k (Qwen3-MoE) or sigmoid scores normalized over
the selected top-k (DeepSeek-V3), plus optional shared experts and the
standard load-balance auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def moe_init(rng, cfg: ModelConfig):
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(rng, 6)

    def expert_stack(rng_key, i, o):
        keys = jax.random.split(rng_key, E)
        return jax.vmap(lambda k: L.dense_init(k, i, o))(keys)

    p = {
        "router": L.dense_init(ks[0], d, E),
        "wi": expert_stack(ks[1], d, f),
        "wg": expert_stack(ks[2], d, f),
        "wo": expert_stack(ks[3], f, d),
    }
    if m.num_shared_experts:
        p["shared"] = L.ffn_init(ks[4], cfg, m.num_shared_experts * f)
    return p


def _capacity(num_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(num_tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8, >= 8


def _route(cfg: ModelConfig, p, xf, router_kind: str):
    """Router: (T, d) -> (top-k weights (T, k) float32, experts (T, k),
    probabilities the load-balance loss reads (T, E))."""
    k = cfg.moe.top_k
    scores = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if router_kind == "sigmoid":                     # DeepSeek-V3
        probs = jax.nn.sigmoid(scores)
        topw, tope = jax.lax.top_k(probs, k)
        lb_probs = probs / (probs.sum(-1, keepdims=True) + 1e-9)
    else:                                            # softmax (Qwen3-MoE)
        probs = jax.nn.softmax(scores, axis=-1)
        topw, tope = jax.lax.top_k(probs, k)
        lb_probs = probs
    topw = topw / (topw.sum(-1, keepdims=True) + 1e-9)
    return topw, tope, lb_probs


def no_counts():
    """Routing counters of a forward that routed nothing."""
    return jnp.zeros((3,), jnp.int32)


def merge_counts(a, b):
    """Counters of two dispatches together: rows and experts hit add up,
    the largest expert's rows is the larger of the two."""
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2])])


# the expert matrices of a layer, stacked (E, in, out); serving passes
# them whole over the layers, (L, E, in, out), with the layer's index
EXPERT_MATS = ("wi", "wg", "wo")


def grouped_matmul(lhs, rhs, group_sizes, layer=None):
    """Rows of ``lhs`` (m, K) sorted by group times their group's matrix
    of ``rhs`` (G, K, N), in float32; rows past ``sum(group_sizes)``
    belong to no group and their output is undefined.  With ``layer``,
    ``rhs`` is the whole stack (L, G, K, N) and layer ``layer``'s
    matrices are used."""
    from repro.kernels import ops as kops
    if layer is not None and rhs.dtype != lhs.dtype:
        # a cast copies anyway: the layer's matrices alone
        rhs = jax.lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
        layer = None
    rhs = rhs.astype(lhs.dtype)
    out = kops.maybe_expert_matmul(lhs, rhs, group_sizes, layer)
    if out is None:
        if layer is not None:
            rhs = jax.lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
    return out


def moe_apply_dropless(cfg: ModelConfig, p, x, router_kind: str = "softmax",
                       valid=None):
    """The serving dispatch: x (B, S, d) -> (out (B, S, d), counters).

    Every token goes to each of its top-k experts; nothing is dropped, so
    a row's output does not depend on what else is in the batch.  The
    token-expert assignments are sorted by expert and each expert matrix
    is one grouped matrix product over its rows (``grouped_matmul``).
    ``p`` holds one layer's expert stacks (E, in, out), or the whole
    stacks (L, E, in, out) and that layer's index ``p["layer"]``.
    ``valid`` (broadcastable to (B, S)) marks real tokens: the others
    (padding lanes, inactive slots) are routed to no expert, add no rows
    and output zero.  ``counters`` is int32 [assignments, experts with at
    least one, the most one expert took].
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.num_experts, m.top_k
    xf = x.reshape(T, d)
    topw, tope, _ = _route(cfg, p, xf, router_kind)
    if valid is not None:
        keep = jnp.broadcast_to(valid, (B, S)).reshape(T, 1)
        tope = jnp.where(keep, tope, E)              # E: no expert
    e_flat = tope.reshape(-1)
    order = jnp.argsort(e_flat)
    sizes = jnp.bincount(e_flat, length=E).astype(jnp.int32)
    rows = xf[order // k]                                         # (T*k, d)
    layer = p.get("layer")
    h = grouped_matmul(rows, p["wi"], sizes, layer)
    if cfg.activation in ("swiglu", "geglu"):
        g = grouped_matmul(rows, p["wg"], sizes, layer)
        act = jax.nn.silu if cfg.activation == "swiglu" else \
            (lambda t: jax.nn.gelu(t, approximate=True))
        h = act(g) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    eo = grouped_matmul(h.astype(x.dtype), p["wo"], sizes, layer)
    # back to token order: each token's k outputs, weighted and summed;
    # rows of no expert hold undefined values and are selected away
    eo = eo[jnp.argsort(order)].reshape(T, k, d)
    eo = jnp.where((tope < E)[..., None], eo * topw[..., None], 0.0)
    out = eo.sum(1)
    if "shared" in p:
        out = out + L.ffn_apply(cfg, p["shared"], xf).astype(jnp.float32)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
    return out.astype(x.dtype).reshape(B, S, d), counts.astype(jnp.int32)


def moe_apply_capacity(cfg: ModelConfig, p, x,
                       router_kind: str = "softmax"):
    """The training dispatch: x (B, S, d) -> (out (B,S,d), aux_loss
    scalar).  Each expert takes at most its capacity of tokens; the
    overflow is dropped, so a token's output depends on the batch."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.num_experts, m.top_k
    C = _capacity(T, cfg)
    xf = x.reshape(T, d)

    topw, tope, lb_probs = _route(cfg, p, xf, router_kind)

    # ---- load-balance aux loss ------------------------------------------
    onehot = jax.nn.one_hot(tope, E, dtype=jnp.float32)           # (T,k,E)
    frac_tokens = onehot.sum((0, 1)) / (T * k)                    # f_e
    mean_prob = lb_probs.mean(0)                                  # P_e
    aux = m.router_aux_coef * E * jnp.sum(frac_tokens * mean_prob)

    # ---- sort-based dispatch --------------------------------------------
    e_flat = tope.reshape(-1)                                     # (T*k,)
    t_flat = jnp.repeat(jnp.arange(T), k)
    w_flat = topw.reshape(-1)
    order = jnp.argsort(e_flat)
    se, st, sw = e_flat[order], t_flat[order], w_flat[order]
    counts = jnp.bincount(e_flat, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * k) - offsets[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)              # overflow slot

    table = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(st.astype(jnp.int32))
    wtab = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(sw)
    table, wtab = table[:-1].reshape(E, C), wtab[:-1].reshape(E, C)

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])     # sentinel row
    gathered = xpad[table]                                        # (E, C, d)

    # ---- expert compute (expert axis shards over `model`) ---------------
    h = jnp.einsum("ecd,edf->ecf", gathered, p["wi"].astype(x.dtype))
    if cfg.activation in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", gathered, p["wg"].astype(x.dtype))
        act = jax.nn.silu if cfg.activation == "swiglu" else \
            (lambda t: jax.nn.gelu(t, approximate=True))
        h = act(g) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    eo = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))   # (E, C, d)

    # ---- combine ----------------------------------------------------------
    eo = eo * wtab[..., None].astype(eo.dtype)
    out = jnp.zeros((T + 1, d), eo.dtype).at[table.reshape(-1)].add(
        eo.reshape(-1, d))[:T]

    if "shared" in p:
        out = out + L.ffn_apply(cfg, p["shared"], xf)
    return out.reshape(B, S, d), aux
