"""Precision policy — paper pillar P1 (FP16 half-precision inference).

The paper runs FP16 inference on GPU.  On TPU the MXU-native half precision
is bf16, so the *default serving policy* here is bf16-compute; fp16 is kept
selectable for paper fidelity (and is what the Table-1 reproduction
benchmark uses).  A policy is three dtypes:

  * ``param_dtype``   — storage dtype of the weights
  * ``compute_dtype`` — dtype activations/matmuls run in
  * ``output_dtype``  — dtype of logits (kept fp32 for a stable softmax)

plus two *storage* axes for serving:

  * ``kv_dtype``      — "auto" (= compute dtype), "bf16", "fp16", or
    "int8".  int8 stores paged attention K/V pages as int8 with
    per-entry, per-kv-head fp32 absmax scales in parallel scale pools
    (see ``kv_cache``); it halves KV bytes/token vs bf16, doubling the
    effective page-pool capacity and the decode kernel's arithmetic
    intensity.  Layer families with dense per-slot state (MLA,
    recurrent, hybrid) keep full-precision caches — the same families
    that opt out of prefix sharing.
  * ``weights_dtype`` — storage of the dense serve-path matmul weights
    (attention qkv/out projections, dense FFNs, the unembedding head).
    "auto" keeps ``param_dtype``; "int8" quantizes each weight at load
    into int8 codes + per-output-channel fp32 absmax scales
    (:func:`quantize_weights`, the weight-matrix mirror of the KV-pool
    scheme), halving weight bytes read per decode step — the dominant
    traffic of autoregressive decode, where every matmul is
    weight-bound.  Matmuls against quantized records dequantize
    in-register (``kernels/quant_matmul``) or accumulate the int8
    codes in fp32 and apply the scale to the product (the exact
    per-column identity ``x @ (q*s) == (x @ q) * s``) on the jnp
    fallback.  Only structurally dense projections quantize: MLA
    low-rank factors, recurrent mixers, MoE expert stacks, norms and
    the embedding *gather* table keep full precision (tied-embedding
    models get a separate quantized copy of the unembed projection;
    the gather table itself is never quantized).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

KV_DTYPES = ("auto", "bf16", "fp16", "int8")
WEIGHTS_DTYPES = ("auto", "bf16", "fp16", "int8")

# Shared with the KV pool's scheme: symmetric absmax, full [-127, 127]
# code range (never -128, keeping |q| * s <= absmax exactly).
W8_QMAX = 127.0


def kv_store_dtype(kv_dtype: str, compute_dtype, *, allow_int8: bool = True):
    """Resolve a ``Policy.kv_dtype`` name to the cache storage dtype.

    ``allow_int8=False`` is the dense-cache path (no scale arrays live
    beside a dense cache), where int8 falls back to the compute dtype.
    """
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                         f"one of {list(KV_DTYPES)}")
    if kv_dtype == "auto":
        return compute_dtype
    if kv_dtype == "bf16":
        return jnp.bfloat16
    if kv_dtype == "fp16":
        return jnp.float16
    return jnp.int8 if allow_int8 else compute_dtype


def weights_store_dtype(weights_dtype: str, param_dtype):
    """Resolve a ``Policy.weights_dtype`` name to the weight storage dtype."""
    if weights_dtype not in WEIGHTS_DTYPES:
        raise ValueError(f"unknown weights_dtype {weights_dtype!r}; "
                         f"one of {list(WEIGHTS_DTYPES)}")
    if weights_dtype == "auto":
        return param_dtype
    if weights_dtype == "bf16":
        return jnp.bfloat16
    if weights_dtype == "fp16":
        return jnp.float16
    return jnp.int8


# ---------------------------------------------------------------------------
# Weight-only int8 quantization (per-output-channel absmax)
# ---------------------------------------------------------------------------


@jax.jit
def quantize_weights(w):
    """Quantize one dense weight ``w`` (..., in, out) to an int8 record.

    Returns ``{"q": int8 (..., in, out), "s": fp32 (..., out)}`` with
    per-output-channel absmax scales (``s = absmax / 127`` over the
    input dim).  Per-*column* scales make the dequantized matmul an
    exact rescale of the integer product — ``x @ (q * s) == (x @ q) * s``
    column by column — so the fused kernel and the jnp fallback can both
    accumulate codes in fp32 and apply the scale once per output.
    All-zero columns get scale 0 (codes 0) via the epsilon guard, the
    same convention as ``kv_cache.quantize_kv``.  Jitted, so the fp32
    widening fuses into the reduction and the rounding instead of
    materializing a float copy of the weight.
    """
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2)
    scale = amax / W8_QMAX
    q = jnp.clip(jnp.round(wf / jnp.maximum(scale, 1e-30)[..., None, :]),
                 -W8_QMAX, W8_QMAX).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize_weights(rec, dtype=jnp.float32):
    """Inverse of :func:`quantize_weights` (up to the rounding error)."""
    return (rec["q"].astype(jnp.float32)
            * rec["s"][..., None, :]).astype(dtype)


def is_quantized_weight(w) -> bool:
    """True for the ``{"q", "s"}`` records :func:`quantize_weights` makes."""
    return isinstance(w, dict) and set(w) == {"q", "s"}


def _array_bytes(a) -> int:
    return int(a.size) * jnp.dtype(a.dtype).itemsize


def weight_record_bytes(w) -> int:
    """Storage bytes of one serve-path weight (array or quantized record)."""
    if is_quantized_weight(w):
        return _array_bytes(w["q"]) + _array_bytes(w["s"])
    return _array_bytes(w)


# Dense serve-path matmul weights, identified structurally: a GQA
# attention dict carries all four projections (mLSTM has wq/wk/wv but no
# wo; MLA factors use different names), a dense FFN dict carries wi+wo
# without a router (MoE expert stacks are excluded by their router key;
# MoE *shared* experts are a plain dense FFN dict and do quantize).
_ATTN_KEYS = ("wq", "wk", "wv", "wo")
_FFN_KEYS = ("wi", "wg", "wo")


def _walk_serve_weights(node):
    """Yield (dict, key) for every dense serve-path matmul weight."""
    if not isinstance(node, dict):
        if isinstance(node, (tuple, list)):
            for v in node:
                yield from _walk_serve_weights(v)
        return
    if all(k in node for k in _ATTN_KEYS):
        for k in _ATTN_KEYS:
            yield node, k
    elif "wi" in node and "wo" in node and "router" not in node:
        for k in _FFN_KEYS:
            if k in node:
                yield node, k
    for v in node.values():
        yield from _walk_serve_weights(v)


def compress_weights(params, policy: "Policy"):
    """Apply ``policy.weights_dtype`` to the dense serve-path weights.

    Returns ``(params, stats)``.  For "int8", each weight is replaced
    in-place (a copied tree) by its :func:`quantize_weights` record; the
    unembedding head quantizes too — directly for untied models, and as
    a *separate* ``embed["head_q8"]`` copy of the transposed gather
    table for tied models (the gather table itself stays full precision
    for exact embedding lookups; the int8 copy costs a quarter of the
    fp32 table but halves the bytes the unembed matmul reads).  "bf16"/
    "fp16" cast the same weight set; "auto" is a no-op.

    ``stats`` reports the serve-path matmul read traffic:
    ``weight_bytes`` (bytes those matmuls read after compression),
    ``weight_bytes_dense`` (same set before), ``weight_bytes_saved``,
    ``n_quantized``, and the resolved ``weights_dtype`` name.  Call
    AFTER :meth:`Policy.cast_params` — cast_params would recast the
    fp32 scales of an already-quantized tree.
    """
    wd = policy.weights_dtype
    if wd not in WEIGHTS_DTYPES:
        raise ValueError(f"unknown weights_dtype {wd!r}; "
                         f"one of {list(WEIGHTS_DTYPES)}")
    items = list(_walk_serve_weights(params))

    # the unembed projection, as the serve-path matmul reads it
    embed = params.get("embed", {}) if isinstance(params, dict) else {}
    head = embed.get("head")
    tied_tokens = None
    if head is None and "tokens" in embed and "heads" not in embed \
            and getattr(embed["tokens"], "ndim", 0) == 2:
        tied_tokens = embed["tokens"]          # tied single-stream vocab

    dense_bytes = sum(weight_record_bytes(d[k]) for d, k in items)
    if head is not None:
        dense_bytes += weight_record_bytes(head)
    elif tied_tokens is not None:
        dense_bytes += weight_record_bytes(tied_tokens)

    if wd == "auto" or not (items or head is not None
                            or tied_tokens is not None):
        return params, {"weights_dtype": wd, "weight_bytes": dense_bytes,
                        "weight_bytes_dense": dense_bytes,
                        "weight_bytes_saved": 0, "n_quantized": 0}

    if wd == "int8":
        transform = quantize_weights
    else:
        store = weights_store_dtype(wd, policy.param_dtype)
        transform = lambda w: w.astype(store)

    # copy every container so the caller's tree is never mutated, then
    # transform the serve-path weights in place on the fresh containers
    def copy_tree(node):
        if isinstance(node, dict):
            return {k: copy_tree(v) for k, v in node.items()}
        if isinstance(node, tuple):
            return tuple(copy_tree(v) for v in node)
        if isinstance(node, list):
            return [copy_tree(v) for v in node]
        return node

    new_params = copy_tree(params)
    n_q = 0
    for d, k in _walk_serve_weights(new_params):
        d[k] = transform(d[k])
        n_q += 1
    new_embed = new_params.get("embed")
    if head is not None:
        new_embed["head"] = transform(head)
        n_q += 1
    elif tied_tokens is not None and wd == "int8":
        # tied models: quantize the TRANSPOSED table (d, V) so unembed
        # reads an int8 (in, out) record like every other projection
        new_embed["head_q8"] = quantize_weights(
            tied_tokens.astype(jnp.float32).T)
        n_q += 1

    comp_items = list(_walk_serve_weights(new_params))
    comp_bytes = sum(weight_record_bytes(d[k]) for d, k in comp_items)
    if head is not None:
        comp_bytes += weight_record_bytes(new_embed["head"])
    elif tied_tokens is not None:
        if wd == "int8":
            comp_bytes += weight_record_bytes(new_embed["head_q8"])
        else:
            comp_bytes += weight_record_bytes(tied_tokens)
    return new_params, {"weights_dtype": wd, "weight_bytes": comp_bytes,
                        "weight_bytes_dense": dense_bytes,
                        "weight_bytes_saved": dense_bytes - comp_bytes,
                        "n_quantized": n_q}


@dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32
    kv_dtype: str = "auto"
    weights_dtype: str = "auto"

    def cast_params(self, params):
        """Cast a parameter pytree to ``param_dtype`` (storage)."""
        return jax.tree.map(
            lambda p: p.astype(self.param_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

    def compute_cast(self, tree):
        """Cast activations (or params at point-of-use) to compute dtype."""
        return jax.tree.map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def output_cast(self, x):
        return x.astype(self.output_dtype)

    def kv_cache_dtype(self, *, dense: bool = False):
        """Storage dtype for KV caches under this policy.  ``dense=True``
        (per-slot caches without scale pools) maps int8 back to the
        compute dtype — only the paged pool supports quantized storage."""
        return kv_store_dtype(self.kv_dtype, self.compute_dtype,
                              allow_int8=not dense)


FP32 = Policy()
BF16 = Policy(jnp.bfloat16, jnp.bfloat16, jnp.float32)          # TPU-native default
FP16 = Policy(jnp.float16, jnp.float16, jnp.float32)            # paper-faithful
MIXED_TRAIN = Policy(jnp.float32, jnp.bfloat16, jnp.float32)    # fp32 master weights


_POLICIES = {"fp32": FP32, "bf16": BF16, "fp16": FP16, "mixed": MIXED_TRAIN}


def get_policy(name: str) -> Policy:
    try:
        return _POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown policy {name!r}; one of {list(_POLICIES)}")
