"""qwen3-30b-a3b: its weights from the seed and its plain float32 reference.

Qwen3-30B-A3B (``qwen3-30b-a3b.json`` names the source) at its published
widths, cut to the 6 layers one chip of an eight-stage pipeline holds.
Each layer is pre-norm: RMSNorm, grouped-query attention (32 query heads
and 4 key/value heads of 128) with a per-head RMSNorm on q and k before
RoPE (theta 1e6), then RMSNorm and a sparse expert layer: a linear
router over 128 experts, a float32 softmax over all of them, the top 8
kept and their weights renormalised to sum 1, and SwiGLU experts of
width 768 with no shared expert.  A final RMSNorm and an untied head
give the logits.  No linear layer has a bias; RMSNorm's epsilon is 1e-6.

The weights are made here, on the device, from the run's seed, in the
program's parameter layout and in bfloat16, the precision the
configuration states for weights and the KV cache.  The reference reads
them as float32 and computes every expert for every row, weighted by
its gate and zero where the router did not choose it.
"""
import jax
import jax.numpy as jnp

from benchlib import plainref as R


def dims(c):
    """The sizes the harness and the metric readers use.  ``ff`` is the
    expert width one token meets (top_k x expert width), so the dense
    feed-forward count of ``arith.layer_matmuls`` counts exactly the
    expert products of a token; E, top_k and f size the expert
    matmul's own readers."""
    k, f = c["num_experts_per_tok"], c["moe_intermediate_size"]
    return dict(d=c["hidden_size"], L=c["num_hidden_layers"],
                Hq=c["num_attention_heads"], Hkv=c["num_key_value_heads"],
                D=c["head_dim"], ff=k * f, ffn_mats=3, V=c["vocab_size"],
                E=c["num_experts"], top_k=k, f=f,
                eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
                kvbits=16)


def make_weights(key, c):
    """Every weight from ``key``: matrices N(0, 1/fan_in), the token
    embedding N(0, 0.02^2), RMSNorm gain offsets N(0, 0.1^2); bfloat16.
    Each expert stack is drawn one layer at a time, so no float32
    temporary of a whole stack exists."""
    m = dims(c)
    d, L, E, f = m["d"], m["L"], m["E"], m["f"]
    q, kv = m["Hq"] * m["D"], m["Hkv"] * m["D"]
    shapes = [("tokens", (m["V"], d), 0.02), ("head", (d, m["V"]), d ** -0.5),
              ("final", (d,), 0.1), ("n1", (L, d), 0.1), ("n2", (L, d), 0.1),
              ("qn", (L, m["D"]), 0.1), ("kn", (L, m["D"]), 0.1),
              ("wq", (L, d, q), d ** -0.5), ("wk", (L, d, kv), d ** -0.5),
              ("wv", (L, d, kv), d ** -0.5), ("wo", (L, q, d), q ** -0.5),
              ("router", (L, d, E), d ** -0.5)]
    w = {name: (std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)).astype(jnp.bfloat16)
         for i, (name, shape, std) in enumerate(shapes)}

    def experts(i, shape, std):
        k = jax.random.fold_in(key, len(shapes) + i)
        return jax.lax.map(
            lambda l: (std * jax.random.normal(jax.random.fold_in(k, l), shape,
                                               jnp.float32)
                       ).astype(jnp.bfloat16), jnp.arange(L))

    layer = {"norm1": {"w": w["n1"]},
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"], "q_norm": {"w": w["qn"]},
                      "k_norm": {"w": w["kn"]}},
             "norm2": {"w": w["n2"]},
             "ffn": {"router": w["router"],
                     "wi": experts(0, (E, d, f), d ** -0.5),
                     "wg": experts(1, (E, d, f), d ** -0.5),
                     "wo": experts(2, (E, f, d), f ** -0.5)}}
    return {"embed": {"tokens": w["tokens"], "head": w["head"]},
            "final_norm": {"w": w["final"]},
            "stacks": ((layer,),)}


def rms_norm(x, w, eps):
    """RMSNorm with the gain stored as its offset from 1."""
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def rope(x, theta):
    """Rotary embedding of x (S, H, D) at positions 0..S-1: the two
    halves of each head rotate together (Qwen's rotate_half form)."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_stats(params, tokens, targets, c, wbits=None, kvbits=None):
    """Logit statistics of one sequence ``tokens`` (S,), S a multiple of
    ``plainref.ROW_BLOCK``: per position the largest logit, its token,
    and the logits of ``targets`` (S, K).  The configuration states
    bfloat16 weights and KV, which float32 holds exactly; ``wbits`` /
    ``kvbits`` (the control) round every weight matrix and the keys and
    values through that many bits."""
    m = dims(c)
    S, Hq, Hkv, D, eps = tokens.shape[0], m["Hq"], m["Hkv"], m["D"], m["eps"]
    E, k = m["E"], m["top_k"]
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(params["embed"]["tokens"])[tokens]

    def layer(x, p):
        a = p["attn"]
        h = rms_norm(x, p["norm1"]["w"], eps)
        q = R.mm(h, R.weight(a["wq"], wbits)).reshape(S, Hq, D)
        kk = R.mm(h, R.weight(a["wk"], wbits)).reshape(S, Hkv, D)
        v = R.mm(h, R.weight(a["wv"], wbits)).reshape(S, Hkv, D)
        q = rope(rms_norm(q, a["q_norm"]["w"], eps), m["theta"])
        kk = rope(rms_norm(kk, a["k_norm"]["w"], eps), m["theta"])
        kk, v = R.round_trip(kk, kvbits, -1), R.round_trip(v, kvbits, -1)
        ctx = R.causal_attention(q, kk, v, D ** -0.5).reshape(S, Hq * D)
        x = x + R.mm(ctx, R.weight(a["wo"], wbits))

        fp = p["ffn"]
        h = rms_norm(x, p["norm2"]["w"], eps)
        probs = jax.nn.softmax(R.mm(h, R.weight(fp["router"], wbits)), -1)
        topw, tope = jax.lax.top_k(probs, k)
        topw = topw / topw.sum(-1, keepdims=True)
        gate = jnp.zeros((S, E), jnp.float32).at[
            jnp.arange(S)[:, None], tope].set(topw)

        def expert(out, e):
            wi, wg, wo = (R.weight(fp[n][e], wbits)
                          for n in ("wi", "wg", "wo"))
            y = R.mm(jax.nn.silu(R.mm(h, wg)) * R.mm(h, wi), wo)
            return out + gate[:, e][:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
        return x + out, None

    x, _ = jax.lax.scan(layer, x, params["stacks"][0][0])
    h = rms_norm(x, params["final_norm"]["w"], eps)
    return R.logit_stats(h, R.weight(params["embed"]["head"], wbits), targets)
