"""unimo-text: its weights from the seed and its plain float32 reference.

The decoder the competition paper serves (arXiv:2407.04991, section
3.1), at the widths of PaddleNLP's ``unimo-text-1.0-large``, in the
block the program runs (``unimo-text.json`` lists where that departs
from the source): learned absolute positions added to the token
embedding, pre-norm blocks of LayerNorm, multi-head attention, a GELU
feed-forward (tanh form), a final LayerNorm and an unembedding tied to
the token embedding.  No linear layer has a bias.  The configuration
states int8 weights (per-output-channel absmax, codes in [-127, 127])
for every attention and feed-forward matrix and for the unembedding,
and an int8 key/value cache (one absmax scale per token and head); the
reference rounds through both.

The weights are made here, on the device, from the run's seed, in the
program's parameter layout and in bfloat16; the int8 codes are derived
from those, by the program for itself and by this file for the
reference.
"""
import jax
import jax.numpy as jnp

from benchlib import plainref as R


def dims(c):
    H = c["num_attention_heads"]
    return dict(d=c["hidden_size"], L=c["num_hidden_layers"], Hq=H, Hkv=H,
                D=c["hidden_size"] // H, ff=c["intermediate_size"],
                ffn_mats=2, V=c["vocab_size"], P=c["max_position_embeddings"],
                eps=float(c["layer_norm_eps"]), wbits=c["weight_bits"],
                kvbits=c["kv_cache_bits"])


def make_weights(key, c):
    """Every weight from ``key``: matrices N(0, 1/fan_in), embeddings
    N(0, 0.02^2), LayerNorm gains 1 + N(0, 0.1^2) and biases
    N(0, 0.1^2); bfloat16."""
    m = dims(c)
    d, L, ff = m["d"], m["L"], m["ff"]
    shapes = [("tokens", (m["V"], d), 0.02, 0), ("pos", (m["P"], d), 0.02, 0),
              ("final_w", (d,), 0.1, 1), ("final_b", (d,), 0.1, 0),
              ("n1w", (L, d), 0.1, 1), ("n1b", (L, d), 0.1, 0),
              ("n2w", (L, d), 0.1, 1), ("n2b", (L, d), 0.1, 0),
              ("wq", (L, d, d), d ** -0.5, 0), ("wk", (L, d, d), d ** -0.5, 0),
              ("wv", (L, d, d), d ** -0.5, 0), ("wo", (L, d, d), d ** -0.5, 0),
              ("wi", (L, d, ff), d ** -0.5, 0),
              ("wdown", (L, ff, d), ff ** -0.5, 0)]
    w = {name: (mean + std * jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)).astype(jnp.bfloat16)
        for i, (name, shape, std, mean) in enumerate(shapes)}
    layer = {"norm1": {"w": w["n1w"], "b": w["n1b"]},
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"]},
             "norm2": {"w": w["n2w"], "b": w["n2b"]},
             "ffn": {"wi": w["wi"], "wo": w["wdown"]}}
    return {"embed": {"tokens": w["tokens"], "pos": w["pos"]},
            "final_norm": {"w": w["final_w"], "b": w["final_b"]},
            "stacks": ((layer,),)}


def reference_stats(params, tokens, targets, c, wbits=None, kvbits=None):
    """Logit statistics of one sequence ``tokens`` (S,), S a multiple of
    ``plainref.ROW_BLOCK``: per position the largest logit, its token,
    and the logits of ``targets`` (S, K).  Weights and keys/values
    round through the configuration's bits, or through ``wbits`` /
    ``kvbits`` where given (the control)."""
    m = dims(c)
    wbits = wbits or m["wbits"]
    kvbits = kvbits or m["kvbits"]
    S, H, D, eps = tokens.shape[0], m["Hq"], m["D"], m["eps"]
    f32 = lambda a: a.astype(jnp.float32)
    emb = f32(params["embed"]["tokens"])
    pos = jnp.minimum(jnp.arange(S), m["P"] - 1)
    x = emb[tokens] + f32(params["embed"]["pos"])[pos]

    def layer(x, p):
        a = p["attn"]
        h = R.layer_norm(x, f32(p["norm1"]["w"]), f32(p["norm1"]["b"]), eps)
        q = R.mm(h, R.weight(a["wq"], wbits)).reshape(S, H, D)
        k = R.mm(h, R.weight(a["wk"], wbits)).reshape(S, H, D)
        v = R.mm(h, R.weight(a["wv"], wbits)).reshape(S, H, D)
        k, v = R.round_trip(k, kvbits, -1), R.round_trip(v, kvbits, -1)
        ctx = R.causal_attention(q, k, v, D ** -0.5).reshape(S, H * D)
        x = x + R.mm(ctx, R.weight(a["wo"], wbits))
        h = R.layer_norm(x, f32(p["norm2"]["w"]), f32(p["norm2"]["b"]), eps)
        f = p["ffn"]
        up = jax.nn.gelu(R.mm(h, R.weight(f["wi"], wbits)), approximate=True)
        return x + R.mm(up, R.weight(f["wo"], wbits)), None

    x, _ = jax.lax.scan(layer, x, params["stacks"][0][0])
    fn = params["final_norm"]
    h = R.layer_norm(x, f32(fn["w"]), f32(fn["b"]), eps)
    return R.logit_stats(h, R.weight(emb.T, wbits), targets)
