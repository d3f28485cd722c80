"""MoE dispatch: sort-based capacity routing (training) and the dropless
grouped dispatch (serving) vs a dense per-token oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.configs.registry import get_reduced
from repro.models import moe as MOE


def _oracle(cfg, p, x, kind):
    """Dense per-token expert mixture (no capacity, no dispatch)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    m = cfg.moe
    scores = xf @ p["router"]
    if kind == "sigmoid":
        probs = jax.nn.sigmoid(scores)
    else:
        probs = jax.nn.softmax(scores, -1)
    topw, tope = jax.lax.top_k(probs, m.top_k)
    topw = topw / (topw.sum(-1, keepdims=True) + 1e-9)
    outs = []
    for e in range(m.num_experts):
        h = xf @ p["wi"][e]
        g = jax.nn.silu(xf @ p["wg"][e])
        outs.append((g * h) @ p["wo"][e])
    outs = jnp.stack(outs, 1)                       # (T, E, d)
    w_full = jnp.zeros((xf.shape[0], m.num_experts)).at[
        jnp.arange(xf.shape[0])[:, None], tope].set(topw)
    out = jnp.einsum("te,ted->td", w_full, outs)
    if "shared" in p:
        from repro.models import layers as L
        out = out + L.ffn_apply(cfg, p["shared"], xf)
    return out.reshape(B, S, d)


@pytest.mark.parametrize("arch,kind", [("qwen3-moe-235b-a22b", "softmax"),
                                       ("deepseek-v3-671b", "sigmoid")])
def test_moe_matches_dense_oracle(arch, kind, key, rng):
    cfg = get_reduced(arch)
    # generous capacity -> no token drops -> exact match expected
    cfg = cfg.replace(moe=MoEConfig(
        num_experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
        num_shared_experts=cfg.moe.num_shared_experts,
        d_ff_expert=cfg.moe.d_ff_expert, capacity_factor=8.0))
    p = MOE.moe_init(key, cfg)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)), jnp.float32) * 0.3
    out, aux = MOE.moe_apply_capacity(cfg, p, x, kind)
    ref = _oracle(cfg, p, x, kind)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)
    assert float(aux) >= 0.0


def test_moe_capacity_drops_are_bounded(key, rng):
    """With tight capacity some tokens drop, output stays finite and the
    kept fraction is >= capacity/perfect-balance bound."""
    cfg = get_reduced("qwen3-moe-235b-a22b")
    cfg = cfg.replace(moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                                    capacity_factor=0.5))
    p = MOE.moe_init(key, cfg)
    x = jnp.asarray(rng.normal(size=(4, 32, cfg.d_model)), jnp.float32)
    out, aux = MOE.moe_apply_capacity(cfg, p, x, "softmax")
    assert bool(jnp.isfinite(out).all())


def test_moe_aux_balanced_router_lower_than_collapsed(key, rng):
    cfg = get_reduced("qwen3-moe-235b-a22b")
    p = MOE.moe_init(key, cfg)
    x = jnp.asarray(rng.normal(size=(2, 64, cfg.d_model)), jnp.float32)
    _, aux_rand = MOE.moe_apply_capacity(cfg, p, x, "softmax")
    # collapse router to a single expert
    p2 = dict(p)
    bias = jnp.zeros_like(p["router"]).at[:, 0].set(50.0)
    p2["router"] = p["router"] * 0.0 + bias
    _, aux_coll = MOE.moe_apply_capacity(cfg, p2, x, "softmax")
    assert float(aux_coll) > float(aux_rand)


@pytest.mark.parametrize("kind", ["softmax", "sigmoid"])
def test_moe_ragged_matches_capacity(kind, key, rng):
    """The dropless grouped dispatch (``lax.ragged_dot`` off the chip) ==
    capacity dispatch when capacity is generous (no drops), and its
    gradient reaches the expert weights."""
    cfg = get_reduced("qwen3-moe-235b-a22b")
    cfg = cfg.replace(moe=MoEConfig(num_experts=4, top_k=2, d_ff_expert=64,
                                    capacity_factor=8.0))
    p = MOE.moe_init(key, cfg)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)),
                    jnp.float32) * 0.3
    out_r, counts = MOE.moe_apply_dropless(cfg, p, x, kind)
    out_c, _ = MOE.moe_apply_capacity(cfg, p, x, kind)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_c),
                               rtol=2e-5, atol=2e-5)
    assert int(counts[0]) == 2 * 24 * 2
    g = jax.grad(lambda q: MOE.moe_apply_dropless(cfg, q, x, kind)[0].sum())(p)
    assert float(jnp.abs(g["wi"]).sum()) > 0


def test_moe_grad_flows(key, rng):
    cfg = get_reduced("qwen3-moe-235b-a22b")
    p = MOE.moe_init(key, cfg)
    x = jnp.asarray(rng.normal(size=(1, 8, cfg.d_model)), jnp.float32)

    def loss(p):
        out, aux = MOE.moe_apply_capacity(cfg, p, x, "softmax")
        return jnp.sum(out ** 2) + aux

    g = jax.grad(loss)(p)
    gnorm = sum(float(jnp.abs(v).sum()) for v in jax.tree.leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0
    assert float(jnp.abs(g["router"]).sum()) > 0   # router receives gradient
