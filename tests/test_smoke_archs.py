"""Required per-arch smoke tests: reduced variant, one forward + one train
step on CPU, asserting output shapes and the absence of NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED, get_reduced
from repro.core.precision import FP32
from repro.models import transformer as T
from repro.training import optimizer as OPT
from repro.training.train_loop import make_train_step

B, S = 2, 16


def _batch(cfg, key):
    shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks else (B, S)
    toks = jax.random.randint(key, shape, 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks,
             "loss_mask": jnp.ones((B, S), jnp.float32)}
    if cfg.num_prefix_embeds:
        batch["prefix_embeds"] = 0.02 * jax.random.normal(
            key, (B, cfg.num_prefix_embeds, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ASSIGNED + ["unimo-text"])
def test_smoke_forward_and_train_step(arch, key):
    cfg = get_reduced(arch)
    params = T.init_params(key, cfg)
    batch = _batch(cfg, key)

    logits, aux = T.forward_train(params, cfg, batch["tokens"],
                                  prefix_embeds=batch.get("prefix_embeds"),
                                  policy=FP32, remat=False)
    S_tot = S + cfg.num_prefix_embeds
    if cfg.num_codebooks:
        assert logits.shape == (B, S_tot, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S_tot, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), "NaN in forward logits"

    step = jax.jit(make_train_step(cfg, OPT.AdamWConfig(warmup_steps=1,
                                                        total_steps=10),
                                   policy=FP32, remat=True))
    opt_state = OPT.init_state(params)
    params2, _, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"])), metrics
    assert not bool(jnp.isnan(metrics["gnorm"])), "NaN gradients"
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()),
                     params, params2))
    assert delta > 0.0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_serve_roundtrip(arch, key):
    """Prefill then two decode steps: shapes + finite outputs + the
    prefill logits match the train forward exactly."""
    cfg = get_reduced(arch)
    params = T.init_params(key, cfg)
    batch = _batch(cfg, key)
    pre = batch.get("prefix_embeds")
    S_tot = S + cfg.num_prefix_embeds

    full, _ = T.forward_train(params, cfg, batch["tokens"],
                              prefix_embeds=pre, policy=FP32, remat=False)
    cache = T.init_cache(cfg, B, 64, jnp.float32)
    lengths = jnp.full((B,), S_tot, jnp.int32)
    lg, cache, _ = T.forward_prefill(params, cfg, batch["tokens"], lengths,
                                  cache, prefix_embeds=pre, policy=FP32)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full),
                               rtol=2e-4, atol=2e-4)
    tok1 = (batch["tokens"][:, :1])
    for i in range(2):
        lg1, cache, _ = T.forward_decode(params, cfg, tok1, cache,
                                      lengths + i, policy=FP32)
        assert not bool(jnp.isnan(lg1).any())
        assert lg1.shape[0] == B and lg1.shape[1] == 1
