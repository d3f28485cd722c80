"""The correctness check on the CPU at a small size: sound runs pass it,
the control and a token altered where it is produced fail it."""
import jax.numpy as jnp
import pytest

from bench_testkit import SMALL_GAP_LIMIT, run_small

CELLS = ["unimo-text.chat-short", "unimo-text.offline-batch"]


@pytest.mark.parametrize("name", CELLS)
def test_served_tokens_agree_with_the_reference(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["logit_gap_max"]["value"] < SMALL_GAP_LIMIT
    # the result line: the keys the driver reads, the checks last
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = run_small(name, seed=31, control=True)
    checks = res["checks"]
    assert not res["correct"], checks
    assert "logit_gap_max" not in checks
    assert checks["control_gap_max"]["value"] > SMALL_GAP_LIMIT


@pytest.mark.parametrize("name", CELLS)
def test_token_altered_where_produced_is_not_correct(name, monkeypatch):
    from repro.core import engine as E
    sample = E.sample

    def altered(logits, key, sp):
        ids = sample(logits, key, sp)
        return jnp.where(ids % 3 == 0, (ids + 1) % logits.shape[-1], ids)

    monkeypatch.setattr(E, "sample", altered)
    res = run_small(name, seed=5)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(name, monkeypatch):
    """Only the first half of each batch's rows is sampled; the rest get
    a fixed token."""
    from repro.core import engine as E
    sample = E.sample

    def half(logits, key, sp):
        ids = sample(logits, key, sp)
        keep = jnp.arange(ids.shape[0]) < (ids.shape[0] + 1) // 2
        return jnp.where(keep, ids, 4)

    monkeypatch.setattr(E, "sample", half)
    res = run_small(name, seed=7)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_decode_step_that_leaves_the_pool_unchanged_is_not_correct(
        name, monkeypatch):
    """The fused decode step returns its KV state unchanged: the tokens
    it decodes never reach the pool, and later tokens attend without
    them."""
    from repro.core import kv_cache as KV
    monkeypatch.setattr(KV, "paged_write_decode",
                        lambda cache, *a, **k: cache)
    res = run_small(name, seed=9)
    assert not res["correct"], res["checks"]
