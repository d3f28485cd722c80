"""The traffic generator: deterministic per seed, true to its file."""
import json
import os

import numpy as np
import pytest

from bench_testkit import BENCH
from benchlib.traffic import generate, lognormal_quantiles

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**33 + 5])
@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name, seed):
    a = generate(mix(name), 10, seed, 1000)
    b = generate(mix(name), 10, seed, 1000)
    assert [(i.arrival, i.max_new, i.prompt.tolist()) for i in a] == \
        [(i.arrival, i.max_new, i.prompt.tolist()) for i in b]


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 11, 2**31 + 12)])
@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(name, seeds):
    m = mix(name)
    a, b = (generate(m, 10, s, 1000) for s in seeds)
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    gaps = lambda items: sorted(np.diff([0.0] + [i.arrival for i in items]))
    assert np.allclose(gaps(a), gaps(b))


@pytest.mark.parametrize("name", MIXES)
def test_requests_match_the_file(name):
    m = mix(name)
    items = generate(m, 20, 3, 12800)
    key = "rate_per_s" if m["arrival"] == "poisson" else "backlog_per_s"
    assert len(items) == round(m[key] * 20)
    for spec, vals in ((m["prompt_tokens"], [len(i.prompt) for i in items]),
                       (m["output_tokens"], [i.max_new for i in items])):
        assert spec["min"] <= min(vals) and max(vals) <= spec["max"]
        assert abs(np.median(vals) - spec["median"]) <= 0.1 * spec["median"]
    ids = np.concatenate([i.prompt for i in items])
    assert ids.min() >= 4 and ids.max() < 12800
    arrivals = [i.arrival for i in items]
    assert arrivals == sorted(arrivals)
    if m["arrival"] == "poisson":
        assert all(i.deadline is None for i in items)
        assert abs(arrivals[-1] - 20) < 0.25 * 20
    else:
        assert set(arrivals) == {0.0}
        assert all(i.deadline == 20 for i in items)


def test_lognormal_quantiles_are_clipped_and_centred():
    v = lognormal_quantiles({"median": 100, "sigma": 1.0, "min": 50,
                             "max": 150}, 1001)
    assert v.min() == 50 and v.max() == 150 and v[500] == 100
