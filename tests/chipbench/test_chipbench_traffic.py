"""The benchmark's traffic generator: seeded, and the same work for every
seed."""

import chipbench_tiny  # noqa: F401  (puts the repository on the path)
import numpy as np
import pytest

from chipbench.cellconfig import load_json
from chipbench.traffic import Traffic, lengths, percentile


def _window(seed, mix="chat", seconds=45.0):
    return Traffic(load_json("traffic", mix + ".json"), 151936,
                   seed).window(seconds)


def test_same_seed_same_requests():
    a, b = _window(2**31 + 5), _window(2**31 + 5)
    assert [(r.due_s, r.max_new) for r in a] == \
        [(r.due_s, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**32 + 3)])
def test_every_seed_gets_the_same_schedule(seeds):
    a, b = (_window(s) for s in seeds)
    rate = load_json("traffic", "chat.json")["arrivals"]["rate_per_s"]
    assert len(a) == len(b) == round(rate * 45)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_window_sizes_follow_the_mix():
    w = _window(3)
    assert all(0 <= r.due_s < 45 for r in w)
    assert w[0].due_s > 0 and np.all(np.diff([r.due_s for r in w]) > 0)
    assert {len(r.prompt) for r in w} <= {128, 256, 512, 1024}
    assert all(16 <= r.max_new <= 512 for r in w)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 151936 for r in w)


def test_saturated_pool_and_warm_up():
    t = Traffic(load_json("traffic", "long-prompt.json"), 151936, 9)
    reqs = [t.next_saturated() for _ in range(600)]
    assert {len(r.prompt) for r in reqs} <= {1024, 2048, 3072, 4096}
    assert all(4 <= r.max_new <= 16 for r in reqs)
    assert np.array_equal(reqs[0].prompt, reqs[32].prompt)
    warm = t.warm()
    assert len(warm) == 8
    assert {len(r.prompt) for r in warm} == {1024, 2048, 3072, 4096}


def test_length_quantiles():
    spec = {"kind": "lognormal", "median": 256, "sigma": 0.8, "min": 64,
            "max": 1024}
    assert lengths(spec, np.array([0.5]))[0] == 256
    assert lengths(spec, np.array([1e-9, 1 - 1e-9])).tolist() == [64, 1024]
    uni = {"kind": "uniform", "min": 4, "max": 16}
    assert lengths(uni, (np.arange(13) + 0.5) / 13).tolist() == \
        list(range(4, 17))


def test_percentile_is_nearest_rank():
    assert percentile(range(1, 101), 90) == 90
    assert percentile([5.0], 95) == 5.0
