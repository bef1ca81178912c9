import numpy as np
import pytest

import traffic

MIX = traffic.load("decode_backlog")


def test_every_seed_gets_the_same_sizes_in_the_same_order_and_its_own_tokens():
    a = traffic.generate(MIX, 2**31 + 11, 92544)
    b = traffic.generate(MIX, 5, 92544)
    assert len(a) == len(b) == MIX["requests"]
    for f in (lambda it: len(it.prompt), lambda it: it.max_new):
        assert list(map(f, a)) == list(map(f, b))
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert all(1 <= t < 92544 for it in a for t in it.prompt)


def test_same_seed_same_requests():
    a, b = (traffic.generate(MIX, 77, 512) for _ in range(2))
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_sizes_are_the_distributions_quantiles():
    items = traffic.generate(MIX, 3, 512)
    n = MIX["requests"]
    assert sorted(len(it.prompt) for it in items) == list(
        traffic.quantiles(MIX["prompt_tokens"], n))
    assert sorted(it.max_new for it in items) == list(
        traffic.quantiles(MIX["output_tokens"], n))


@pytest.mark.parametrize("n", [1, 2, 8, 1000, 1024])
def test_every_power_of_two_prefix_spreads_over_the_strata(n):
    order = traffic.spread_order(n)
    assert sorted(order) == list(range(n))
    k = 1
    while 2 * k <= n and n & (n - 1) == 0:
        k *= 2
        assert sorted(order[:k] * k // n) == list(range(k))


def test_lengths_stay_in_range_and_fit_max_len():
    lo, hi = MIX["prompt_tokens"], MIX["output_tokens"]
    q = traffic.quantiles(lo, 4001), traffic.quantiles(hi, 4001)
    assert q[0].min() >= lo["min"] and q[0].max() <= lo["max"]
    assert q[1].min() >= hi["min"] and q[1].max() <= hi["max"]
    # prompt + output + one 8-step segment fit the configuration's 2048 positions
    assert lo["max"] + hi["max"] + 8 <= 2048


def test_medians_are_the_sources():
    q = traffic.quantiles(MIX["prompt_tokens"], 4001), traffic.quantiles(MIX["output_tokens"], 4001)
    assert np.median(q[0]) == MIX["prompt_tokens"]["median"]
    assert np.median(q[1]) == MIX["output_tokens"]["median"]
