"""The request generator: deterministic per seed, the same sizes and
rate for every seed, and latency timed from due times."""

import json
import os

import numpy as np
import pytest

from bench.traffic import Traffic

HERE = os.path.dirname(__file__)


def _spec(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", ["decode-long", "chat-poisson"])
def test_same_seed_same_requests(name):
    seed = 2**31 + 17
    a, b = Traffic(_spec(name), seed, 50304), Traffic(_spec(name), seed, 50304)
    for i in (0, 5, 4100):
        ra, rb = a.request(i), b.request(i)
        assert ra.max_new == rb.max_new
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
    if name == "chat-poisson":
        np.testing.assert_array_equal(a.arrivals, b.arrivals)


def test_seeds_share_sizes_and_rate():
    s = _spec("chat-poisson")
    a, b = Traffic(s, 1, 50304), Traffic(s, 2, 50304)
    n = s["pool"]
    la = sorted(len(a.request(i).prompt) for i in range(n))
    lb = sorted(len(b.request(i).prompt) for i in range(n))
    assert la == lb
    first = lambda t: [t.request(i).max_new for i in range(8)]
    assert first(a) != first(b)
    # the whole pool's arrivals span the same time whatever the seed
    assert a.arrivals[-1] == pytest.approx(b.arrivals[-1])
    assert a.arrivals[-1] == pytest.approx(n / s["arrival"]["rate"], rel=0.01)


def test_lengths_follow_the_file():
    s = _spec("decode-long")
    t = Traffic(s, 7, 50304)
    p = np.array([len(t.request(i).prompt) for i in range(s["pool"])])
    o = np.array([t.request(i).max_new for i in range(s["pool"])])
    assert p.min() >= s["prompt"]["min"] and p.max() <= s["prompt"]["max"]
    assert np.median(p) == pytest.approx(s["prompt"]["median"], abs=1)
    assert np.median(o) == pytest.approx(s["output"]["median"], abs=1)


def test_closed_loop_start_is_staggered():
    s = _spec("decode-long")
    a, b = Traffic(s, 3, 50304).initial(32), Traffic(s, 4, 50304).initial(32)
    rem = [r.max_new for r in a]
    # the same remaining lengths for every seed, in another order
    assert sorted(rem) == sorted(r.max_new for r in b) and rem != [r.max_new for r in b]
    assert len(set(rem)) > 20 and 1 <= min(rem) and max(rem) <= s["output"]["max"]
    # a request met at a random moment has, on average, E[L^2] / 2E[L] left
    t = Traffic(s, 3, 50304)
    L = np.array([t.request(i).max_new for i in range(s["pool"])])
    assert np.mean(rem) == pytest.approx((L ** 2).mean() / (2 * L.mean()), rel=0.1)
