"""The generator: the same seed gives the same inputs, lengths stay inside
their bounds, and the open loop's schedule is absolute."""

import numpy as np
import pytest

import traffic


def mixed(seed, scale=1.0, plan_seed=None):
    """The mixed cell's traffic, its own sample path by the seed unless a
    ``plan_seed`` is given (the file names one; ``test_plan_seed...``)."""
    spec = dict(traffic.load("open_mixed"))
    spec["arrivals"] = {"dist": "poisson", "rate_per_s": 1.6}
    if plan_seed is not None:
        spec["arrivals"]["plan_seed"] = plan_seed
    return traffic.ServeTraffic(spec, 50272, seed, scale)


def test_requests_are_a_function_of_seed_and_index():
    a, b, c = mixed(3), mixed(3), mixed(4)
    for i in (0, 1, 17, 400):
        ra, rb, rc = a.request(i), b.request(i), c.request(i)
        assert ra["max_new"] == rb["max_new"]
        assert np.array_equal(ra["prompt"], rb["prompt"])
        assert not np.array_equal(ra["prompt"][:16], rc["prompt"][:16])
    # consuming more or fewer requests changes none of them
    assert np.array_equal(a.request(5)["prompt"], mixed(3).request(5)["prompt"])


def test_lengths_stay_inside_the_mix():
    t = mixed(1)
    reqs = [t.request(i) for i in range(600)]
    n = np.array([len(r["prompt"]) for r in reqs])
    new = np.array([r["max_new"] for r in reqs])
    short, long_ = n[n <= 384], n[n >= 1024]
    assert len(short) + len(long_) == len(n)
    assert 0.14 < len(long_) / len(n) < 0.26
    assert short.min() >= 32 and long_.max() <= 1792
    assert 16 <= new.min() and new.max() <= 256
    assert 100 < np.median(short) < 160
    assert t.prompt_bounds() == [(32, 384), (1024, 1792)]
    assert t.longest_request() == 2048


def test_open_plan_is_fixed_work_arranged_by_the_seed():
    spec = traffic.load("open_mixed")
    rate = spec["arrivals"]["rate_per_s"]
    a, b, c = mixed(9).open_plan(3.0, 40.0), mixed(9).open_plan(3.0, 40.0), \
        mixed(10).open_plan(3.0, 40.0)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    due = np.array([r["due"] for r in a])
    assert np.all(np.diff(due) > 0) and due[0] >= -3.0 and due[-1] < 40.0
    for plan in (a, c):
        inside = [r for r in plan if r["due"] >= 0]
        assert len(inside) == round(rate * 40.0)
        long_ = [r for r in inside if len(r["prompt"]) >= 1024]
        assert len(long_) == round(0.2 * len(inside))
    # another seed: the same work (to a few tokens), another arrangement
    tok = lambda plan: sum(len(r["prompt"]) for r in plan if r["due"] >= 0)
    new = lambda plan: sum(r["max_new"] for r in plan if r["due"] >= 0)
    assert abs(tok(a) - tok(c)) < 0.03 * tok(a)
    assert abs(new(a) - new(c)) < 0.05 * new(a)
    assert [r["due"] for r in a] != [r["due"] for r in c]
    assert all(32 <= len(r["prompt"]) <= 1792 and 16 <= r["max_new"] <= 256
               for r in a)


def test_plan_seed_fixes_times_and_lengths_and_leaves_tokens_to_the_seed():
    shape = lambda plan: [(r["due"], r["cls"], len(r["prompt"]), r["max_new"])
                          for r in plan]
    own = mixed(2500000501).open_plan(3.0, 51.0)
    a = mixed(9, plan_seed=2500000501).open_plan(3.0, 51.0)
    b = mixed(2500000777, plan_seed=2500000501).open_plan(3.0, 51.0)
    # the plan is that seed's own sample path, whatever --seed is
    assert shape(a) == shape(b) == shape(own)
    assert sum(r["due"] >= 0 for r in a) == 82
    # the seed still draws the tokens, and the same seed the same tokens
    assert not any(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    again = mixed(9, plan_seed=2500000501).open_plan(3.0, 51.0)
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, again))
    # the cell's file names one: two seeds offer the same requests
    spec = traffic.load("open_mixed")
    assert spec["arrivals"]["plan_seed"] == 2500000501
    c, d = (traffic.ServeTraffic(spec, 50272, s).open_plan(3.0, 51.0)
            for s in (1, 2))
    assert shape(c) == shape(d) == shape(own)


def test_arrivals_are_a_poisson_process_given_its_count():
    spec = dict(traffic.load("open_mixed"))
    spec["arrivals"] = {"dist": "poisson", "rate_per_s": 20.0}
    due = [r["due"] for r in traffic.ServeTraffic(spec, 100, 1).open_plan(0, 500)]
    gaps = np.diff(due)
    assert len(due) == 10000
    # exponential gaps: as wide as their mean, and a tenth under a tenth of it
    assert 0.95 < gaps.std() / gaps.mean() < 1.05
    assert 0.08 < np.mean(gaps < 0.1 * gaps.mean()) < 0.11
    spec["arrivals"] = {"dist": "paced", "rate_per_s": 20.0}
    with pytest.raises(ValueError):
        traffic.ServeTraffic(spec, 100, 1).open_plan(0, 5)


@pytest.mark.parametrize("dist, lo, mid, hi", [
    ({"dist": "fixed", "value": 8}, 8, 8, 8),
    ({"dist": "uniform", "lo": 128, "hi": 256}, 128, 192, 256),
    ({"dist": "lognormal", "median": 96, "sigma": 0.7, "lo": 16, "hi": 256},
     16, 96, 256),
])
def test_lengths_at_quantiles(dist, lo, mid, hi):
    got = traffic.lengths_at(dist, [0.0, 0.5, 0.999999])
    assert list(got) == [lo, mid, hi]
    assert list(traffic.lengths_at(dist, [0.5], scale=0.125)) == [max(round(mid / 8), 1)]
    assert traffic.length_bounds(dist) == (lo, hi)


def test_train_batches():
    s = traffic.TokenSampler({"dist": "zipf", "a": 1.1}, 250880, 7)
    a = traffic.train_batch(s, 7, 3, 4, 2048)["input_ids"]
    b = traffic.train_batch(traffic.TokenSampler({"dist": "zipf", "a": 1.1},
                                                 250880, 7), 7, 3, 4, 2048)["input_ids"]
    assert a.shape == (4, 2048) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.train_batch(s, 7, 4, 4, 2048)["input_ids"])
    assert 0 <= a.min() and a.max() < 250880
    # Zipf: the most frequent token takes far more than a uniform share
    counts = np.bincount(a.ravel())
    assert counts.max() > 0.03 * a.size
