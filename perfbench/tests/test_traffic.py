"""The generator: the same seed gives the same inputs, lengths stay inside
their bounds, and the open loop's schedule is absolute."""

import json
import os

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


def closed_mixes_of_the_cells():
    """Every ``"loop": "closed"`` file that a cell of BENCHMARK.json names."""
    with open(os.path.join(os.path.dirname(traffic.HERE), "BENCHMARK.json")) as f:
        names = sorted({w["traffic"] for w in json.load(f)["workloads"]})
    return [n for n in names if traffic.load(n).get("loop") == "closed"]


PLANNED = closed_mixes_of_the_cells()


@pytest.mark.parametrize("name", PLANNED)
def test_every_closed_loop_a_cell_names_carries_a_plan(name):
    assert isinstance(traffic.load(name)["plan_seed"], int)


@pytest.mark.parametrize("name", PLANNED)
def test_a_closed_loops_plan_seed_fixes_classes_and_lengths_in_their_order(name):
    """Every seed offers request i with the same class and lengths, and its
    own tokens (PERF.md section 6, PR 59)."""
    spec = traffic.load(name)
    plan = spec["plan_seed"]
    shape = lambda t, n=1000: [(r["cls"], len(r["prompt"]), r["max_new"])
                               for r in (t.request(i) for i in range(n))]
    a, b = (traffic.ServeTraffic(spec, 50272, s) for s in (7, 2147483777))
    assert shape(a) == shape(b)
    # the plan is that seed's own draw of lengths, as a mix with no plan
    # gives them
    free = {k: v for k, v in spec.items() if k != "plan_seed"}
    assert shape(a, 200) == shape(traffic.ServeTraffic(free, 50272, plan), 200)
    assert shape(a, 200) != shape(traffic.ServeTraffic(free, 50272, 7), 200)
    # the seed draws the tokens, the same seed the same tokens
    assert not any(np.array_equal(a.request(i)["prompt"][:16],
                                  b.request(i)["prompt"][:16])
                   for i in range(0, 1000, 50))
    assert np.array_equal(a.request(3)["prompt"],
                          traffic.ServeTraffic(spec, 50272, 7).request(3)["prompt"])


@pytest.mark.parametrize("name", PLANNED)
def test_a_plan_is_a_sample_of_the_mix_and_not_another_mix(name):
    """Over the plan's first 500 requests the classes' shares lie within 5
    points of the file's and each length's quartiles within 5% of the
    distribution's own."""
    spec = traffic.load(name)
    t = traffic.ServeTraffic(spec, 50272, 1)
    reqs = [t.request(i) for i in range(500)]
    for ci, c in enumerate(spec["classes"]):
        mine = [r for r in reqs if r["cls"] == ci]
        assert abs(len(mine) / 500 - t.shares[ci]) < 0.05
        for key, got in (("prompt", [len(r["prompt"]) for r in mine]),
                         ("answer", [r["max_new"] for r in mine])):
            want = traffic.lengths_at(c[key], [0.25, 0.5, 0.75])
            have = np.quantile(got, [0.25, 0.5, 0.75])
            assert np.all(np.abs(have - want) < 0.05 * want), (name, key, have, want)
            lo, hi = traffic.length_bounds(c[key])
            assert lo <= min(got) and max(got) <= hi


def serve_mixes_of_the_cells():
    """Every serve mix that a cell of BENCHMARK.json names."""
    with open(os.path.join(os.path.dirname(traffic.HERE), "BENCHMARK.json")) as f:
        names = sorted({w["traffic"] for w in json.load(f)["workloads"]})
    return [n for n in names if traffic.load(n).get("kind") == "serve"]


@pytest.mark.parametrize("name", serve_mixes_of_the_cells())
def test_a_mix_may_name_the_seed_its_weights_are_made_from(name):
    """A rule on the data, so that a later cell brings only its own files: a
    mix that names a ``weights_seed`` gets those weights for every ``--seed``
    and says in its ``why`` what the key gives up (the served check then sees
    one router's skew: PERF.md section 6, PR 59); any other mix's weights are
    the seed's. The prompts are the seed's in either case."""
    from runners.serve import ServeRunner
    spec = traffic.load(name)
    got = [ServeRunner({"chips": 1}, {}, spec, s, print).weights_seed()
           for s in (7, 2147483777)]
    if "weights_seed" in spec:
        assert isinstance(spec["weights_seed"], int)
        assert got == [spec["weights_seed"]] * 2
        assert "weights_seed" in spec["why"] and "gives up" in spec["why"]
    else:
        assert got == [7, 2147483777]


def test_a_closed_loop_without_a_plan_draws_by_the_seed_as_before():
    """A file without the key (a trial mix, a test's own): request i is what
    it was before PR 59, value for value."""
    for name, want in (("closed_decode", [(185, 367, 27252), (143, 150, 28229),
                                          (222, 346, 2488)]),
                       ("closed_shortlong_6k", [(598, 991, 27252), (345, 557, 28229),
                                                (816, 948, 2488)])):
        free = {k: v for k, v in traffic.load(name).items() if k != "plan_seed"}
        a, b = (traffic.ServeTraffic(free, 50272, s) for s in (7, 8))
        got = [a.request(i) for i in (0, 1, 999)]
        assert [(len(r["prompt"]), r["max_new"], int(r["prompt"][0]))
                for r in got] == want
        assert [len(a.request(i)["prompt"]) for i in range(50)] != \
            [len(b.request(i)["prompt"]) for i in range(50)]


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
