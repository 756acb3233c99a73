"""The trace reduction on a small recorded trace (two decode steps from the
chip, ``data/decode_two_steps.json``) and on hand-made intervals. Each
reduction is checked against a brute-force raster of the same events."""

import json
import os

import numpy as np
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "decode_two_steps.json")
TICK = 1e-7          # the raster's grain; events are recorded to the ns


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return json.load(f)


def raster(intervals, lo, hi):
    n = int(round((hi - lo) / TICK))
    grid = np.zeros(n, bool)
    for s, e in intervals:
        grid[int(round((s - lo) / TICK)):int(round((e - lo) / TICK))] = True
    return grid


def test_busy_union_matches_a_raster(trace):
    dev = trace["devices"]["/device:TPU:0"]
    lo, hi = tr.window_of(trace)
    grid = raster([(s, s + d) for _, s, d in dev["ops"]], lo, hi)
    assert tr.busy_seconds(dev["ops"]) == pytest.approx(grid.sum() * TICK, rel=2e-3)
    # nested ops (a loop and its body) are not counted twice
    assert tr.busy_seconds(dev["ops"]) <= hi - lo


def test_self_time_sums_to_busy_time(trace):
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    by_name = tr.sum_by_name(ops)
    assert sum(v[0] for v in by_name.values()) == pytest.approx(
        tr.busy_seconds(ops), rel=1e-6)
    assert sum(v[1] for v in by_name.values()) == len(ops)


def test_per_name_sums(trace):
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    secs, calls = tr.matching(ops, "paged_decode_attention")
    # two decode steps x 24 layers, each call of the kernel a leaf
    assert calls == 48
    assert secs == pytest.approx(sum(d for n, _, d in ops
                                     if "paged_decode_attention" in n))
    assert tr.sum_by_name(ops)["paged_decode_attention.9"] == [
        pytest.approx(secs), 48]


def test_programs_are_told_apart_by_what_ran_inside(trace):
    dev = trace["devices"]["/device:TPU:0"]
    decode = tr.programs_containing(dev, "paged_decode_attention")
    assert len(decode) == 2 and all(d > 0.1 for _, _, d in decode)
    assert tr.programs_containing(dev, "flash_fwd") == []


def test_gaps_and_their_attribution(trace):
    dev = trace["devices"]["/device:TPU:0"]
    lo, hi = tr.window_of(trace)
    found = tr.gaps(dev["ops"], (lo, hi))
    grid = raster([(s, s + d) for _, s, d in dev["ops"]], lo, hi)
    assert sum(d for _, d in found) == pytest.approx((~grid).sum() * TICK, rel=2e-2)
    assert sum(d for _, d in found) + tr.busy_seconds(dev["ops"]) == \
        pytest.approx(hi - lo, rel=1e-9)
    by = tr.attribute(found, trace["host"])
    assert sum(by.values()) == pytest.approx(sum(d for _, d in found))
    # the host spans of the file sit in the idle stretch between the two
    # steps: 4 ms of it under "sample", of which the 1 ms under "emit" goes
    # to "emit" (the innermost span), 0.5 ms before them to "exec"
    assert by["emit"] == pytest.approx(0.001, rel=1e-3)
    assert by["sample"] == pytest.approx(0.003, rel=1e-3)
    assert by["exec"] > 0 and by["unattributed"] > 0


def test_breakdown_lists_at_most_ten_each(trace):
    b = tr.breakdown(trace)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    names = [n for n, _ in b["device_ops"]]
    assert "paged_decode_attention.9" in names and any(
        n.startswith("copy") for n in names)


# ------------------------------------------------------- hand-made intervals

OPS = [["while.1", 0.0, 10.0], ["fusion.1", 1.0, 2.0], ["all-gather.1", 3.0, 2.0],
       ["fusion.2", 5.0, 2.0], ["all-reduce.2", 12.0, 1.0]]
ASYNC = [["all-gather-start.1", 2.0, 4.0]]      # another line: overlaps compute


def test_union_total_and_uncovered():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total(tr.union([(0, 2), (1, 3), (5, 6)])) == 4
    mine = tr.spans(OPS, tr.COLLECTIVE)
    others = tr.spans(OPS, "fusion")
    # [3,5) and [12,13) run while no fusion does
    assert tr.uncovered(mine, others) == pytest.approx(3.0)
    # [2,6) less [1,3) and [5,7): [3,5)
    assert tr.uncovered(tr.spans(ASYNC), others) == pytest.approx(2.0)
    assert tr.uncovered([(0, 10)], []) == 10
    assert tr.uncovered([(0, 10)], [(0, 10)]) == 0


def test_self_times_take_children_out():
    got = {n: s for n, _, _, s in tr.self_times(
        sorted(OPS, key=lambda e: (e[1], -e[2])))}
    # while.1 spans [0,10); its children cover [1,3), [3,5), [5,7)
    assert got["while.1"] == pytest.approx(4.0)
    assert got["fusion.1"] == 2.0 and got["all-reduce.2"] == 1.0


def test_gaps_on_hand_made_window():
    assert tr.gaps(OPS, (0.0, 14.0)) == [(10.0, 2.0), (13.0, 1.0)]
    assert tr.gaps(OPS, (0.0, 14.0), min_gap=1.5) == [(10.0, 2.0)]
    by = tr.attribute([(10.0, 2.0), (13.0, 1.0)],
                      [["step", 9.0, 2.5], ["input", 10.5, 0.5]])
    assert by == {"input": pytest.approx(0.5), "step": pytest.approx(1.0),
                  "unattributed": pytest.approx(1.5)}
