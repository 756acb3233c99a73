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


# ------------------------------------------------------------ an op's scope

class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    events = [
        _Event("%fusion.142 = bf16[40,2048]{1,0} fusion(%copy.3, %p.1), kind=kOutput",
               2000, 500),
        _Event("%paged_decode_attention.9 = bf16[40,32,64] custom-call(%fusion.142)",
               1000, 900),
        _Event("%copy.20 = bf16[2048,2048]{0,1} copy(%p.4)", 3000, 100)]


def test_an_op_keeps_its_name_and_gains_its_scope():
    scopes = {_Line.events[0].name: "jit(paged_decode)/while/body/mlp/dot_general",
              _Line.events[1].name: "jit(paged_decode)/while/body/attention/pallas_call"}
    ops = tr._events(_Line, own_names=True, scopes=scopes)
    assert [e[:3] for e in ops] == [
        ["paged_decode_attention.9", 1e-6, 9e-7], ["fusion.142", 2e-6, 5e-7],
        ["copy.20", 3e-6, 1e-7]]
    assert [tr.scope_of(e) for e in ops] == [
        "jit(paged_decode)/while/body/attention/pallas_call",
        "jit(paged_decode)/while/body/mlp/dot_general", ""]
    # a trace without scopes gives the same names; a program (XLA Modules)
    # has a name and no scope
    assert [e[:3] for e in tr._events(_Line, own_names=True)] == [e[:3] for e in ops]
    assert all(len(e) == 3 for e in tr._events(_Line, scopes=scopes))


SCOPED = [["while.1", 0.0, 8.0, "jit(f)/while"],
          ["fusion.1", 1.0, 2.0, "jit(f)/while/body/mlp/dot_general"],
          ["fusion.2", 3.0, 2.0, "jit(f)/while/body/attention/dot_general"],
          ["fusion.3", 5.0, 2.5, "jit(f)/while/body/mlp/mul"],
          ["fusion.1", 12.0, 1.0, "jit(g)/vocab_head/dot_general"]]


def test_time_under_a_scope():
    from readers import trace_op_time

    assert tr.matching(SCOPED, "", scope="/mlp/") == (pytest.approx(4.5), 2)
    assert tr.matching(SCOPED, r"^fusion\.1$") == (pytest.approx(3.0), 2)
    assert tr.matching(SCOPED, r"^fusion\.1$", scope="/mlp/") == (pytest.approx(2.0), 1)
    # events recorded before scopes were kept match no scope, and every name
    assert tr.matching(OPS, "fusion", scope="mlp") == (0.0, 0)
    assert tr.matching(OPS, "fusion") == (pytest.approx(4.0), 2)
    facts = {"trace": {"devices": {"/device:TPU:0": {
        "ops": SCOPED, "programs": [], "async": []}}, "host": []}}
    assert trace_op_time.read({"pattern": "", "scope": "/mlp/"}, facts) == \
        pytest.approx(100 * 4.5 / 9.0)           # of 9 busy seconds
    assert trace_op_time.read({"pattern": "fusion", "scope": "/attention/",
                               "mode": "ms_per_call"}, facts) == pytest.approx(2000.0)


def test_largest_ops_carry_a_scope_and_the_breakdown_only_names():
    top = tr.largest_ops(SCOPED, top=3)
    # fusion.1 is one name in two programs: the first event's scope stands
    assert top == [["fusion.1", pytest.approx(3.0), 2, "jit(f)/while/body/mlp/dot_general"],
                   ["fusion.3", pytest.approx(2.5), 1, "jit(f)/while/body/mlp/mul"],
                   ["fusion.2", pytest.approx(2.0), 1, "jit(f)/while/body/attention/dot_general"]]
    b = tr.breakdown({"devices": {"d": {"ops": SCOPED, "programs": [], "async": []}},
                      "host": []}, top=3)
    assert b["device_ops"] == [[n, s] for n, s, _, _ in top]


# ----------------------------------------- a counter that does not exist yet

def test_a_required_counter_that_does_not_exist_reads_nothing():
    from readers import counter_delta, counter_ratio

    counters = {"serving/decode_steps": 10.0, "serving/generated_tokens": 300.0}
    facts = {"window": {"marks": {
        "start": {"counters": dict.fromkeys(counters, 0.0)},
        "end": {"counters": counters}}, "rows": 40}}
    drops = {"counter": "serving/moe_dropped_tokens"}
    # as every data file of PR 24 reads: absent counts as never moved
    assert counter_delta.read(drops, facts) == 0.0
    assert counter_delta.read({**drops, "require": ["serving/moe_dropped_tokens"]},
                              facts) is None
    share = {"num": {"serving/moe_dropped_tokens": 1},
             "den": {"serving/generated_tokens": 1}, "percent": True}
    assert counter_ratio.read(share, facts) == 0.0
    assert counter_ratio.read({**share, "require": ["serving/moe_dropped_tokens"]},
                              facts) is None
    # a counter that exists and never moved is a reading
    assert counter_delta.read({"counter": "serving/decode_steps",
                               "require": ["serving/decode_steps"]}, facts) == 10.0
    facts["window"]["marks"]["end"]["counters"]["serving/moe_dropped_tokens"] = 0.0
    assert counter_ratio.read({**share, "require": ["serving/moe_dropped_tokens"]},
                              facts) == 0.0
    # a train cell has no marks at all
    assert counter_delta.read(drops, {"window": {}}) is None


# --------------------------- the recorded step that holds scopes, and the file

SCOPED_DATA = os.path.join(os.path.dirname(__file__), "data",
                           "decode_step_scoped.json")


def test_scopes_on_the_recorded_step():
    with open(SCOPED_DATA) as f:
        trace = json.load(f)
    dev = trace["devices"]["/device:TPU:0"]
    ops = dev["ops"]
    # names as they always were: the op's own HLO name, nothing of its text
    assert not any(c in e[0] for e in ops for c in "% =")
    assert tr.matching(ops, "paged_decode_attention")[1] == 24
    # the compiler's fusion.142 is the MLP's down-projection, once a layer;
    # the up-projection carries the layer's scope no more (XLA fused it with
    # what follows the scope), so /mlp/ holds nothing else
    down = [e for e in ops if e[0] == "fusion.142"]
    assert len(down) == 24 and {tr.scope_of(e) for e in down} == {
        "jit(paged_decode)/while/body/closed_call/mlp/dot_general"}
    assert tr.matching(ops, "", scope="/mlp/") == tr.matching(ops, r"^fusion\.142$")
    assert tr.matching(ops, "", scope="/vocab_head/")[1] == 7
    assert tr.matching(ops, "pallas|custom-call", scope="/paged_decode_attention/") \
        == (0.0, 0)                       # a kernel keeps its own name
    assert tr.matching(ops, "^paged_decode", scope="/paged_decode_attention/pallas_call$") \
        == tr.matching(ops, r"^paged_decode_attention\.9$")
    # the reductions take an op with a scope as they took one without
    assert tr.busy_seconds(ops) == pytest.approx(
        sum(v[0] for v in tr.sum_by_name(ops).values()), rel=1e-6)
    assert len(tr.programs_containing(dev, "paged_decode_attention")) == 1
    top = tr.largest_ops(ops)
    assert [row[0] for row in top[:2]] == ["paged_decode_attention.9", "fusion.142"]
    assert top[1][3].endswith("/mlp/dot_general")
    assert [n for n, _ in tr.breakdown(trace)["device_ops"]] == [r[0] for r in top]
    by = tr.attribute(tr.gaps(ops, tr.window_of(trace)), trace["host"])
    assert by["serve.fetch"] > 0


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_scopes_are_read_from_the_files_own_bytes(tmp_path):
    stat_meta = lambda i, name: _field(5, _field(1, i) + _field(
        2, _field(1, i) + _field(2, name)))
    event_meta = lambda i, name, *stats: _field(4, _field(1, i) + _field(
        2, _field(1, i) + _field(2, name) + b"".join(_field(5, s) for s in stats)))
    long_name = b"%fusion.142 = bf16[40,2048]{1,0} fusion(%p.1), kind=kOutput"
    device = (_field(1, 7) + _field(2, b"/device:TPU:0")
              + _field(3, b"\x08\x01")                       # a line, passed over
              + event_meta(1, long_name,
                           _field(1, 2) + _field(5, b"convolution fusion"),
                           _field(1, 1) + _field(5, b"jit(f)/while/body/mlp/dot_general:"))
              + event_meta(2, b"%copy.20 = bf16[8]{0} copy(%p.4)",
                           _field(1, 3) + b"\x11" + bytes(8))  # a double: fixed width
              + event_meta(3, b"%custom-call.9 = bf16[8]{0} custom-call(%p.4)",
                           _field(1, 1) + _field(7, 4))        # a reference value
              + event_meta(4, long_name,                       # the same text again
                           _field(1, 1) + _field(6, b"jit(g)/mlp/dot_general:"))
              + stat_meta(1, b"tf_op") + stat_meta(2, b"hlo_category")
              + stat_meta(3, b"flops") + stat_meta(4, b"jit(f)/attention/kernel"))
    host = _field(2, b"/host:CPU") + event_meta(
        1, b"pb.serve.step", _field(1, 1) + _field(5, b"not an op")) \
        + stat_meta(1, b"tf_op")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host) + _field(4, b"a warning"))
    assert tr.op_scopes(str(path)) == {"/device:TPU:0": {
        long_name.decode(): "jit(f)/while/body/mlp/dot_general",
        "%custom-call.9 = bf16[8]{0} custom-call(%p.4)": "jit(f)/attention/kernel"}}
    with pytest.raises(ValueError):
        list(tr._fields(b"\x0b"))                            # a group: not in xplane
