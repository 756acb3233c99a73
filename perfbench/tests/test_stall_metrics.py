"""The metrics that name what stalls the serving loop (PR 35; PR 59 retired
``late_steps``, ``gc_full_collections`` and ``gap_gc_share``, which
``late_time_share``, ``gc_pause_share`` and the ledger's
``breakdown.idle_gaps`` repeat): the reader of a counter's rate on hand-made
facts, the entry that stays against its file, found by name, and a ``gc``
span of another thread taking the idle stretch from the phase of the loop it
fell into."""

import json
import os

import pytest

import trace_reduce as tr
from readers import counter_rate, trace_gap_by_annotation

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
RETIRED = ("late_steps", "gc_full_collections", "gap_gc_share")


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _facts(start, end, seconds=51.0):
    return {"window": {"seconds": seconds, "marks": {
        "start": {"counters": start}, "end": {"counters": end}}}}


def test_counter_rate_arithmetic():
    params = _spec("gc_pause_share")["params"]
    assert params["scale"] == 0.1 and params["counter"] == "host/gc_pause_ms"
    # 510 ms of collections in a 51 s window: 1% of its wall clock
    facts = _facts({"host/gc_pause_ms": 40.0}, {"host/gc_pause_ms": 550.0})
    assert counter_rate.read(params, facts) == pytest.approx(1.0)
    # a counter that exists and never moved reads 0 ...
    still = _facts({"host/gc_pause_ms": 0.0}, {"host/gc_pause_ms": 0.0})
    assert counter_rate.read(params, still) == 0.0
    # ... a program that does not count it yet (the parent) has no reading,
    # and neither has a cell whose runner takes no marks (a train cell)
    assert counter_rate.read(params, _facts({}, {})) is None
    assert counter_rate.read(params, {"window": {"seconds": 51.0}}) is None
    # the rate is a second's: no scale, events a second
    assert counter_rate.read({"counter": "n"}, _facts({}, {"n": 102.0})) \
        == pytest.approx(2.0)


@pytest.mark.parametrize("group,moves", [
    ("decode", "serve_out_tokens_per_s"), ("mixed", "itl_p90_ms")])
def test_the_manifests_entry_by_name(group, moves):
    """By name, wherever it stands in the list: a later cell enters itself
    on its ``workloads`` and a later entry follows it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == f"{group}.gc_pause_share"]
    (e2e,) = [m for m in manifest["end_to_end"] if m["name"] == moves]
    assert entry == {"name": f"{group}.gc_pause_share", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "serving engine", "moves": moves,
                     "workloads": e2e["workloads"]}
    assert _spec("gc_pause_share")["what"]
    names = {m["name"] for m in manifest["per_layer"]}
    assert not names & {f"{group}.{gone}" for gone in RETIRED}


def test_a_gc_span_of_another_thread_takes_the_gap():
    """The loop's thread is inside ``serve.commit`` (from 10 ms) when a
    client's thread starts a full collection (20-120 ms, the interpreter
    lock held); the device runs dry at 30 ms and gets its next step at 125
    ms, 5 ms after the loop got the lock back."""
    host = [["serve.step", 0.0, 0.200], ["serve.exec", 0.001, 0.190],
            ["serve.commit", 0.010, 0.115], ["gc", 0.020, 0.100]]
    ops = [["fusion.1", 0.0, 0.030, ""], ["fusion.1", 0.125, 0.030, ""]]
    idle = tr.attribute(tr.gaps(ops, (0.0, 0.155)), host)
    assert idle == {"gc": pytest.approx(0.090),
                    "serve.commit": pytest.approx(0.005)}
    facts = {"trace": {"devices": {"/device:TPU:0": {
        "ops": ops, "programs": [], "async": []}}, "host": host}}
    by = {n: trace_gap_by_annotation.read({"annotation": n}, facts)
          for n in ("gc", "serve.commit", "unattributed")}
    window = tr.window_of(facts["trace"])
    span = window[1] - window[0]
    assert by["gc"] == pytest.approx(100 * 0.090 / span)
    # the identity of attribute(): the parts still sum to the idle share
    busy = tr.busy_seconds(ops)
    assert sum(by.values()) == pytest.approx(100 * (1 - busy / span))
    # a program from before PR 35 emits no gc span: 0, and no raise
    facts["trace"]["host"] = host[:3]
    assert trace_gap_by_annotation.read({"annotation": "gc"}, facts) == 0.0
