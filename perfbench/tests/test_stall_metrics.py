"""The metrics that name what stalls the serving loop (PR 35): the reader of
a counter's rate on hand-made facts, the eight entries the manifest gained
against their files, and a ``gc`` span of another thread taking the idle
stretch from the phase of the loop it fell into."""

import json
import os

import pytest

import trace_reduce as tr
from readers import counter_delta, counter_rate, trace_gap_by_annotation

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DECODE = ["opt1b3_serve_decode", "olmoe1b7b_serve_decode",
          "solaropen2_serve_decode", "sdar30b_serve_blockgen"]
NEW = {"late_steps": ("count", "program_counter"),
       "gc_pause_share": ("%", "program_counter"),
       "gc_full_collections": ("count", "program_counter"),
       "gap_gc_share": ("%", "device_trace")}


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


@pytest.mark.parametrize("name", ["late_steps", "gc_full_collections"])
def test_the_counted_metrics_read_growth_or_nothing(name):
    spec = _spec(name)
    assert spec["reader"] == "counter_delta"
    counter = spec["params"]["counter"]
    assert spec["params"]["require"] == [counter]
    facts = _facts({counter: 3.0}, {counter: 11.0})
    assert counter_delta.read(spec["params"], facts) == 8.0
    assert counter_delta.read(spec["params"], _facts({}, {"x": 1.0})) is None


@pytest.mark.parametrize("group,moves,cells", [
    ("decode", "serve_out_tokens_per_s", DECODE),
    ("mixed", "itl_p90_ms", ["opt1b3_serve_mixed"])])
@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifests_new_entries(name, group, moves, cells):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    unit, source = NEW[name]
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == f"{group}.{name}"]
    assert entry == {"name": f"{group}.{name}", "unit": unit,
                     "better": "lower", "source": source,
                     "layer": "serving engine", "moves": moves,
                     "workloads": cells}
    # at the end of the list: an entry put in the middle reads as a change
    tail = [m["name"].rpartition(".")[2] for m in manifest["per_layer"][-8:]]
    assert sorted(set(tail)) == sorted(NEW)
    assert _spec(name)["what"]


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
    assert trace_gap_by_annotation.read(_spec("gap_gc_share")["params"],
                                        facts) == 0.0
