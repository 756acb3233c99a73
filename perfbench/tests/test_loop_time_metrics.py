"""The metrics that read the serving loop's own time (PR 54): each of the
thirteen files loads, names a reader that is there and counters the program
pre-creates, reads what its ``what`` says on hand-made marks and nothing
from a program without the counters (the parent); its manifest entries are
found BY NAME, wherever later entries put them; and a rehearsal of a decode
cell and of the mixed cell prints every reading the manifest enters there."""

import importlib
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.scheduler import ServingTelemetry  # noqa: E402
from deepspeed_tpu.monitor.metrics import MetricsRegistry  # noqa: E402

UNITS = {"host_step_ms": "ms", "host_schedule_ms": "ms",
         "host_inputs_ms": "ms", "host_dispatch_ms": "ms",
         "host_sample_ms": "ms", "host_commit_ms": "ms",
         "host_release_ms": "ms", "host_book_ms": "ms",
         "host_other_ms": "ms",
         "host_offcpu_share": "%", "late_time_share": "%",
         "commit_record_us_per_row": "us", "commit_wake_us_per_row": "us"}
#: the manifest holds 128 per-layer metrics at most and had 108: the mixed
#: cell enters six of the thirteen (PERF.md section 7; its counters are all
#: there, ``benchmarks/serve_stall_probe.py`` prints them)
MIXED = ["host_step_ms", "host_dispatch_ms", "host_commit_ms",
         "host_other_ms", "host_offcpu_share", "late_time_share"]
S = "serving/"
#: a window of 1,000 steps in 51 s, by counter
START = {S + k: 5.0 for k in ServingTelemetry._LOOP}
GROWN = {"loop_steps": 1000, "loop_busy_ms": 4000.0, "loop_cpu_ms": 375.0,
         "loop_cpu_busy_ms": 500.0,
         "loop_schedule_ms": 200.0, "loop_inputs_ms": 300.0,
         "loop_dispatch_ms": 400.0, "loop_sample_ms": 100.0,
         "loop_fetch_ms": 9000.0, "loop_commit_ms": 2500.0,
         "loop_release_ms": 150.0, "loop_intake_ms": 50.0,
         "loop_book_ms": 120.0,
         "late_ms": 510.0, "late_slack_ms": 51.0,
         "commit_sampled_rows": 4000, "commit_record_ms": 20.0,
         "commit_wake_ms": 12.0}
READS = {"host_step_ms": 4.0, "host_schedule_ms": 0.2, "host_inputs_ms": 0.3,
         "host_dispatch_ms": 0.4, "host_sample_ms": 0.1,
         "host_commit_ms": 2.5, "host_release_ms": 0.15,
         "host_book_ms": 0.12, "host_other_ms": 0.18, "host_offcpu_share": 25.0,
         "late_time_share": 1.0, "commit_record_us_per_row": 5.0,
         "commit_wake_us_per_row": 3.0}


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _facts(start, end):
    return {"window": {"seconds": 51.0, "marks": {
        "start": {"counters": start}, "end": {"counters": end}}}}


def test_the_hand_made_window_is_whole():
    assert set(GROWN) == set(ServingTelemetry._LOOP)
    assert set(READS) == set(UNITS)


@pytest.mark.parametrize("name", sorted(UNITS))
def test_a_file_reads_the_programs_counters(name):
    spec = _spec(name)
    assert spec["what"] and spec["reader"] in ("counter_ratio",
                                               "counter_rate")
    reader = importlib.import_module("readers." + spec["reader"])
    params = spec["params"]
    named = set(params["require"]) | set(params.get("num", ())) \
        | set(params.get("den", ())) | {params.get("counter", S + "late_ms")}
    # every counter it names exists at 0 once a telemetry does
    registry = MetricsRegistry()
    ServingTelemetry(registry)
    there = registry.snapshot()["counters"]
    assert named <= set(there) and not any(there[k] for k in named)
    assert set(params["require"]) <= named
    end = {S + k: START[S + k] + v for k, v in GROWN.items()}
    assert reader.read(params, _facts(START, end)) \
        == pytest.approx(READS[name])
    # the parent's program has none of the counters: nothing, and no raise
    other = {S + "decode_steps": 7.0}
    assert reader.read(params, _facts(other, other)) is None
    # a window in which the loop took no step has no step to divide by
    if spec["reader"] == "counter_ratio":
        assert reader.read(params, _facts(START, START)) is None


@pytest.mark.parametrize("group,moves", [
    ("decode", "serve_out_tokens_per_s"), ("mixed", "itl_p90_ms")])
@pytest.mark.parametrize("name", sorted(UNITS))
def test_the_manifests_entries_by_name(name, group, moves):
    manifest = _manifest()
    found = [m for m in manifest["per_layer"]
             if m["name"] == f"{group}.{name}"]
    if group == "mixed" and name not in MIXED:
        assert not found
        return
    (entry,) = found
    (e2e,) = [m for m in manifest["end_to_end"] if m["name"] == moves]
    assert entry == {"name": f"{group}.{name}", "unit": UNITS[name],
                     "better": "lower", "source": "program_counter",
                     "layer": "serving engine", "moves": moves,
                     "workloads": e2e["workloads"]}


def test_the_manifest_keeps_to_its_limit():
    assert len(_manifest()["per_layer"]) <= 128


@pytest.mark.parametrize("cell,trace,group,names", [
    ("lfm2_24b_serve_rollout", "0", "decode", sorted(UNITS)),
    ("opt1b3_serve_mixed", "1", "mixed", MIXED)])
def test_a_rehearsal_prints_the_readings(cell, trace, group, names):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--rehearse", "--trace", trace, "--seed", "2147483999",
         "--seconds", "6"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"]
    assert {f"{group}.{n}" for n in names} <= set(last["per_layer_names"])
    (line,) = [ln for ln in out.stdout.splitlines()
               if "per-layer (what this run could read): " in ln]
    read = json.loads(line.split("could read): ", 1)[1])
    for n in names:
        assert read[f"{group}.{n}"]["unit"] == UNITS[n]
        assert read[f"{group}.{n}"]["value"] >= 0 \
            or n in ("host_other_ms", "host_offcpu_share")
    step = read[f"{group}.host_step_ms"]["value"]
    assert 0 < step and read[f"{group}.host_commit_ms"]["value"] <= step
