"""A second cell is files and entries: a new traffic file, one ``workloads``
entry, its name under the metrics it reports — and no edit to a file of
``perfbench/``. Run end to end in the rehearsal (CPU backend, toy sizes)."""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_a_new_cell_needs_only_files_and_entries(tmp_path):
    mix = tmp_path / "closed_short.json"
    mix.write_text(json.dumps({
        "kind": "serve", "loop": "closed", "clients_per_row": 2, "ramp_s": 1,
        "classes": [{"share": 1, "prompt": {"dist": "uniform", "lo": 64, "hi": 512},
                     "answer": {"dist": "fixed", "value": 64}}],
        "trace_seconds": 1, "drain_s": 20, "check": {"tokens": 4}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    like = "opt1b3_serve_decode"
    manifest["workloads"].append({
        "name": "toy_closed_short", "config": "opt-1.3b", "traffic": str(mix),
        "chips": 1, "why": "a second toy cell, for the rehearsal only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append("toy_closed_short")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "toy_closed_short", "--seed", "5", "--seconds", "4", "--trace", "0",
         "--manifest", str(path), "--rehearse"],
        capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "decode.batch_occupancy" in last["per_layer_names"]
    # a rehearsal never prints a device metric
    assert not any("hbm" in n or "idle" in n for n in last["per_layer_names"])
