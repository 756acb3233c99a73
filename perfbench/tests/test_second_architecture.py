"""A second architecture is files and entries: its toy configuration, its
map, its reference module (``reference/parallel_rope_decoder.py``: rope,
parallel residual, untied head) and manifest entries, and no edit to a file
of ``perfbench/``. Run end to end in the rehearsal, which the toy's
configuration file names as its own (``"rehearsal"``). Then the same serve
cell checked against the wrong reference, to show that the check bites
through the seam."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TOY = "rehearsal-neox-tiny"


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    """BENCHMARK.json with the toy as a configuration and two cells over it,
    each reporting what the cell it is modelled on reports."""
    manifest = _load(ROOT, "BENCHMARK.json")
    manifest["configs"].append({
        "name": TOY, "source": "none", "reduced": [], "why": "a toy",
        "file": f"perfbench/configs/{TOY}.json"})
    for name, traffic, like in (
            ("neox_toy_serve", "closed_decode", "opt1b3_serve_decode"),
            ("neox_toy_train", "train_zero1_seq2048", "bloom560m_train_1chip")):
        manifest["workloads"].append({
            "name": name, "config": TOY, "traffic": traffic, "chips": 1,
            "why": "a toy cell of the second architecture, rehearsal only"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    path = tmp_path_factory.mktemp("neox") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def rehearse(manifest, cell, *rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2500000321", "--seconds", "10", "--trace", "0",
         "--manifest", manifest, "--rehearse", *rehearsal],
        capture_output=True, text=True, timeout=600)
    return run, run.stdout.strip().splitlines()


@pytest.mark.parametrize("cell, reports", [
    ("neox_toy_serve", "decode.batch_occupancy"),
    ("neox_toy_train", "train.compiles_in_window")])
def test_a_new_architecture_needs_only_files_and_entries(manifest, cell, reports):
    run, lines = rehearse(manifest, cell)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    # the toy's own rehearsal, not the dense toy
    assert f"config {TOY}," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert reports in last["per_layer_names"]


def test_the_check_bites_through_the_seam(manifest, tmp_path):
    """The same serve cell, its map pointed at ``dense_decoder``: sequential
    residual, no rope, tied head. A configuration and a map are found by
    name under ``perfbench/``; an absolute name leads to one file here,
    which therefore holds both."""
    wrong = {**_load(BENCH, "configs", TOY + ".json"),
             **_load(BENCH, "reference", "maps", TOY + ".json"),
             "reference": "dense_decoder"}
    (tmp_path / "neox-as-dense.json").write_text(json.dumps(wrong))
    run, lines = rehearse(manifest, "neox_toy_serve",
                          str(tmp_path / "neox-as-dense"))
    if run.returncode == 14:            # the wrong reference could not run
        return
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"] and last["failed"] == 0
    verdict = json.loads(next(
        ln for ln in lines if "] check (" in ln).split("): ", 1)[1])
    assert not all(s["ok"] for s in verdict["reference"])
    assert max(s["worst_gap_bf16_steps"] for s in verdict["reference"]) > 40
