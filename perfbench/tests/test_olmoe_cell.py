"""The OLMoE cell's additions: the cost functions beside ``costs.py``, the
reader that sets a scope's device time against operands the program counted,
and the cell's rehearsal on the CPU backend, with its control."""

import json
import os
import subprocess
import sys

import pytest

import costs_moe
from readers import counted_roofline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "olmoe1b7b_serve_decode"
OLMOE = {"d_model": 2048, "n_layer": 16, "d_expert": 1024, "n_experts": 64,
         "experts_per_token": 8, "vocab": 50304}


def test_costs_by_hand():
    # one expert's three matrices: 3 x 2,048 x 1,024
    assert costs_moe.expert_matmuls(
        {**OLMOE, "assignments": 512, "experts_touched": 64}) == (
        512 * 3 * 2 * 2048 * 1024, 64 * 3 * 2048 * 1024 * 2)
    assert costs_moe.expert_matmuls(
        {**OLMOE, "assignments": 8, "experts_touched": 8})[1] == 8 * 6291456 * 2
    # the parameters a token touches in the published 16 layers: 1.3B of 6.9B
    active = costs_moe.active_param_count(OLMOE)
    assert active == 16 * (4 * 2048 ** 2 + 2048 * 64 + 8 * 6291456) + 50304 * 2048
    assert 1.15e9 < active < 1.35e9
    assert costs_moe.train_flops_per_token(OLMOE, 4096) == \
        6.0 * active + 12.0 * 16 * 2048 * 4096


def _facts(experts_ms):
    """Two decode executions of two layers each, with one op of
    ``experts_ms`` a layer under ``experts``, and after each a prefill
    execution with ops under the same scope; counters: every layer step
    touched 4 experts with 16 assignments."""
    ops, progs = [], []
    for step in range(2):
        t0 = step * 1.0
        progs.append(["jit_paged_decode", t0, 0.5])
        progs.append(["jit_paged_prefill", t0 + 0.6, 0.2])
        for l in range(2):
            at = t0 + 0.1 * l
            ops.append(["paged_decode_attention.1", at, 0.01,
                        "jit(paged_decode)/while/body/attention/pallas_call"])
            ops.append(["fusion.7", at + 0.02, experts_ms / 1e3,
                        "jit(paged_decode)/while/body/mlp/experts/dot_general"])
        ops.append(["flash_fwd.3", t0 + 0.61, 0.01,
                    "jit(paged_prefill)/while/body/attention/pallas_call"])
        ops.append(["ragged-dot-none.2", t0 + 0.65, 0.002, "ragged-dot-none"])
        ops.append(["fusion.9", t0 + 0.7, 0.002,
                    "jit(paged_prefill)/while/body/mlp/experts/mul"])
    counters = {"serving/moe_layer_steps": 10.0, "serving/moe_assignments": 160.0,
                "serving/moe_experts_touched": 40.0}
    return {"trace": {"devices": {"0": {"ops": ops, "programs": progs}}},
            "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
            "dims": {"n_layer": 2}, "shapes": {"d_model": 2048, "d_expert": 1024},
            "window": {"marks": {"start": {"counters": {}},
                                 "end": {"counters": counters}}}}


PARAMS = {"scope": "/experts/|^ragged-dot",
          "program_contains": "paged_decode_attention", "per_execution": "n_layer",
          "per": "serving/moe_layer_steps",
          "operands": {"assignments": "serving/moe_assignments",
                       "experts_touched": "serving/moe_experts_touched"},
          "cost": "costs_moe:expert_matmuls",
          "require": ["serving/moe_layer_steps"]}


def test_counted_roofline_on_a_small_fixture():
    # 4 experts x 12.58 MB at 819 GB/s = 0.0614 ms a layer step (memory-bound:
    # 16 assignments are 0.0010 ms of arithmetic); the ops took 0.1228 ms
    least_ms = 4 * 3 * 2048 * 1024 * 2 / 819e9 * 1e3
    # the prefill executions' ops under the same scope, and their ragged-dot,
    # are not the decode step's: only what ran inside a decode execution counts
    got = counted_roofline.read(PARAMS, _facts(2 * least_ms))
    assert got == pytest.approx(50.0, rel=1e-6)
    # and the other way round: 2 ms of such ops a layer of a prefill execution
    assert counted_roofline.read({**PARAMS, "program_contains": "flash_fwd"},
                                 _facts(2 * least_ms)) == pytest.approx(
        100 * least_ms / 2.0, rel=1e-6)


def test_counted_roofline_reads_nothing_without_its_counters_or_a_trace():
    facts = _facts(0.1)
    facts["window"]["marks"]["end"]["counters"] = {"serving/decode_steps": 5.0}
    assert counted_roofline.read(PARAMS, facts) is None      # the parent commit
    facts = _facts(0.1)
    facts["trace"] = None
    assert counted_roofline.read(PARAMS, facts) is None      # --trace 0
    facts = _facts(0.1)
    facts["trace"]["devices"]["0"]["ops"] = [
        op for op in facts["trace"]["devices"]["0"]["ops"]
        if "experts" not in op[3] and "ragged" not in op[3]]
    assert counted_roofline.read(PARAMS, facts) is None      # a dense model


def rehearse(*rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2600000027", "--seconds", "8", "--trace", "0",
         "--rehearse", *rehearsal],
        capture_output=True, text=True, timeout=600)
    return run, run.stdout.strip().splitlines()


def test_the_cell_rehearses_correct_with_its_new_metrics():
    run, lines = rehearse()
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert "config rehearsal-olmoe-tiny," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] > 0 and last["failed"] == 0
    for name in ("decode.experts_touched_per_layer_step",
                 "decode.expert_load_imbalance", "decode.moe_dropped_assignments",
                 "decode.batch_occupancy"):
        assert name in last["per_layer_names"], name
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    assert 1.0 <= per_layer["decode.experts_touched_per_layer_step"]["value"] <= 8.0


def test_the_check_bites_on_this_cell_too(tmp_path):
    """The same cell checked against ``dense_decoder`` (LayerNorm, biases, one
    MLP, a tied head): either that reference cannot run on these weights, or
    it runs and disagrees."""
    with open(os.path.join(BENCH, "configs", "rehearsal-olmoe-tiny.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "reference", "maps", "rehearsal-olmoe-tiny.json")) as f:
        name_map = json.load(f)
    (tmp_path / "olmoe-as-dense.json").write_text(json.dumps(
        {**config, **name_map, "reference": "dense_decoder"}))
    run, lines = rehearse(str(tmp_path / "olmoe-as-dense"))
    if run.returncode == 14:            # the wrong reference could not run
        return
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"]
