"""The Solar-Open2 cell's additions: its metric files read nothing on a
configuration without the counters, and the cell's rehearsal on the CPU
backend (the ``solar_open2`` ``tiny`` preset through the serve runner), with
its control."""

import json
import os
import subprocess
import sys

from readers import counted_roofline, counter_ratio

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "solaropen2_serve_decode"
TOY = "rehearsal-solar-open2-tiny"


def metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _facts(counters, kda_ms=0.5):
    """Two decode executions of a four-layer period (one paged kernel, three
    state updates of ``kda_ms`` each) at 128 live rows a step."""
    ops, progs = [], []
    for step in range(2):
        t0 = step * 1.0
        progs.append(["jit_paged_decode", t0, 0.5])
        ops.append(["paged_decode_attention.1", t0, 0.01,
                    "jit(paged_decode)/while/body/attention/paged_decode_attention/pallas_call"])
        for l in range(3):
            ops.append([f"fusion.{l}", t0 + 0.05 * (l + 1), kda_ms / 1e3,
                        "jit(paged_decode)/while/body/linear_attention/kda_state_update/mul"])
    return {"trace": {"devices": {"0": {"ops": ops, "programs": progs}}},
            "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
            "dims": {"n_layer": 4, "n_linear_attn_layer": 3, "n_full_attn_layer": 1},
            "shapes": {"lin_heads": 64, "lin_head_dim": 128, "n_head": 64,
                       "n_kv_head": 8, "head_dim": 128},
            "window": {"marks": {"start": {"counters": {}},
                                 "end": {"counters": counters}}}}


STATEFUL = {"serving/decode_steps": 10.0, "serving/decode_state_rows": 1280.0,
            "serving/decode_live_kv_tokens": 10 * 128 * 1000.0,
            "serving/moe_layer_steps": 40.0, "serving/moe_assignments": 40 * 128.0,
            "serving/moe_max_expert_load": 40 * 6.4}
#: what a configuration without recurrent state counts (OLMoE)
STATELESS = {k: v for k, v in STATEFUL.items() if "state" not in k}


def test_the_new_metrics_read_by_hand():
    # 128 rows x 64 x 128 x 128 x 4 B read and written = 1.074 GB at 819 GB/s
    # = 1.311 ms a layer; the ops took 2.622 ms
    least_ms = 2 * 128 * 64 * 128 * 128 * 4 / 819e9 * 1e3
    got = counted_roofline.read(metric("kda_state_update_roofline")["params"],
                                _facts(STATEFUL, 2 * least_ms))
    assert abs(got - 50.0) < 1e-6
    # the GQA kernel a FULL-ATTENTION layer: 128,000 live tokens x 2 x 8 x 128
    # x 2 B = 524 MB = 0.640 ms against its 10 ms
    got = counted_roofline.read(metric("gqa_paged_decode_roofline")["params"],
                                _facts(STATEFUL))
    assert abs(got - 100 * (128000 * 2 * 8 * 128 * 2 / 819e9) / 0.01) < 1e-6
    # the busiest held expert's 6.4 rows over 128 / 40 = 3.2 a held expert
    got = counter_ratio.read(metric("held_expert_load_imbalance")["params"],
                             _facts(STATEFUL))
    assert abs(got - 2.0) < 1e-9


def test_they_read_nothing_without_the_counters():
    for name in ("kda_state_update_roofline", "gqa_paged_decode_roofline"):
        assert counted_roofline.read(metric(name)["params"], _facts(STATELESS)) is None
    assert counter_ratio.read(metric("held_expert_load_imbalance")["params"],
                              _facts(STATELESS)) is None


def rehearse(*rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3100000031", "--seconds", "8", "--trace", "0",
         "--rehearse", *rehearsal],
        capture_output=True, text=True, timeout=900)
    return run, run.stdout.strip().splitlines()


def test_the_cell_rehearses_correct_with_its_new_metrics():
    run, lines = rehearse()
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert f"config {TOY}," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] > 0 and last["failed"] == 0
    for name in ("decode.held_expert_load_imbalance", "decode.late_time_share",
                 "decode.moe_dropped_assignments", "decode.batch_occupancy",
                 "decode.preemptions"):
        assert name in last["per_layer_names"], name
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    assert per_layer["decode.preemptions"]["value"] == 0.0
    # 2 held experts of 16: under 2 touched a layer and step
    assert 0.0 < per_layer["decode.experts_touched_per_layer_step"]["value"] <= 2.0


def test_the_check_bites_on_this_cell_too(tmp_path):
    """The same cell checked against a reference whose beta is sigmoid(x Wb)
    without the factor 2 (``kda_allow_neg_eigval`` read as false): the served
    tokens are not that model's. That holds for this toy in float32 on the
    CPU; at the cell's sizes on the chip the same plant moves the final
    hidden state by 4.8% and passes 31 of 32 checks, and what the check does
    refuse there is a neighbour's state slot or a lost conv state (PERF.md
    section 6, PR 31)."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "reference", "maps", TOY + ".json")) as f:
        name_map = json.load(f)
    name_map["fixed"]["beta_scale"] = 1.0
    # an absolute name leads the harness to one file for both
    (tmp_path / "solar-beta-1.json").write_text(json.dumps({**config, **name_map}))
    run, lines = rehearse(str(tmp_path / "solar-beta-1"))
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"]
