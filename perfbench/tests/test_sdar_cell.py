"""The SDAR cell's additions: its metric files read what the block step
counts and nothing on a registry without ``serving/block_passes``, and the
cell's rehearsal on the CPU backend (the ``sdar`` ``tiny`` preset through
the serve runner: generation by diffusion over blocks end to end), with its
control."""

import json
import os
import subprocess
import sys

from readers import counter_ratio

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "sdar30b_serve_blockgen"
TOY = "rehearsal-sdar-tiny"
#: PR 59 retired ``commit_pass_share`` (no commit pass is left since PR 55)
#: and ``unmask_time_share`` (0.0016% of device time)
NEW = ("tokens_per_row_pass", "positions_per_token")


def metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _facts(counters):
    return {"window": {"marks": {"start": {"counters": {}},
                                 "end": {"counters": counters}}}}


#: ten fused passes of 64 rows: 4 denoise passes and a commit a block
BLOCKS = {"serving/block_passes": 10.0, "serving/block_row_passes": 640.0,
          "serving/block_commit_row_passes": 128.0,
          "serving/block_decided_tokens": 512.0, "serving/decode_steps": 10.0}
#: what a model that decodes a token a step counts
DECODES = {"serving/decode_steps": 10.0, "serving/generated_tokens": 640.0}


def test_the_new_metrics_read_by_hand():
    read = lambda name: counter_ratio.read(  # noqa: E731
        metric(name)["params"], _facts(BLOCKS))
    assert abs(read("tokens_per_row_pass") - 0.8) < 1e-12
    assert abs(read("positions_per_token") - 5.0) < 1e-12


def test_they_read_nothing_without_the_block_counters():
    for name in NEW:
        assert counter_ratio.read(metric(name)["params"], _facts(DECODES)) is None


def test_the_manifest_enters_them_for_this_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].rpartition(".")[2] in NEW}
    assert len(mine) == 2
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_out_tokens_per_s"
    # and the cell stays out of what assumes a token a row-step or another stack
    for m in manifest["per_layer"]:
        if m["name"].rpartition(".")[2] in (
                "batch_occupancy", "expert_load_imbalance",
                "held_expert_load_imbalance", "kda_state_update_roofline",
                "gqa_paged_decode_roofline", "shared_expert_time_share",
                "linear_attention_time_share"):
            assert CELL not in m["workloads"], m["name"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "closed_blockgen_4x4"


def rehearse(*rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3300000033", "--seconds", "8", "--trace", "0",
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
    for name in ("decode.tokens_per_row_pass", "decode.positions_per_token",
                 "decode.moe_dropped_assignments", "decode.preemptions",
                 "decode.live_kv_tokens_per_step"):
        assert name in last["per_layer_names"], name
    assert "decode.batch_occupancy" not in last["per_layer_names"]
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    assert per_layer["decode.preemptions"]["value"] == 0.0
    assert per_layer["decode.compiles_in_window"]["value"] == 0.0
    # 4 denoise passes a block of 4, its commit riding the next block's
    # first pass since PR 55 (1.0 and 4.0 but for the passes that the
    # window's two edges cut: a pass counts when it is dispatched, its
    # tokens when they land)
    assert 0.95 < per_layer["decode.tokens_per_row_pass"]["value"] < 1.05
    assert 3.8 < per_layer["decode.positions_per_token"]["value"] < 4.2
    # 4 held experts of 8: at most 4 touched a layer and step
    assert 0.0 < per_layer["decode.experts_touched_per_layer_step"]["value"] <= 4.0


def test_the_check_bites_on_this_cell_too(tmp_path):
    """The same cell checked against a reference whose mask is the plain
    causal one inside a block too (block length 1: every row then reads a
    pass that never saw the block's later ``[MASK]`` positions): the served
    tokens are not that model's."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "reference", "maps", TOY + ".json")) as f:
        name_map = json.load(f)
    del name_map["from_config"]["block"]
    name_map["fixed"]["block"] = 1
    # an absolute name leads the harness to one file for both
    (tmp_path / "sdar-block-1.json").write_text(json.dumps({**config, **name_map}))
    run, lines = rehearse(str(tmp_path / "sdar-block-1"))
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"]
