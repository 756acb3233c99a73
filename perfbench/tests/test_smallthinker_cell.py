"""The SmallThinker cell's additions: its cost functions by hand, its metric
files reading what a stack with window layers counts and nothing on a
registry without those counters, the manifest's entries, and the cell's
rehearsal on the CPU backend (the ``smallthinker`` ``tiny`` preset through
the serve runner, prompts that cross its window of two blocks), with its
controls."""

import json
import os
import subprocess
import sys

import pytest

import costs_window_attn
from readers import counted_roofline, counter_ratio, trace_op_time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "smallthinker21b_serve_longctx"
CONFIG = "smallthinker-21ba3b-instruct"
TOY = "rehearsal-smallthinker-tiny"
NEW = ("window_paged_decode_roofline", "window_attention_time_share",
       "live_window_kv_blocks_per_step")
SHAPES = {"n_head": 28, "n_kv_head": 4, "head_dim": 128, "window": 4096,
          "n_full_attn_layer": 3, "n_window_layer": 9, "n_layer": 12}


def metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _facts(counters):
    return {"window": {"marks": {"start": {"counters": {}},
                                 "end": {"counters": counters}}}}


def test_the_cost_functions_by_hand():
    # 16 rows at position 9,000: a full layer reads 144,000 tokens, a window
    # layer 16 x 4,096; 2 KB a token (2 x 4 x 128 x 2 B); the mean layer
    flops, nbytes = costs_window_attn.window_paged_decode(
        {**SHAPES, "live_kv_tokens": 144000.0,
         "live_window_kv_tokens": 65536.0})
    mean = (144000 * 3 + 65536 * 9) / 12
    assert nbytes == mean * 2048 and flops == 4 * mean * 28 * 128
    # a bucket of 8,192: 4,096 x 8,192 - 4,096^2 / 2 scores a head
    flops, nbytes = costs_window_attn.flash_fwd_band({**SHAPES, "bucket": 8192.0})
    assert flops == 4 * (4096 * 8192 - 4096 * 4096 / 2) * 28 * 128
    assert nbytes == 2 * 8192 * 32 * 128 * 2
    # a bucket inside the window is the plain triangle
    flops, _ = costs_window_attn.flash_fwd_band({**SHAPES, "bucket": 2048.0})
    assert flops == 4 * (2048 * 2048 / 2) * 28 * 128


#: ten decode steps of 16 rows deep in their contexts, and two prefills
WINDOWED = {"serving/decode_steps": 10.0,
            "serving/decode_live_kv_tokens": 1440000.0,
            "serving/decode_live_window_kv_tokens": 655360.0,
            "serving/decode_live_window_kv_blocks": 5280.0,
            "serving/decode_live_kv_blocks": 11360.0,
            "serving/prefill_steps": 2.0,
            "serving/prefill_padded_tokens": 16384.0}
#: what a stack without window layers counts
PLAIN = {k: v for k, v in WINDOWED.items() if "window" not in k}


def _trace():
    """One decode step and one prefill: [name, start, duration, scope]."""
    ops = [["paged_decode_attention", 0.00, 0.001,
            "jit(paged_decode)/while/body/attention/paged_decode_attention"],
           ["paged_decode_attention", 0.01, 0.003,
            "jit(paged_decode)/while/body/window_attention/paged_decode_attention"],
           ["fusion.9", 0.02, 0.006, "jit(paged_decode)/while/body/mlp/experts/dot"],
           ["flash_fwd_band", 1.00, 0.090,
            "jit(paged_prefill)/while/body/window_attention/flash_fwd_band"],
           ["flash_fwd", 1.10, 0.010,
            "jit(paged_prefill)/while/body/attention/flash_fwd"]]
    programs = [["jit_paged_decode", 0.0, 0.03], ["jit_paged_prefill", 1.0, 0.2]]
    return {"devices": {"0": {"ops": ops, "programs": programs}}}


def test_the_new_metrics_read_by_hand():
    assert counter_ratio.read(
        metric("live_window_kv_blocks_per_step")["params"],
        _facts(WINDOWED)) == 528.0                 # 16 rows x 33 blocks
    facts = {**_facts(WINDOWED), "trace": _trace(), "dims": SHAPES,
             "shapes": SHAPES, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}
    # the ops under `window_attention` over the device's busy time
    got = trace_op_time.read(metric("window_attention_time_share")["params"], facts)
    assert abs(got - 100.0 * 0.093 / 0.110) < 1e-9
    # the paged kernel of both kinds inside the decode program, all layers
    mean = (144000 * 3 + 65536 * 9) / 12
    least = mean * 2048 / 819e9 * 12
    got = counted_roofline.read(
        metric("window_paged_decode_roofline")["params"], facts)
    assert abs(got - 100.0 * least / 0.004) < 1e-9


def test_they_read_nothing_without_the_window_counters():
    facts = {**_facts(PLAIN), "trace": _trace(), "dims": SHAPES,
             "shapes": SHAPES, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}
    assert counter_ratio.read(
        metric("live_window_kv_blocks_per_step")["params"], facts) is None
    assert counted_roofline.read(
        metric("window_paged_decode_roofline")["params"], facts) is None


def test_the_manifest_enters_them_for_this_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].rpartition(".")[2] in NEW}
    assert len(mine) == 3
    for m in mine.values():
        # a later cell with a window may enter itself beside this one
        assert CELL in m["workloads"] and m["moves"] == "serve_out_tokens_per_s"
    # the 3 s of trace often hold no prefill (the traffic's `why`), and a
    # traced run has to report every metric the cell is listed under: what
    # reads a prefill program is no entry of this cell
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert "decode.window_flash_roofline" not in by_name
    assert CELL not in by_name["decode.prefill_ms_per_ktoken"]["workloads"]
    # the cell stays out of what takes every KV layer to read every token,
    # and of what another stack's parts count
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in ("decode.paged_decode_roofline", "decode.gqa_paged_decode_roofline",
                 "decode.kda_state_update_roofline", "decode.shared_expert_time_share",
                 "decode.held_expert_load_imbalance", "decode.tokens_per_row_pass"):
        assert name not in listed
    for name in ("decode.expert_load_imbalance", "decode.expert_matmul_roofline",
                 "decode.live_kv_tokens_per_step", "decode.batch_occupancy",
                 "decode.paged_decode_time_share", "compile_cache_misses"):
        assert name in listed
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "closed_longctx_8k"
    assert cell["config"] == CONFIG and len(cell["why"]) <= 200
    e2e = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_out_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.03


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") \
        else []
    row = next((r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"),
               None)
    published = row["config"] if row else {
        "head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "sliding_window_size": 4096, "vocab_size": 151936,
        "max_position_embeddings": 16384, "rope_theta": 1500000}
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 12
    assert config["num_hidden_layers_published"] == 52
    assert len(config["reduced"]) == 1 and \
        config["reduced"][0].startswith("num_hidden_layers")
    # the bytes the file states: 12 layers and both ends, in bf16
    layer = 4 * 0 + 2560 * 3584 * 2 + 2560 * 512 * 2 + 5120 + 2560 * 64 \
        + 64 * 3 * 2560 * 768
    assert layer == 398_627_840
    assert str(12 * layer + 2 * 151936 * 2560 + 2560) == "5561448960"
    assert "5,561,448,960" in config["assumed"]["parameter_count"]
    serve = config["assumed"]["serve"]
    assert serve["block_size"] == 128 and 12 <= serve["max_running"] <= 16
    # a longest request's blocks for every row, and the dummy
    assert serve["max_num_blocks"] == serve["max_running"] * 90 + 1


def rehearse(*rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3700000037", "--seconds", "8", "--trace", "0",
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
    for name in ("decode.live_window_kv_blocks_per_step",
                 "decode.live_kv_tokens_per_step", "decode.batch_occupancy",
                 "decode.expert_load_imbalance", "decode.preemptions",
                 "decode.moe_dropped_assignments", "decode.late_time_share"):
        assert name in last["per_layer_names"], name
    assert "decode.paged_decode_roofline" not in last["per_layer_names"]
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    assert per_layer["decode.preemptions"]["value"] == 0.0
    assert per_layer["decode.compiles_in_window"]["value"] == 0.0
    # 4 rows, a window of two blocks: at most 3 ring blocks a row, and the
    # rows (307-576 tokens: 3-5 blocks of a full layer) are past it
    assert 4.0 <= per_layer["decode.live_window_kv_blocks_per_step"]["value"] <= 12.0
    assert per_layer["decode.live_kv_tokens_per_step"]["value"] > 4 * 256
    # both warmed buckets are those `_bucket` names for the toy's prompts
    assert sum("warm-up: prompt bucket" in ln for ln in lines) == 2


@pytest.mark.parametrize("control,change", [
    ("window_64_for_256", {"window": 64}),
    ("window_left_out", {"window_layout": [0, 0, 0, 0]}),
    ("rope_on_full_layers", {"rope_layout": [1, 1, 1, 1]})])
def test_the_check_bites_on_this_cell_too(tmp_path, control, change):
    """The same cell checked against a reference with ONE thing of the
    family changed on its side: the served tokens are not that model's, and
    the run is not ``correct``. The configuration's seeded init (peaked
    attention: ``models/presets.py`` ``smallthinker``, the toy in the same
    regime) is what lets a served token say so; the cell's own sizes are
    measured on the chip by ``benchmarks/smallthinker_check_controls.py``
    (PERF.md section 6, PR 37)."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "reference", "maps", TOY + ".json")) as f:
        name_map = json.load(f)
    # an absolute name leads the harness to one file for both
    (tmp_path / f"st-{control}.json").write_text(
        json.dumps({**config, **name_map, **change}))
    run, lines = rehearse(str(tmp_path / f"st-{control}"))
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"]
