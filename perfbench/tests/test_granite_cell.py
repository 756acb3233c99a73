"""The granite-4.0-h-micro cell's additions: its metric files reading what a
stack with Mamba-2 layers counts and traces (and nothing on a registry without
the state counter), the manifest's entries, the configuration against the
catalog, the map's sizes feeding ``costs_mamba2``, and the cell's rehearsal on
the CPU backend (the ``granite_hybrid`` ``tiny`` preset, one whole published
period, through the serve runner)."""

import json
import os
import subprocess
import sys

import pytest

import costs_mamba2
import correctness
from readers import counted_roofline, trace_op_time, trace_program_time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "granite4hmicro_serve_chat"
CONFIG = "granite-4.0-h-micro"
TOY = "rehearsal-granite-hybrid-tiny"
NEW = ("mamba2_time_share", "ssd_state_update_roofline", "ssd_scan_time_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def metric(name):
    return load(BENCH, "layer_metrics", name + ".json")


def dims_of(config_name):
    sys.path.insert(0, BENCH)
    import run
    return run.model_dims(load(BENCH, "configs", config_name + ".json"),
                          correctness.load_map(config_name))


def test_the_maps_sizes_feed_the_cost_functions():
    dims = dims_of(CONFIG)
    assert (dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_state"],
            dims["ssm_chunk"]) == (64, 64, 128, 256)
    assert (dims["n_mamba_layer"], dims["n_full_attn_layer"], dims["n_layer"]) \
        == (36, 4, 40)
    assert (dims["n_head"], dims["n_kv_head"], dims["head_dim"]) == (32, 8, 64)
    # a row's state in one layer is 2 MB, in all 36 the 75.5 MB of the issue
    _, nbytes = costs_mamba2.ssd_decode_update({**dims, "state_rows": 1.0})
    assert nbytes * dims["n_mamba_layer"] // 2 == 75_497_472
    toy = dims_of(TOY)
    assert (toy["n_mamba_layer"], toy["n_full_attn_layer"], toy["n_layer"]) \
        == (9, 1, 10)
    assert costs_mamba2.ssd_decode_update({**toy, "state_rows": 2.0}) \
        == (6.0 * 2 * 4 * 32 * 16, 2 * 2 * 4 * 32 * 16 * 4)


def _facts(counters, dims):
    return {"window": {"marks": {"start": {"counters": {}},
                                 "end": {"counters": counters}}},
            "dims": dims, "shapes": dims,
            "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}


#: ten decode steps of 64 live rows, and two prefills of 256 and 512 tokens
COUNTED = {"serving/decode_steps": 10.0, "serving/decode_state_rows": 640.0,
           "serving/decode_live_kv_tokens": 256000.0,
           "serving/prefill_steps": 2.0}


def _trace():
    """One decode step (its 36 Mamba-2 layers' ops folded into three) and one
    prefill: [name, start, duration, scope]."""
    d, p = "jit(paged_decode)/while/body/", "jit(paged_prefill)/while/body/"
    ops = [["fusion.1", 0.000, 0.002, d + "mamba2/in_proj/dot_general"],
           ["add_dynamic-update-slice_fusion.3", 0.002, 0.020,
            d + "mamba2/ssd_state_update/while/body/add"],
           ["fusion.7", 0.022, 0.004, d + "mamba2/ssd_state_update/while/body/reduce"],
           ["paged_decode_attention", 0.026, 0.001,
            d + "attention/paged_decode_attention"],
           ["fusion.9", 0.027, 0.003, d + "mlp/dot_general"],
           ["fusion.20", 1.000, 0.006, p + "mamba2/ssd_chunk_scan/while/body/dot"],
           ["fusion.21", 1.006, 0.002, p + "mamba2/gated_norm/mul"],
           ["flash_fwd", 1.008, 0.001, p + "attention/flash_fwd"],
           ["fusion.22", 1.009, 0.011, p + "mlp/dot_general"]]
    programs = [["jit_paged_decode", 0.0, 0.03], ["jit_paged_prefill", 1.0, 0.02]]
    return {"devices": {"0": {"ops": ops, "programs": programs}}}


def test_the_new_metrics_read_by_hand():
    dims = dims_of(CONFIG)
    facts = {**_facts(COUNTED, dims), "trace": _trace()}
    busy = 0.030 + 0.020
    got = trace_op_time.read(metric("mamba2_time_share")["params"], facts)
    assert abs(got - 100.0 * (0.002 + 0.020 + 0.004 + 0.006 + 0.002) / busy) < 1e-9
    got = trace_op_time.read(metric("ssd_scan_time_share")["params"], facts)
    assert abs(got - 100.0 * 0.006 / busy) < 1e-9
    # 64 live rows a step: 268 MB a layer at 819 GB/s, 36 layers an
    # execution, over the 24 ms the scope took inside the decode program
    least = 36 * 268_435_456 / 819e9
    got = counted_roofline.read(metric("ssd_state_update_roofline")["params"], facts)
    assert abs(got - 100.0 * least / 0.024) < 1e-9 and got < 100.0
    # the full-attention kernel's share reads this stack's 4 KV layers
    got = counted_roofline.read(metric("gqa_paged_decode_roofline")["params"], facts)
    kv = 25600 * 2 * 8 * 64 * 2 * 4 / 819e9
    assert abs(got - 100.0 * kv / 0.001) < 1e-9


def test_they_read_nothing_where_the_program_counts_no_state():
    """The parent commit's program (no Mamba-2 layers) under these files: no
    state counter and no scope, so nothing to read, and nothing raised."""
    dims = dims_of(CONFIG)
    plain = {k: v for k, v in COUNTED.items() if "state" not in k}
    trace = _trace()
    for op in trace["devices"]["0"]["ops"]:
        op[3] = op[3].replace("mamba2/", "attention/").replace("ssd_", "x_")
    facts = {**_facts(plain, dims), "trace": trace}
    assert counted_roofline.read(
        metric("ssd_state_update_roofline")["params"], facts) is None
    for name in ("mamba2_time_share", "ssd_scan_time_share"):
        assert not trace_op_time.read(metric(name)["params"], facts)
    assert trace_program_time is not None


def test_the_manifests_entries():
    manifest = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "closed_chat_short",
                    "chips": 1} and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    conf = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert conf["reduced"] == [] and len(conf["why"]) <= 200
    assert conf["file"] == f"perfbench/configs/{CONFIG}.json"
    e2e = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_out_tokens_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.03
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].rpartition(".")[2] in NEW}
    assert sorted(mine) == sorted("decode." + n for n in NEW)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"].rpartition(".")[2] + ".json"))
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in ("decode.gqa_paged_decode_roofline", "decode.copy_time_share",
                 "decode.decode_step_ms", "decode.batch_occupancy",
                 "decode.live_kv_tokens_per_step", "compile_cache_misses"):
        assert name in listed, name
    # what another stack's parts count stays theirs
    for name in ("decode.paged_decode_roofline", "decode.kda_state_update_roofline",
                 "decode.linear_attention_time_share", "decode.experts_time_share",
                 "decode.window_paged_decode_roofline"):
        assert name not in listed, name
    # every listed metric resolves to a file and a reader
    sys.path.insert(0, BENCH)
    import run
    for m in run.layer_metrics_for(manifest, CELL):
        assert os.path.exists(os.path.join(BENCH, "readers", m["reader"] + ".py"))


def test_the_configuration_is_the_catalogs_row_uncut():
    config = load(BENCH, "configs", CONFIG + ".json")
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == CONFIG)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    assert config["reduced"] == [] and config["num_hidden_layers"] == 40
    assert config["layer_types"].count("mamba") == 36
    assert [i for i, t in enumerate(config["layer_types"]) if t == "attention"] \
        == [5, 15, 25, 35]
    serve = config["assumed"]["serve"]
    assert serve == {"block_size": 128, "max_running": 64, "max_num_blocks": 769}
    # a longest request's 12 blocks for every row, and the dummy
    assert serve["max_num_blocks"] == serve["max_running"] * 12 + 1
    assert "3,191,396,096" in config["assumed"]["parameter_count"]
    spec = load(BENCH, "traffic", "closed_chat_short.json")
    (cls,) = spec["classes"]
    assert (spec["loop"], spec["clients_per_row"], spec["ramp_s"],
            spec["trace_seconds"], spec["drain_s"], spec["check"]["tokens"]) \
        == ("closed", 1.5, 12, 2, 30, 8)
    assert cls["prompt"] == {"dist": "lognormal", "median": 256, "sigma": 0.8,
                             "lo": 32, "hi": 1024}
    assert cls["answer"] == {"dist": "lognormal", "median": 160, "sigma": 0.7,
                             "lo": 16, "hi": 512}
    assert cls["prompt"]["hi"] + cls["answer"]["hi"] == 12 * serve["block_size"]


def rehearse(*rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4300000043", "--seconds", "8", "--trace", "0",
         "--rehearse", *rehearsal],
        capture_output=True, text=True, timeout=900)
    return run, run.stdout.strip().splitlines()


def test_the_cell_rehearses_correct_with_its_counters():
    run, lines = rehearse()
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert f"config {TOY}," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] > 0 and last["failed"] == 0
    for name in ("decode.live_kv_tokens_per_step", "decode.batch_occupancy",
                 "decode.preemptions", "decode.late_time_share",
                 "decode.compiles_in_window", "compile_cache_misses"):
        assert name in last["per_layer_names"], name
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.preemptions"]["value"] == 0.0
    assert per_layer["decode.compiles_in_window"]["value"] == 0.0
    # a request turns over every few steps, and every row stays taken
    assert per_layer["decode.batch_occupancy"]["value"] > 90.0
    assert last["attempted"] >= 20
    assert sum("warm-up: prompt bucket" in ln for ln in lines) == 1


@pytest.mark.parametrize("control,change", [
    ("residual_multiplier_1", {"residual_multiplier": 1.0}),
    ("embedding_multiplier_1", {"embedding_multiplier": 1.0})])
def test_the_check_bites_on_what_this_init_lets_it_see(tmp_path, control, change):
    """The same cell checked against a reference with ONE of Granite's
    scalars changed on its side: the served tokens are not that model's,
    and the run is not ``correct``. The toy's seeded init (embedding 0.006
    under matrices at 0.113: ``models/presets.py`` ``granite_hybrid``) is
    what lets a served token say so: at the default 0.02 / 0.02 the tied
    head over the x12 embedding serves the input token back whatever the
    layers do, and the check passes every fault. What the cell's own sizes
    see is measured on the chip by ``benchmarks/granite_check_controls.py``
    (PERF.md section 6, PR 43: the residual multiplier yes, a lost state and
    a dropped conv state no)."""
    config = load(BENCH, "configs", TOY + ".json")
    name_map = load(BENCH, "reference", "maps", TOY + ".json")
    # an absolute name leads the harness to one file for both
    (tmp_path / f"gh-{control}.json").write_text(
        json.dumps({**config, **name_map, **change}))
    run, lines = rehearse(str(tmp_path / f"gh-{control}"))
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"]
