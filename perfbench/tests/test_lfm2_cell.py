"""The LFM2-24B-A2B cell's additions: the map's sizes as the cost functions and
readers see them (``n_moe_layer`` among ``facts["dims"]``), the four new metric
files on a toy trace, the manifest's entries, the configuration against the
catalog row, the traffic's parameters as ISSUE 52 names them, and the cell's
rehearsal on the CPU backend (the program against the reference is tier-1's,
``tests/unit/test_lfm2_moe.py``; the reference by hand,
``test_reference_lfm2_moe.py``)."""

import json
import os
import subprocess
import sys

import pytest

import correctness
import costs
import costs_moe
from readers import counted_roofline, trace_op_time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lfm2_24b_serve_rollout"
CONFIG = "lfm2-24b-a2b"
TOY = "rehearsal-lfm2-moe-tiny"
NEW = ("short_conv_time_share", "short_conv_state_time_share",
       "lead_layer_time_share", "moe_layers_expert_matmul_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def dims_of(config_name):
    sys.path.insert(0, BENCH)
    import run
    return run.model_dims(load(BENCH, "configs", config_name + ".json"),
                          correctness.load_map(config_name))


def test_the_maps_sizes_reach_the_readers_and_the_cost_function():
    dims = dims_of(CONFIG)
    assert (dims["n_layer"], dims["n_full_attn_layer"], dims["n_conv_layer"],
            dims["n_moe_layer"], dims["n_dense_layer"]) == (9, 2, 7, 8, 1)
    assert (dims["n_experts"], dims["experts_per_token"], dims["d_expert"],
            dims["d_ff"], dims["conv_kernel"]) == (64, 4, 1536, 11776, 3)
    assert (dims["d_model"], dims["n_head"], dims["n_kv_head"], dims["head_dim"],
            dims["vocab"], dims["max_seq"]) == (2048, 32, 8, 64, 65536, 2560)
    # ISSUE 52's arithmetic: a layer's 64 experts are 1.21 GB, 1.47 ms at
    # 819 GB/s; 256 rows x 4 are 19.3 GFLOP of assigned work, 0.098 ms: the
    # COUNTED bound is the bytes', whatever form computed it
    flops, nbytes = costs_moe.expert_matmuls(
        {**dims, "assignments": 256 * 4, "experts_touched": 64})
    assert nbytes == 64 * 3 * 2048 * 1536 * 2 == 1_207_959_552
    assert flops == 1024 * 3 * 2 * 2048 * 1536
    t, roof = costs.roofline_seconds((flops, nbytes), PEAK)
    assert roof == "memory" and round(t * 1e3, 2) == 1.47
    # the GQA layers' bytes: 4 KB a token in the cut (2 layers x 8 x 64 x k, v)
    _, kv = costs.paged_decode_attention({**dims, "live_kv_tokens": 1.0})
    assert kv * dims["n_full_attn_layer"] == 4096
    toy = dims_of(TOY)
    assert (toy["n_layer"], toy["n_moe_layer"], toy["n_full_attn_layer"],
            toy["n_conv_layer"]) == (5, 4, 1, 4)


def test_the_metric_files_name_what_the_program_emits():
    m = {n: load(BENCH, "layer_metrics", n + ".json") for n in NEW}
    for name, scope in (("short_conv_time_share", "/short_conv/"),
                        ("short_conv_state_time_share", "/short_conv/state/"),
                        ("lead_layer_time_share", "/lead/")):
        assert m[name]["reader"] == "trace_op_time"
        assert m[name]["params"] == {"pattern": "", "scope": scope,
                                     "mode": "share_of_busy"}
    # the accepted roofline's parameters but for the layers an execution runs
    roof = m["moe_layers_expert_matmul_roofline"]
    accepted = load(BENCH, "layer_metrics", "expert_matmul_roofline.json")
    assert roof["reader"] == accepted["reader"] == "counted_roofline"
    assert {**roof["params"], "per_execution": "n_layer"} == accepted["params"]
    assert roof["params"]["per_execution"] == "n_moe_layer"
    # the scopes are the program's: the kind's name around the mixer, `state`
    # inside it, `lead` around the leading layers
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.models import state_mixers, transformer
    assert transformer.SHORT_CONV == "short_conv"
    assert 'named_scope("state")' in open(state_mixers.__file__).read()
    assert 'named_scope("lead")' in open(transformer.__file__).read()


def _facts():
    """Two decode executions of a lead and two periods' MoE layers (3 expert
    calls of 2 ms each would read n_layer = 3 high), a prefill after each."""
    ops, progs = [], []
    for step in range(2):
        t0 = step * 1.0
        progs.append(["jit_paged_decode", t0, 0.5])
        progs.append(["jit_paged_prefill", t0 + 0.6, 0.2])
        d = "jit(paged_decode)/"
        ops += [
            ["fusion.1", t0 + 0.01, 0.004, d + "lead/short_conv/in_proj/dot_general"],
            ["gather.2", t0 + 0.02, 0.001, d + "lead/short_conv/state/gather"],
            ["fusion.3", t0 + 0.03, 0.010, d + "lead/mlp/dot_general"],
            ["paged_decode_attention.1", t0 + 0.05, 0.01,
             d + "while/body/attention/pallas_call"]]
        for l in range(2):
            at = t0 + 0.1 + 0.1 * l
            ops += [
                ["fusion.4", at, 0.003, d + "while/body/short_conv/out_proj/dot_general"],
                ["scatter.5", at + 0.01, 0.002, d + "while/body/short_conv/state/scatter"],
                ["fusion.7", at + 0.02, 0.002, d + "while/body/mlp/experts/dot_general"]]
        ops.append(["fusion.9", t0 + 0.7, 0.005,
                    "jit(paged_prefill)/while/body/short_conv/state/mul"])
    counters = {"serving/moe_layer_steps": 4.0, "serving/moe_assignments": 64.0,
                "serving/moe_experts_touched": 16.0}
    return {"trace": {"devices": {"0": {"ops": ops, "programs": progs}}},
            "peak": PEAK, "dims": {"n_layer": 3, "n_moe_layer": 2},
            "shapes": {"d_model": 2048, "d_expert": 1536},
            "window": {"marks": {"start": {"counters": {}},
                                 "end": {"counters": counters}}}}


def test_the_new_metric_files_read_a_toy_trace():
    facts = _facts()
    busy = 2 * (0.004 + 0.001 + 0.010 + 0.01 + 2 * (0.003 + 0.002 + 0.002) + 0.005)
    read = lambda n: trace_op_time.read(  # noqa: E731
        load(BENCH, "layer_metrics", n + ".json")["params"], facts)
    # the whole mixer, the lead's among them and a prefill's too
    assert read("short_conv_time_share") == pytest.approx(
        100 * 2 * (0.004 + 0.001 + 2 * (0.003 + 0.002) + 0.005) / busy)
    assert read("short_conv_state_time_share") == pytest.approx(
        100 * 2 * (0.001 + 2 * 0.002 + 0.005) / busy)
    assert read("lead_layer_time_share") == pytest.approx(
        100 * 2 * (0.004 + 0.001 + 0.010) / busy)
    # 4 touched experts x 18.9 MB at 819 GB/s a layer step, against 2 ms
    roof = load(BENCH, "layer_metrics", "moe_layers_expert_matmul_roofline.json")
    least = 4 * 3 * 2048 * 1536 * 2 / 819e9
    got = counted_roofline.read(roof["params"], facts)
    assert got == pytest.approx(100 * least / 0.002, rel=1e-6)
    # the accepted file would multiply by every layer, the dense lead too
    accepted = load(BENCH, "layer_metrics", "expert_matmul_roofline.json")
    assert counted_roofline.read(accepted["params"], facts) == \
        pytest.approx(got * 3 / 2, rel=1e-6)
    # and a program without the scopes (the parent commit) reads nothing high
    facts["trace"]["devices"]["0"]["ops"] = [
        op for op in facts["trace"]["devices"]["0"]["ops"]
        if "short_conv" not in op[3] and "/lead/" not in op[3]]
    assert read("short_conv_time_share") == 0.0 == read("lead_layer_time_share")


def test_the_manifest_enters_the_cell_and_only_adds():
    man = load(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "closed_rollout_2k", 1)
    conf = {c["name"]: c for c in man["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types"]
    assert conf["source"].endswith("LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert conf["file"] == f"perfbench/configs/{CONFIG}.json"
    per = {p["name"]: p for p in man["per_layer"]}
    for n in NEW:
        # a later cell of these layer kinds may enter itself beside this one
        assert CELL in per["decode." + n]["workloads"]
        assert per["decode." + n]["moves"] == "serve_out_tokens_per_s"
        assert per["decode." + n]["source"] == "device_trace"
    assert per["decode.moe_layers_expert_matmul_roofline"]["layer"] == "kernels"
    listed = {n for n, p in per.items() if CELL in p.get("workloads", ())}
    assert {"decode.decode_step_ms", "decode.loop_host_share",
            "decode.device_idle_share", "decode.hbm_peak_gb",
            "decode.experts_time_share", "decode.router_time_share",
            "decode.moe_dispatch_time_share", "decode.moe_dropped_assignments",
            "decode.experts_touched_per_layer_step",
            "decode.expert_load_imbalance", "decode.gqa_paged_decode_roofline",
            "decode.prefill_ms_per_ktoken", "compile_cache_misses"} <= listed
    # another stack's constants are not this cell's: the accepted expert
    # roofline multiplies by n_layer, 9 here against 8 MoE layers
    assert not listed & {"decode.expert_matmul_roofline",
                         "decode.paged_decode_roofline",
                         "decode.held_expert_load_imbalance",
                         "decode.mamba2_time_share",
                         "decode.latent_decode_roofline"}
    e2e = {e["name"]: e for e in man["end_to_end"]}
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]
    assert e2e["serve_out_tokens_per_s"]["bound"] == 0.03
    sys.path.insert(0, BENCH)
    import run
    for m in run.layer_metrics_for(man, CELL):
        assert os.path.exists(os.path.join(BENCH, "readers", m["reader"] + ".py"))
    # the imbalance's weight is this model's count of experts
    weight = load(BENCH, "layer_metrics", "expert_load_imbalance.json")
    assert weight["params"]["num"] == {"serving/moe_max_expert_load": 64}


def test_the_configuration_is_the_catalog_row_cut_where_reduced_says():
    cfg = load(BENCH, "configs", CONFIG + ".json")
    cut = {"num_hidden_layers": 9, "num_dense_layers": 1}
    types = cfg["layer_types_published"]
    assert cfg["layer_types"] == types[1:10] == \
        ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
        assert cfg["source"] == row["source_url"]
        assert row["config"]["layer_types"] == types
        for key, value in row["config"].items():
            if key != "layer_types":
                assert cfg[key] == cut.get(key, value), key
    assert (cfg["num_hidden_layers_published"],
            cfg["num_dense_layers_published"]) == (40, 2)
    assert [r.split(":")[0] for r in cfg["reduced"]] == \
        ["num_hidden_layers", "num_dense_layers", "layer_types"]
    serve = cfg["assumed"]["serve"]
    # a longest request's 20 blocks for every row, and the dummy
    assert serve == {"block_size": 128, "max_running": 256,
                     "max_num_blocks": 256 * 20 + 1}
    assert "5,177,950,976" in cfg["assumed"]["parameter_count"]
    for reason in ("tie_embedding", "head_dim", "hidden_act", "topk_eps",
                   "expert_bias", "dtype", "init", "serve_max_seq", "why"):
        assert len(cfg["assumed"][reason]) > 40, reason
    assert cfg["preset"] == {"family": "lfm2_moe", "size": "24b-a2b-9l"}
    assert cfg["rehearsal"] == TOY


def test_the_traffic_is_the_issues():
    spec = load(BENCH, "traffic", "closed_rollout_2k.json")
    assert (spec["kind"], spec["loop"], spec["clients_per_row"], spec["ramp_s"],
            spec["trace_seconds"], spec["drain_s"], spec["check"]) == \
        ("serve", "closed", 1.5, 25, 2, 30, {"tokens": 8})
    (only,) = spec["classes"]
    assert only["prompt"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert only["answer"] == {"dist": "uniform", "lo": 1024, "hi": 2048}
    # a longest request fills a row's table exactly
    assert 512 + 2048 == load(BENCH, "configs", CONFIG + ".json")["serve_max_seq"]
    assert len(spec["why"]) > 200


def test_the_cell_rehearses_correct_with_its_counters():
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "5200000052", "--seconds", "8", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900)
    lines = run.stdout.strip().splitlines()
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert f"config {TOY}," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] >= 20 and last["failed"] == 0
    for name in ("decode.live_kv_tokens_per_step", "decode.batch_occupancy",
                 "decode.preemptions", "decode.late_time_share",
                 "decode.experts_touched_per_layer_step",
                 "decode.moe_dropped_assignments", "decode.expert_load_imbalance",
                 "decode.compiles_in_window", "compile_cache_misses"):
        assert name in last["per_layer_names"], name
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.preemptions"]["value"] == 0.0
    assert per_layer["decode.compiles_in_window"]["value"] == 0.0
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    assert per_layer["decode.batch_occupancy"]["value"] > 90.0
    forms = json.loads(next(
        ln for ln in lines if "forms selected: " in ln).split("selected: ", 1)[1])
    assert "mixer=short_conv" in forms and "experts=dense" in forms
    # prompts of 16-64 tokens: one prefill bucket
    assert sum("warm-up: prompt bucket" in ln for ln in lines) == 1
