"""The Trinity-Large-Preview cell's additions: the map's sizes as the cost
functions and readers see them, the one new metric file on planted counters,
the manifest's entries BY NAME (never by position in a list), the
configuration against the catalog row, the traffic's parameters as ISSUE 56
names them, the reference on a case computed by hand, and the cell's
rehearsal on the CPU backend (the program against the reference is tier-1's,
``tests/unit/test_trinity.py``)."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import correctness
import costs
import costs_moe
import costs_window_attn
from readers import counter_ratio

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "trinitylarge_serve_shortlong"
CONFIG = "trinity-large-preview"
TOY = "rehearsal-trinity-tiny"
TRAFFIC = "closed_shortlong_6k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
#: the entries ISSUE 56 lists the cell under, beside those every decode cell
#: is under
NAMED = ("decode.experts_time_share", "decode.router_time_share",
         "decode.moe_dispatch_time_share",
         "decode.experts_touched_per_layer_step",
         "decode.moe_dropped_assignments",
         "decode.moe_layers_expert_matmul_roofline",
         "decode.shared_expert_time_share", "decode.lead_layer_time_share",
         "decode.window_paged_decode_roofline",
         "decode.window_attention_time_share",
         "decode.live_window_kv_blocks_per_step",
         "decode.prefill_ms_per_ktoken")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def dims_of(config_name):
    sys.path.insert(0, BENCH)
    import run
    return run.model_dims(load(BENCH, "configs", config_name + ".json"),
                          correctness.load_map(config_name))


def test_the_maps_sizes_reach_the_readers_and_the_cost_functions():
    dims = dims_of(CONFIG)
    assert (dims["n_layer"], dims["n_window_layer"], dims["n_full_attn_layer"],
            dims["n_moe_layer"], dims["n_dense_layer"]) == (5, 4, 1, 4, 1)
    assert (dims["n_experts"], dims["experts_held"], dims["experts_per_token"],
            dims["d_expert"], dims["d_ff"], dims["window"]) == \
        (256, 32, 4, 3072, 12288, 4096)
    assert (dims["d_model"], dims["n_head"], dims["n_kv_head"], dims["head_dim"],
            dims["vocab"], dims["max_seq"]) == (3072, 48, 8, 128, 25024, 7168)
    # an expert is 3 x 3,072 x 3,072 x 2 B = 56.6 MB; ~20 touched a layer
    # step are 1.13 GB, 1.38 ms at 819 GB/s, over 64 x 4 / 8 assignments
    flops, nbytes = costs_moe.expert_matmuls(
        {**dims, "assignments": 32, "experts_touched": 20})
    assert nbytes == 20 * 3 * 3072 * 3072 * 2 == 1_132_462_080
    t, roof = costs.roofline_seconds((flops, nbytes), PEAK)
    assert roof == "memory" and round(t * 1e3, 2) == 1.38
    # KV: 4 KB a token and layer; a layer's mean over 4 window and 1 full
    _, kv = costs_window_attn.window_paged_decode(
        {**dims, "live_kv_tokens": 5.0, "live_window_kv_tokens": 5.0})
    assert kv == 5 * 4096
    toy = dims_of(TOY)
    assert (toy["n_layer"], toy["n_window_layer"], toy["n_full_attn_layer"],
            toy["n_moe_layer"], toy["window"]) == (9, 7, 2, 8, 256)


def test_the_new_metric_reads_the_programs_counters_and_nothing_without():
    spec = load(BENCH, "layer_metrics", "window_ring_fill.json")
    assert spec["reader"] == "counter_ratio"
    held, ring = "serving/decode_window_blocks_held", \
        "serving/decode_window_ring_blocks"
    assert spec["params"]["num"] == {held: 1} and spec["params"]["den"] == {ring: 1}
    assert set(spec["params"]["require"]) == {held, ring}

    def facts(start, end):
        return {"window": {"marks": {"start": {"counters": start},
                                     "end": {"counters": end}}}}
    # 64 rows x 33 a step; 54 short rows of 8 blocks and 10 whole rings
    got = counter_ratio.read(spec["params"], facts(
        {held: 100.0, ring: 2112.0},
        {held: 100.0 + 10 * (54 * 8 + 10 * 33), ring: 2112.0 + 10 * 64 * 33}))
    assert got == pytest.approx(100 * 762 / 2112)
    # every row past the window: 100
    assert counter_ratio.read(spec["params"], facts(
        {held: 0.0, ring: 0.0}, {held: 528.0, ring: 528.0})) == 100.0
    # the parent has no such counters: nothing to read, nothing raised
    assert counter_ratio.read(spec["params"], facts(
        {"serving/decode_steps": 1.0}, {"serving/decode_steps": 9.0})) is None
    # and the program names them
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.inference import scheduler
    text = open(scheduler.__file__).read()
    assert f'"{held}"' in text and f'"{ring}"' in text
    assert '"serving/window_blocks_used"' in text


def test_the_manifest_enters_the_cell_by_name():
    man = load(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    conf = {c["name"]: c for c in man["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types", "num_experts", "vocab_size"]
    assert conf["source"].endswith(
        "arcee-ai/Trinity-Large-Preview/blob/main/config.json")
    assert conf["file"] == f"perfbench/configs/{CONFIG}.json"
    per = {p["name"]: p for p in man["per_layer"]}
    new = per["decode.window_ring_fill"]
    assert new == {"name": "decode.window_ring_fill", "unit": "%",
                   "better": "lower", "source": "program_counter",
                   "layer": "serving engine",
                   "moves": "serve_out_tokens_per_s",
                   "workloads": [CELL, "smallthinker21b_serve_longctx"]}
    listed = {n for n, p in per.items() if CELL in p.get("workloads", ())}
    assert set(NAMED) <= listed
    # and what every decode cell is under
    e2e = {e["name"]: e for e in man["end_to_end"]}
    serve_cells = set(e2e["serve_out_tokens_per_s"]["workloads"])
    assert CELL in serve_cells
    # PR 59 retired the nine shares of the device's idle time by host span,
    # which this cell never entered (one pass each of the harness's
    # quadratic ``attribute()``; the result line's ``breakdown`` carries the
    # same split as ``idle_gaps``): what every other decode cell is under,
    # this one is under
    for name, p in per.items():
        if serve_cells - {CELL} <= set(p.get("workloads", ())):
            assert CELL in p["workloads"], name
    assert not any(n.startswith("decode.gap_") for n in per)
    assert {"decode.device_idle_share", "decode.batch_occupancy"} <= listed
    # another stack's constants are not this cell's, and no third copy of
    # the held-expert imbalance
    assert not listed & {"decode.expert_matmul_roofline",
                         "decode.paged_decode_roofline",
                         "decode.gqa_paged_decode_roofline",
                         "decode.held_expert_load_imbalance",
                         "decode.held16_expert_load_imbalance",
                         "decode.expert_load_imbalance",
                         "decode.linear_attention_time_share",
                         "decode.latent_decode_roofline"}
    for p in man["per_layer"]:
        if CELL in p.get("workloads", ()):
            assert p["moves"] in ("serve_out_tokens_per_s", "setup_s"), p["name"]
    assert e2e["serve_out_tokens_per_s"]["bound"] == 0.03
    sys.path.insert(0, BENCH)
    import run
    for m in run.layer_metrics_for(man, CELL):
        assert os.path.exists(os.path.join(BENCH, "readers", m["reader"] + ".py"))
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


def test_the_configuration_is_the_catalog_row_cut_where_reduced_says():
    cfg = load(BENCH, "configs", CONFIG + ".json")
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32,
           "vocab_size": 25024}
    types = cfg["layer_types_published"]
    assert cfg["layer_types"] == [types[0]] + types[8:12] == \
        ["sliding_attention"] * 4 + ["full_attention"]
    assert cfg["published_layers"] == [0, 8, 9, 10, 11]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Large-Preview")
        assert cfg["source"] == row["source_url"]
        assert row["config"]["layer_types"] == types
        for key, value in row["config"].items():
            if key != "layer_types":
                assert cfg[key] == cut.get(key, value), key
    assert (cfg["num_hidden_layers_published"], cfg["num_dense_layers_published"],
            cfg["num_experts_published"], cfg["vocab_size_published"]) == \
        (60, 6, 256, 200192)
    assert (cfg["expert_parallel"], cfg["expert_share"], cfg["expert_offset"]) \
        == (8, 0, 0)
    assert [r.split(":")[0] for r in cfg["reduced"]] == \
        ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
         "vocab_size"]
    serve = cfg["assumed"]["serve"]
    # 20 long rows of 56 blocks, 44 short ones of 16, and the dummy
    assert serve == {"block_size": 128, "max_running": 64,
                     "max_num_blocks": 20 * 56 + 44 * 16 + 1}
    assert "4,321,903,872" in cfg["assumed"]["parameter_count"]
    for reason in ("attention_gate", "qk_norm", "positions", "sandwich_norm",
                   "embedding_scale", "topk_eps", "hidden_act", "dtype",
                   "seeded_init", "serve_max_seq", "why"):
        assert len(cfg["assumed"][reason]) > 40, reason
    assert len(cfg["deployment"]) > 200
    assert cfg["preset"] == {"family": "trinity", "size": "large-preview-5l-ep8"}
    assert cfg["rehearsal"] == TOY


def test_the_traffic_is_the_issues():
    spec = load(BENCH, "traffic", TRAFFIC + ".json")
    assert (spec["kind"], spec["loop"], spec["clients_per_row"], spec["ramp_s"],
            spec["trace_seconds"], spec["drain_s"], spec["check"]) == \
        ("serve", "closed", 1.5, 20, 2, 30, {"tokens": 8})
    short, long_ = spec["classes"]
    assert (short["share"], long_["share"]) == (0.85, 0.15)
    assert short["prompt"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert long_["prompt"] == {"dist": "uniform", "lo": 4608, "hi": 6144}
    assert short["answer"] == long_["answer"] == \
        {"dist": "uniform", "lo": 512, "hi": 1024}
    cfg = load(BENCH, "configs", CONFIG + ".json")
    # a longest request fills a row's table exactly: 56 blocks
    assert 6144 + 1024 == cfg["serve_max_seq"] == 56 * 128
    # the check's prompts: one under a ring, one whose ring has wrapped
    import traffic as traffic_mod
    bounds = traffic_mod.ServeTraffic(spec, 25024, 1).prompt_bounds()
    assert (min(bounds)[0] + 1, max(bounds)[0] + 1) == (257, 4609)
    assert 4609 > cfg["sliding_window"] + 128 * 2
    # of 64 rows at most 20 are long with probability over 0.999
    p = sum(math.comb(64, k) * 0.15 ** k * 0.85 ** (64 - k) for k in range(21))
    assert p > 0.999
    assert len(spec["why"]) > 200


def test_the_reference_on_a_case_computed_by_hand():
    """One sliding MoE layer, d = 4, one query head and one kv head of 2, a
    window of 2, two experts of width 1 of which the first is held, the top
    one taken: every number below is written out, not computed by the module
    under test."""
    import jax
    import jax.numpy as jnp
    from reference import trinity_decoder as ref

    eps = 1e-12                     # only so that a zero branch norms to 0
    cfg = dict(n_layer=1, n_head=1, n_kv_head=1, head_dim=2, d_model=4, eps=eps,
               rope_theta=10000.0, window=2,
               layer_types=["sliding_attention"], n_dense_layer=0, n_experts=2,
               experts_held=1, expert_offset=0, experts_per_token=1,
               d_expert=1, route_norm=True, route_scale=2.0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    one = f32([1, 1, 1, 1])
    # two tokens; token 0 = (2, 0, 0, 0), token 1 = (0, 2, 0, 0): RMS 1 each
    x = f32([[[2, 0, 0, 0], [0, 2, 0, 0]]])
    w = {
        "ln1_g": one, "ln1_post_g": one, "ln2_g": one, "ln2_post_g": one,
        # q, k read the first two channels as they are; v the same
        "wq": f32([[1, 0], [0, 1], [0, 0], [0, 0]]),
        "wk": f32([[1, 0], [0, 1], [0, 0], [0, 0]]),
        "wv": f32([[1, 0], [0, 1], [0, 0], [0, 0]]),
        "q_g": f32([1, 1]), "k_g": f32([1, 1]),
        "w_gate_attn": f32(np.zeros((4, 2))),          # sigmoid(0) = 1/2
        "wo": f32([[0, 0, 2, 0], [0, 0, 0, 2]]),       # heads -> channels 2, 3
        "router": f32([[1, 0], [0, 0], [0, 0], [0, 0]]),
        "expert_bias": f32([0.0, 0.6]),
        "shared_gate": f32(np.zeros((4, 1))), "shared_up": f32(np.zeros((4, 1))),
        "shared_down": f32(np.zeros((1, 4))),
        "e_gate": [f32([[1], [1], [0], [0]])],
        "e_up": [f32([[1], [1], [0], [0]])],
        "e_down": [f32([[0, 0, 0, 1.0]])],
    }
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.layer(cfg, w, x, 0))[0]
    # attention, token 0: normed a = (2, 0, 0, 0); q = k = v = (2, 0), normed
    # per head to (sqrt 2, 0); rope at position 0 turns nothing; it sees
    # itself alone: o = v = (2, 0); gate 1/2: (1, 0); Wo: (0, 0, 2, 0);
    # post-norm (rms 1): (0, 0, 2, 0). h0 = (2, 0, 2, 0)
    h0 = np.array([2, 0, 2, 0.0])
    # token 1: q = k = (0, sqrt 2) before rope; at position 1 the pair
    # (u0, u1) turns by 1 rad (theta^0): q1 = k1 = sqrt 2 (-sin 1, cos 1);
    # k0 = (sqrt 2, 0). scores / sqrt 2: s0 = q1.k0 / sqrt 2 = -sqrt 2 sin 1,
    # s1 = q1.k1 / sqrt 2 = sqrt 2
    s0, s1 = -math.sqrt(2) * math.sin(1), math.sqrt(2)
    p0 = math.exp(s0) / (math.exp(s0) + math.exp(s1))
    o = np.array([2 * p0, 2 * (1 - p0)])               # v0 = (2, 0), v1 = (0, 2)
    y = np.array([0, 0, o[0], o[1]])                   # gate 1/2, Wo 2
    h1 = np.array([0, 2, 0, 0.0]) + y / math.sqrt(np.mean(y ** 2))
    want = []
    for h in (h0, h1):
        m = h / math.sqrt(np.mean(h ** 2))
        s = np.array([1 / (1 + math.exp(-m[0])), 0.5])  # router reads channel 0
        top = int(np.argmax(s + np.array([0.0, 0.6])))  # the bias chooses
        f = np.zeros(4)
        if top == 0:            # held here; weight s0 / s0 * 2 = 2
            u = m[0] + m[1]
            f[3] = 2.0 * (u / (1 + math.exp(-u))) * u   # silu(u) * u, Wdown
        # expert 1 is held elsewhere: its part is left out, and the shared
        # expert is zero: the branch is 0 and its norm leaves 0
        rms = math.sqrt(np.mean(f ** 2))
        want.append(h + (f / rms if rms else f))
    # token 0: m0 = 2 / sqrt 2, sigmoid 0.80 > 0.5 + 0.6? no: expert 1 wins
    # the choice by its bias, and nothing of token 0's MLP is computed here
    np.testing.assert_allclose(got[0], h0, atol=1e-6)
    np.testing.assert_allclose(got, np.stack(want), atol=1e-5)
    # without the bias token 0 takes expert 0, and its branch joins with rms 1
    w["expert_bias"] = f32([0.0, 0.0])
    with jax.default_matmul_precision("highest"):
        again = np.asarray(ref.layer(cfg, w, x, 0))[0]
    np.testing.assert_allclose(again[0], h0 + np.array([0, 0, 0, 2.0]), atol=1e-5)


def test_the_cell_rehearses_correct_with_its_new_metric():
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "5600000056", "--seconds", "8", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900)
    lines = run.stdout.strip().splitlines()
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert f"config {TOY}," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] >= 10 and last["failed"] == 0
    for name in ("decode.window_ring_fill", "decode.live_window_kv_blocks_per_step",
                 "decode.live_kv_tokens_per_step", "decode.preemptions",
                 "decode.experts_touched_per_layer_step",
                 "decode.moe_dropped_assignments", "decode.compiles_in_window",
                 "compile_cache_misses"):
        assert name in last["per_layer_names"], name
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    # short rows beside long ones: the rows hold less than a ring each
    assert 20.0 < per_layer["decode.window_ring_fill"]["value"] < 100.0
    assert per_layer["decode.compiles_in_window"]["value"] == 0.0
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    forms = json.loads(next(
        ln for ln in lines if "forms selected: " in ln).split("selected: ", 1)[1])
    assert {"paged_window_decode=gather_einsum",
            "paged_window_prefill=einsum"} <= set(forms)
    # prompts of 32-128 and 576-768 tokens: three prefill buckets
    assert sum("warm-up: prompt bucket" in ln for ln in lines) == 3
