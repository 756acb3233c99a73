"""The Kanana-2-30B-A3B cell's additions: the map's sizes as the cost
functions and readers see them, the new metric file on planted facts, the
manifest's entries BY
NAME (never by position in a list or by a count the next cell changes), the
configuration against the catalog row, the traffic's parameters as ISSUE 61
names them, the reference on a case computed by hand, the cell's rehearsal
on the CPU backend and a planted fault that the rehearsal's check refuses
(the program against the reference is tier-1's, ``tests/unit/test_kanana.py``)."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import correctness
import costs
import costs_latent_attn
import costs_moe

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "kanana2_30b_serve_longdoc"
LONGCAT = "longcatflashomni_serve_ctx3k"
CONFIG = "kanana-2-30b-a3b-instruct-2601"
TOY = "rehearsal-deepseek-v3-tiny"
TRAFFIC = "closed_longdoc_16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
#: the entries ISSUE 61 lists the cell under, beside those every decode cell
#: is under
NAMED = ("decode.experts_time_share", "decode.router_time_share",
         "decode.moe_dispatch_time_share",
         "decode.experts_touched_per_layer_step",
         "decode.moe_dropped_assignments", "decode.shared_expert_time_share",
         "decode.lead_layer_time_share",
         "decode.moe_layers_expert_matmul_roofline",
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
    assert (dims["n_layer"], dims["n_cache_layer"], dims["n_moe_layer"],
            dims["n_dense_layer"], dims["n_head"]) == (8, 8, 7, 1, 32)
    assert (dims["kv_lora_rank"], dims["qk_rope_head_dim"],
            dims["qk_nope_head_dim"], dims["v_head_dim"], dims["head_dim"]) \
        == (512, 64, 128, 128, 192)
    assert (dims["n_experts"], dims["experts_per_token"], dims["d_expert"],
            dims["d_ff"], dims["n_shared_experts"]) == (128, 6, 768, 6144, 2)
    assert (dims["d_model"], dims["vocab"], dims["max_seq"]) == \
        (2048, 128256, 21248)
    # a cached row: 576 values; 12 rows of ~16.6 k tokens are 0.23 GB a cache
    # layer, 0.28 ms at 819 GB/s
    assert costs_latent_attn.latent_row(dims) == 576
    flops, nbytes = costs_latent_attn.latent_decode(
        {**dims, "live_tokens": 200_000.0})
    assert nbytes == 200_000 * 1152
    t, roof = costs.roofline_seconds((flops, nbytes), PEAK)
    assert roof == "memory" and round(t * 1e3, 3) == 0.281
    # an expert is 3 x 2,048 x 768 x 2 B = 9.4 MB; ~55 touched a layer step
    # are 0.52 GB, 0.63 ms, over 12 x 6 = 72 assignments
    flops, nbytes = costs_moe.expert_matmuls(
        {**dims, "assignments": 72, "experts_touched": 55})
    assert nbytes == 55 * 3 * 2048 * 768 * 2 == 519_045_120
    t, roof = costs.roofline_seconds((flops, nbytes), PEAK)
    assert roof == "memory" and round(t * 1e3, 2) == 0.63
    toy = dims_of(TOY)
    assert (toy["n_layer"], toy["n_cache_layer"], toy["n_moe_layer"],
            toy["kv_lora_rank"], toy["n_experts"]) == (3, 3, 2, 128, 8)


def _facts(counters, dims, scope="latent_prefill"):
    """Two prefill programs of 100 ms in a trace, each with 2 x 20 ms of ops
    under ``scope`` (two cache layers) beside other ops, and a window over
    which the program counted ``counters``."""
    ops, progs = [], []
    for i, t0 in enumerate((1.0, 2.0)):
        progs.append([f"jit_prefill.{i}", t0, 0.1])
        ops += [["flash_fwd.3", t0 + 0.01, 0.015,
                 f"jit(prefill)/lead/latent_attention/{scope}/pallas_call"],
                ["fusion.7", t0 + 0.03, 0.005,
                 f"jit(prefill)/lead/latent_attention/{scope}/dot_general"],
                ["flash_fwd.3", t0 + 0.05, 0.02,
                 f"jit(prefill)/while/body/latent_attention/{scope}/pallas_call"],
                ["fusion.9", t0 + 0.08, 0.01, "jit(prefill)/while/body/experts"]]
    return {"trace": {"devices": {"0": {"ops": ops, "programs": progs}}},
            "window": {"marks": {"start": {"counters": {k: 0.0 for k in counters}},
                                 "end": {"counters": counters}}},
            "peak": PEAK, "dims": dims, "shapes": {**dims, "rows": 12}}


def test_the_new_metric_file_reads_planted_facts_and_nothing_without():
    share = load(BENCH, "layer_metrics", "latent_prefill_time_share.json")
    assert share == {"reader": "trace_op_time", "what": share["what"],
                     "params": {"pattern": "", "scope": "/latent_prefill/",
                                "mode": "share_of_busy"}}
    # two programs of 50 ms of ops each, 40 ms of them under the scope
    from readers import trace_op_time
    facts = _facts({}, dims_of(CONFIG))
    assert trace_op_time.read(share["params"], facts) == pytest.approx(80.0)
    # a trace with no op under the scope (the parent's has no such scope): 0
    assert trace_op_time.read(
        share["params"], _facts({}, dims_of(CONFIG), scope="other")) == 0.0
    # and the program names the scope and the counter ISSUE 61 asks for
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.inference import scheduler
    from deepspeed_tpu.models import latent_attention
    assert '"serving/prefill_tokens_squared"' in open(scheduler.__file__).read()
    assert 'named_scope("latent_prefill")' in open(latent_attention.__file__).read()


def test_the_manifest_enters_the_cell_by_name():
    man = load(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    conf = {c["name"]: c for c in man["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"].endswith(
        "kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
    assert conf["file"] == f"perfbench/configs/{CONFIG}.json"
    per = {p["name"]: p for p in man["per_layer"]}
    common = {"moves": "serve_out_tokens_per_s"}
    # no share of the prefill's roofline until a reader takes the traced
    # prefills' own lengths (PERF.md section 7, PR 61 (c))
    assert "decode.latent_prefill_roofline" not in per
    assert per["decode.latent_prefill_time_share"] == {
        **common, "name": "decode.latent_prefill_time_share", "unit": "%",
        "better": "lower", "source": "device_trace", "layer": "model step",
        "workloads": [LONGCAT, CELL]}
    assert per["decode.itl_p99_ms"] == {
        **common, "name": "decode.itl_p99_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "serving engine", "workloads": [CELL]}
    listed = {n for n, p in per.items() if CELL in p.get("workloads", ())}
    assert set(NAMED) <= listed
    e2e = {e["name"]: e for e in man["end_to_end"]}
    serve_cells = set(e2e["serve_out_tokens_per_s"]["workloads"])
    assert CELL in serve_cells
    # what every other decode cell is under, this one is under
    for name, p in per.items():
        if serve_cells - {CELL} <= set(p.get("workloads", ())):
            assert CELL in p["workloads"], name
    assert {"decode.device_idle_share", "decode.batch_occupancy",
            "decode.hbm_peak_gb", "compile_cache_misses"} <= listed
    # another stack's constants are not this cell's, and no fourth copy of
    # a load imbalance
    assert not [n for n in listed if "imbalance" in n]
    assert not listed & {"decode.expert_matmul_roofline",
                         "decode.paged_decode_roofline",
                         "decode.gqa_paged_decode_roofline",
                         "decode.window_paged_decode_roofline",
                         "decode.zero_expert_assignment_share",
                         "decode.linear_attention_time_share"}
    for p in man["per_layer"]:
        if CELL in p.get("workloads", ()):
            assert p["moves"] in ("serve_out_tokens_per_s", "setup_s"), p["name"]
    assert e2e["serve_out_tokens_per_s"]["bound"] == 0.03
    sys.path.insert(0, BENCH)
    import run
    for m in run.layer_metrics_for(man, CELL):
        assert os.path.exists(os.path.join(BENCH, "readers", m["reader"] + ".py"))
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    assert len(man["per_layer"]) <= 128 and len(man["workloads"]) <= 24


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    cfg = load(BENCH, "configs", CONFIG + ".json")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in cfg, key
            assert cfg[key] == {"num_hidden_layers": 8}.get(key, value), key
        assert row["config"]["num_hidden_layers"] == \
            cfg["num_hidden_layers_published"] == 48
    assert cfg["q_lora_rank"] is None and cfg["model_type"] == "deepseek_v3"
    assert (cfg["pipeline_stages"], cfg["pipeline_stage"]) == (6, 1)
    assert cfg["pipeline_stages"] * cfg["num_hidden_layers"] == 48
    assert [r.split(":")[0] for r in cfg["reduced"]] == ["num_hidden_layers"]
    serve = cfg["assumed"]["serve"]
    # 12 rows of 166 blocks (20,480 + 768 tokens), and the dummy
    assert cfg["serve_max_seq"] == 20480 + 768 == 166 * 128
    assert serve == {"block_size": 128, "max_running": 12,
                     "max_num_blocks": 12 * 166 + 1}
    assert "5,069,642,624" in cfg["assumed"]["parameter_count"]
    for reason in ("rope", "head_dim", "kv_a_layernorm_eps", "router_dtype",
                   "e_score_correction_bias", "shared_experts", "cache_dtype",
                   "dtype", "init", "serve_max_seq", "parameter_count", "why"):
        assert len(cfg["assumed"][reason]) > 40, reason
    assert len(cfg["deployment"]) > 200 and "STAGE 1" in cfg["deployment"]
    assert cfg["preset"] == {"family": "deepseek_v3", "size": "kanana-2-30b-8l"}
    assert cfg["rehearsal"] == TOY
    toy = load(BENCH, "configs", TOY + ".json")
    assert toy["rehearsal"] == TOY and toy["preset"]["family"] == "deepseek_v3"


def test_the_traffic_is_the_issues():
    spec = load(BENCH, "traffic", TRAFFIC + ".json")
    assert (spec["kind"], spec["loop"], spec["clients_per_row"], spec["ramp_s"],
            spec["trace_seconds"], spec["drain_s"], spec["check"]) == \
        ("serve", "closed", 1.5, 20, 6, 30, {"tokens": 8})
    assert isinstance(spec["plan_seed"], int) and "weights_seed" not in spec
    (only,) = spec["classes"]
    assert only["share"] == 1.0
    assert only["prompt"] == {"dist": "uniform", "lo": 12289, "hi": 20480}
    assert only["answer"] == {"dist": "uniform", "lo": 256, "hi": 768}
    import traffic as traffic_mod
    mix = traffic_mod.ServeTraffic(spec, 128256, 1)
    assert mix.longest_request() == 21248
    assert mix.prompt_bounds() == [(12289, 20480)]
    # the window's prompts are the plan's, whatever the seed
    other = traffic_mod.ServeTraffic(spec, 128256, 2)
    assert [len(mix.request(i)["prompt"]) for i in range(20)] == \
        [len(other.request(i)["prompt"]) for i in range(20)]
    # eight prefill programs to warm: buckets 1,024 apart
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.inference.engine import InferenceEngine
    buckets = {InferenceEngine._bucket(n, 21248) for n in range(12289, 20481)}
    assert sorted(buckets) == list(range(13312, 20481, 1024))
    assert len(spec["why"]) > 200


def test_the_reference_on_a_case_computed_by_hand():
    """One MoE layer, d = 4, ONE head of keys 2 (no position) + 2 (roped) and
    values 2 over a latent of 2, two experts of width 1 of which the top one
    is taken, one shared expert of width 1: every number below is written
    out, not computed by the module under test."""
    import jax
    import jax.numpy as jnp
    from reference import deepseek_v3_decoder as ref

    cfg = dict(n_layer=1, n_head=1, d_model=4, eps=0.0, rope_theta=10000.0,
               kv_lora_rank=2, qk_nope_head_dim=2, qk_rope_head_dim=2,
               v_head_dim=2, n_dense_layer=0, n_experts=2, experts_per_token=1,
               d_expert=1, n_shared_experts=1, n_group=1, topk_group=1,
               route_norm=True, route_scale=2.0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    one4 = f32([1, 1, 1, 1])
    # two tokens, RMS 1 each: (2, 0, 0, 0) and (0, 2, 0, 0)
    x = f32([[2, 0, 0, 0], [0, 2, 0, 0]])
    w = {
        "ln1_g": one4, "ln2_g": one4,
        # q = [q_nope | q_rope] = (u0, u1 | u0, u1)
        "wq": f32([[1, 0, 1, 0], [0, 1, 0, 1], [0, 0, 0, 0], [0, 0, 0, 0]]),
        # [ckv | kr] = (u0, u1 | u0, u1); the latent's norm scales by (1, 1)
        "wkv_a": f32([[1, 0, 1, 0], [0, 1, 0, 1], [0, 0, 0, 0], [0, 0, 0, 0]]),
        "kv_g": f32([1, 1]),
        # [k_nope | v] = (c0, c1 | c0, c1)
        "wkv_b": f32([[1, 0, 1, 0], [0, 1, 0, 1]]),
        "wo": f32([[0, 0, 1, 0], [0, 0, 0, 1]]),       # head -> channels 2, 3
        "router": f32([[1, 0], [0, 0], [0, 0], [0, 0]]),
        "expert_bias": f32([0.0, 0.3]),
        "shared_gate": f32([[0], [0], [1], [0]]),
        "shared_up": f32([[0], [0], [1], [0]]),
        "shared_down": f32([[1, 0, 0, 0.0]]),
        "e_gate": (f32([[[[1], [1], [0], [0]], [[0], [0], [0], [0]]]]), 0),
        "e_up": (f32([[[[1], [1], [0], [0]], [[0], [0], [0], [0]]]]), 0),
        "e_down": (f32([[[[0, 0, 0, 1.0]], [[0, 0, 0, 0.0]]]]), 0),
    }
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.layer(cfg, w, x, 0))
    # token 0 (position 0): u = (2, 0, 0, 0); the latent (2, 0) has RMS
    # sqrt 2: ckv = (sqrt 2, 0); it sees itself alone: o = v = ckv;
    # h0 = x0 + (0, 0, sqrt 2, 0)
    r2 = math.sqrt(2)
    h0 = np.array([2, 0, r2, 0])
    # token 1 (position 1): u = (0, 2, 0, 0): q_nope = (0, 2), ckv1 =
    # (0, sqrt 2) = k_nope1 = v1; k_nope0 = (sqrt 2, 0).
    # rope over 2 values: one pair, angle pos x theta^0 = pos. q_rope1 =
    # (0, 2) turned by 1 rad = (-2 sin 1, 2 cos 1) = kr1; kr0 = (2, 0) at
    # position 0. Scores / sqrt(2 + 2) = / 2:
    #   s0 = (q_nope1 . k_nope0 + q_rope1 . kr0) / 2 = (0 - 4 sin 1) / 2
    #   s1 = (q_nope1 . k_nope1 + q_rope1 . kr1) / 2 = (2 sqrt 2 + 4) / 2
    s0, s1 = -2 * math.sin(1), r2 + 2
    p0 = math.exp(s0) / (math.exp(s0) + math.exp(s1))
    o = p0 * np.array([r2, 0]) + (1 - p0) * np.array([0, r2])
    h1 = np.array([0, 2, o[0], o[1]])
    want = []
    for h in (h0, h1):
        m = h / math.sqrt(np.mean(h ** 2))
        s = np.array([1 / (1 + math.exp(-m[0])), 0.5])  # router reads channel 0
        top = int(np.argmax(s + np.array([0.0, 0.3])))  # the bias chooses
        f = np.zeros(4)
        if top == 0:          # weight s0 / (s0 + 1e-20) x 2 = 2: not s0 + b
            u = m[0] + m[1]
            f[3] = 2.0 * (u / (1 + math.exp(-u))) * u   # silu(u) * u, Wdown
        # expert 1's matrices are zero: chosen, it adds nothing
        f[0] += (m[2] / (1 + math.exp(-m[2]))) * m[2]   # the shared expert
        want.append(h + f)
    np.testing.assert_allclose(got, np.stack(want), atol=1e-5)
    # token 0's m0 = 2 / sqrt(1.5): sigmoid 0.837 > 0.5 + 0.3, expert 0;
    # token 1's m0 = 0: sigmoid 0.5 < 0.8, expert 1 by its bias
    assert abs(got[0, 3] - want[0][3]) < 1e-5 and want[0][3] > 1.0
    assert got[1, 3] == pytest.approx(h1[3], abs=1e-5)
    # the rope the published way keeps the de-interleaved order: a pair of
    # one is its own de-interleaving
    np.testing.assert_allclose(
        np.asarray(ref.rope(f32([[[0, 2.0]], [[0, 2.0]]]), 10000.0))[1, 0],
        [-2 * math.sin(1), 2 * math.cos(1)], atol=1e-6)


def rehearse(*rehearsal):
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "6100000061", "--seconds", "8", "--trace", "0",
         "--rehearse", *rehearsal],
        capture_output=True, text=True, timeout=900)
    return run, run.stdout.strip().splitlines()


def test_the_cell_rehearses_correct_with_its_metrics():
    run, lines = rehearse()
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert f"config {TOY}," in lines[0]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["platform"] == "cpu" and last["correct"]
    assert last["attempted"] >= 10 and last["failed"] == 0
    for name in ("decode.itl_p99_ms", "decode.live_kv_tokens_per_step",
                 "decode.preemptions", "decode.batch_occupancy",
                 "decode.experts_touched_per_layer_step",
                 "decode.moe_dropped_assignments", "decode.compiles_in_window",
                 "compile_cache_misses"):
        assert name in last["per_layer_names"], name
    per_layer = json.loads(next(
        ln for ln in lines if "] per-layer (" in ln).split("): ", 1)[1])
    assert per_layer["decode.compiles_in_window"]["value"] == 0.0
    assert per_layer["decode.moe_dropped_assignments"]["value"] == 0.0
    assert per_layer["decode.preemptions"]["value"] == 0.0
    # 4 rows x top-3 over 8 experts: most of them touched a layer and step
    assert 4.0 < per_layer["decode.experts_touched_per_layer_step"]["value"] <= 8.0
    forms = json.loads(next(
        ln for ln in lines if "forms selected: " in ln).split("selected: ", 1)[1])
    assert {"latent_decode=gather_einsum", "latent_prefill=einsum"} <= set(forms)
    # prompts of 384-640 tokens: three prefill buckets
    assert sum("warm-up: prompt bucket" in ln for ln in lines) == 3


def test_the_check_bites_on_this_cell_too(tmp_path):
    """The same cell checked against a reference whose three weights are not
    divided by their sum (``norm_topk_prob`` read as false): the served tokens
    are not that model's (5.9-20 bf16 steps on four prompts of two seeds; a
    missing ``routed_scaling_factor`` reads 0 on three of those four: the
    check sees what the seeded init lets it see). That holds for this toy on
    the CPU; what the check refuses at the cell's sizes on the chip is
    PERF.md section 6, PR 61."""
    config = load(BENCH, "configs", TOY + ".json")
    name_map = load(BENCH, "reference", "maps", TOY + ".json")
    config["norm_topk_prob"] = False
    # an absolute name leads the harness to one file for both
    (tmp_path / "kanana-no-norm.json").write_text(
        json.dumps({**config, **name_map}))
    run, lines = rehearse(str(tmp_path / "kanana-no-norm"))
    assert run.returncode == 1, run.stdout[-2000:] + run.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and not last["correct"]
