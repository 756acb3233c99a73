"""The LongCat-Flash-Omni cell's additions: the cost functions of latent
attention from the map's sizes, the metric files' parameters, the manifest's
entries, the configuration against the catalog row, the traffic's parameters
as ISSUE 48 names them, and the reference's share arithmetic on a hand-sized
case (the reference against the published code is tier-1's,
``tests/unit/test_module_inject.py``; the program against the reference,
``tests/unit/test_longcat_flash.py``)."""

import json
import os
import sys

import costs
import costs_latent_attn
import correctness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "longcatflashomni_serve_ctx3k"
CONFIG = "longcat-flash-omni"
TOY = "rehearsal-longcat-flash-tiny"
NEW = ("latent_attention_time_share", "latent_decode_roofline",
       "zero_expert_assignment_share", "held16_expert_load_imbalance")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def dims_of(config_name):
    sys.path.insert(0, BENCH)
    import run
    return run.model_dims(load(BENCH, "configs", config_name + ".json"),
                          correctness.load_map(config_name))


def test_the_maps_sizes_feed_the_cost_functions():
    dims = dims_of(CONFIG)
    assert (dims["n_layer"], dims["n_cache_layer"], dims["n_head"]) == (4, 8, 64)
    assert (dims["kv_lora_rank"], dims["qk_rope_head_dim"],
            dims["qk_nope_head_dim"], dims["v_head_dim"]) == (512, 64, 128, 128)
    assert (dims["n_experts"], dims["experts_held"], dims["zero_experts"],
            dims["experts_per_token"], dims["d_expert"]) == (512, 16, 256, 12, 2048)
    assert costs_latent_attn.latent_row(dims) == 576
    # ISSUE 48's arithmetic: ~202 k live tokens a step are 1.86 GB of rows
    # over the 8 cache layers, 225 GFLOP of absorbed attention
    flops, nbytes = costs_latent_attn.latent_decode({**dims, "live_tokens": 202_000.0})
    assert nbytes == 202_000 * 1152 and round(8 * nbytes / 1e9, 2) == 1.86
    assert flops == 2.0 * 202_000 * 64 * 1088 and round(8 * flops / 1e9) == 225
    t, roof = costs.roofline_seconds((flops, nbytes),
                                     {"bf16_tflops": 197.0, "hbm_gbps": 819.0})
    assert roof == "memory" and abs(t - 202_000 * 1152 / 819e9) < 1e-9
    pf, pb = costs_latent_attn.latent_prefill({**dims, "prompt_tokens": 3072})
    assert pf == 64 * 3072 * 3072 * 320 and pb == 64 * 3072 * 640 * 2
    toy = dims_of(TOY)
    assert (toy["n_layer"], toy["n_cache_layer"], toy["kv_lora_rank"]) == (2, 4, 128)


def test_the_metric_files_name_what_the_program_emits():
    m = {n: load(BENCH, "layer_metrics", n + ".json") for n in NEW}
    assert m["latent_attention_time_share"]["params"]["scope"] == "/latent_attention/"
    roof = m["latent_decode_roofline"]["params"]
    assert roof["scope"] == "/latent_decode/" and \
        roof["per_execution"] == "n_cache_layer" and \
        roof["cost"] == "costs_latent_attn:latent_decode"
    # the decode program is told by its kernel, as every serving cell's is
    import re
    assert re.search(roof["program_contains"], "latent_paged_decode_attention.19")
    assert re.search(load(BENCH, "layer_metrics", "decode_step_ms.json")
                     ["params"]["contains"], "latent_paged_decode_attention.19")
    # an attention metric waits for no MoE counter
    assert roof["require"] == ["serving/decode_live_kv_tokens"]
    share = m["zero_expert_assignment_share"]["params"]
    assert share["require"] == ["serving/moe_zero_expert_assignments"]
    # Solar's reading with this configuration's 16 held experts for its 40
    held = m["held16_expert_load_imbalance"]["params"]
    solar = load(BENCH, "layer_metrics", "held_expert_load_imbalance.json")["params"]
    assert held["num"] == {"serving/moe_max_expert_load": 16} and \
        held["den"] == solar["den"] and \
        dims_of(CONFIG)["experts_held"] == 16
    sys.path.insert(0, ROOT)
    from deepspeed_tpu.inference import scheduler
    src = open(scheduler.__file__).read()
    for counter in (*share["num"], *share["den"], *held["num"], *held["den"],
                    *roof["operands"].values()):
        assert counter in src, counter


def test_the_manifest_enters_the_cell_and_only_adds():
    man = load(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "closed_ctx3k_1k", 1)
    conf = {c["name"]: c for c in man["configs"]}[CONFIG]
    assert conf["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert conf["source"].endswith("LongCat-Flash-Omni/blob/main/config.json")
    per = {p["name"]: p for p in man["per_layer"]}
    for n in NEW:
        assert per["decode." + n]["workloads"] == [CELL]
        assert per["decode." + n]["moves"] == "serve_out_tokens_per_s"
    listed = {n for n, p in per.items() if CELL in p.get("workloads", ())}
    assert {"decode.decode_step_ms", "decode.expert_matmul_roofline",
            "decode.prefill_ms_per_ktoken", "decode.hbm_peak_gb"} <= listed
    # another model's constants: not this cell's
    assert not listed & {"decode.paged_decode_roofline",
                         "decode.gqa_paged_decode_roofline",
                         "decode.held_expert_load_imbalance",
                         "decode.expert_load_imbalance"}
    e2e = {e["name"]: e for e in man["end_to_end"]}
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]
    assert e2e["serve_out_tokens_per_s"]["bound"] == 0.03


def test_the_configuration_is_the_catalog_row_cut_where_reduced_says():
    cfg = load(BENCH, "configs", CONFIG + ".json")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Omni")
    assert cfg["source"] == row["source_url"]
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
    assert (cfg["num_layers_published"], cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (28, 512, 131072)
    assert [r.split(":")[0] for r in cfg["reduced"]] == list(cut)
    assert cfg["assumed"]["serve"] == {"block_size": 128, "max_running": 64,
                                       "max_num_blocks": 64 * 36 + 1}
    assert "5,172,749,312" in cfg["assumed"]["parameter_count"]
    for reason in ("towers", "norm_topk_prob", "router_dtype", "cache_dtype", "init"):
        assert len(cfg["assumed"][reason]) > 40


def test_the_traffic_is_the_issues():
    spec = load(BENCH, "traffic", "closed_ctx3k_1k.json")
    assert (spec["loop"], spec["clients_per_row"], spec["ramp_s"],
            spec["trace_seconds"], spec["drain_s"], spec["check"]) == \
        ("closed", 1.5, 20, 2, 30, {"tokens": 8})
    (only,) = spec["classes"]
    assert only["prompt"] == {"dist": "uniform", "lo": 2048, "hi": 3072}
    assert only["answer"] == {"dist": "uniform", "lo": 768, "hi": 1536}
    # a longest request fills a row's table exactly
    assert 3072 + 1536 == load(BENCH, "configs", CONFIG + ".json")["serve_max_seq"]


def test_the_references_zero_experts_and_shares_by_hand():
    """Two tokens, three real experts of which the share holds the second,
    two zero-compute experts, top-2, factor 2: the router's scores decide by
    hand who is chosen, and the share adds only its own expert and the zero
    experts' part."""
    import jax.numpy as jnp
    import numpy as np
    from reference import longcat_flash_decoder as ref

    cfg = {"n_experts": 3, "experts_held": 1, "expert_offset": 1,
           "zero_experts": 2, "experts_per_token": 2, "routed_scaling": 2.0}
    D = 4
    m = jnp.asarray([[[1.0, 0, 0, 0], [0, 1.0, 0, 0]]])
    router = np.zeros((D, 5), np.float32)
    router[0] = [0.0, 3.0, 0.0, 2.0, 0.0]     # token 0: expert 1, zero expert 3
    router[1] = [4.0, 0.0, 0.0, 0.0, 0.0]     # token 1: expert 0, then the bias
    bias = np.array([0, 0, 0, 0, 0.1], np.float32)        # ... picks zero 4
    eye = jnp.eye(D)[None]                    # the held expert: silu(x) * x
    w = {"router": jnp.asarray(router), "b_select": jnp.asarray(bias),
         "e_gate": eye, "e_up": eye, "e_down": eye}
    out = np.asarray(ref.moe(cfg, w, m))
    p0 = np.exp(router[0]) / np.exp(router[0]).sum()
    p1 = np.exp(router[1]) / np.exp(router[1]).sum()
    silu1 = 1.0 / (1.0 + np.exp(-1.0))
    want0 = np.array([2 * p0[1] * silu1 + 2 * p0[3], 0, 0, 0])
    want1 = np.array([0, 2 * p1[4], 0, 0])    # expert 0 is another share's
    np.testing.assert_allclose(out[0, 0], want0, atol=1e-6)
    np.testing.assert_allclose(out[0, 1], want1, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.moe(cfg, w, m, zero=False))[0, 1], 0, atol=1e-7)
