"""FLOPs and bytes on hand-computed shapes."""

import pytest

import costs

BLOOM = {"d_model": 1024, "n_layer": 24, "d_ff": 4096, "vocab": 250880,
         "n_head": 16, "positions": "alibi", "max_seq": 2048,
         "embed_layernorm": True}
OPT = {"d_model": 2048, "n_layer": 24, "d_ff": 8192, "vocab": 50272,
       "n_head": 32, "positions": "learned", "max_seq": 2048,
       "embed_layernorm": False}


def test_parameter_counts_match_the_published_sizes():
    assert costs.param_count(BLOOM) == 559_214_592      # "560m"
    assert costs.param_count(OPT) == 1_315_753_984      # "1.3b" less 2 position rows x 2048


def test_train_flops_per_token():
    # 6 N + 12 L d S
    assert costs.train_flops_per_token(BLOOM, 2048) == pytest.approx(
        6 * 559_214_592 + 12 * 24 * 1024 * 2048)
    assert costs.train_flops_per_token(BLOOM, 2048) / 1e9 == pytest.approx(3.96, abs=0.01)
    assert costs.train_flops_per_token(OPT, 2048) / 1e9 == pytest.approx(9.10, abs=0.01)


def test_flash_costs_by_hand():
    sh = {"batch_per_chip": 4, "n_head": 16, "seq": 2048, "head_dim": 64}
    tile = 4 * 16 * 2048 * 2048 * 64            # one S x S x hd product, all heads
    assert costs.flash_fwd(sh) == (2 * 2 * tile * 0.5, 4 * 4 * 16 * 2048 * 64 * 2)
    assert costs.flash_dq(sh)[0] == 3 * 2 * tile * 0.5
    assert costs.flash_dkv(sh) == (4 * 2 * tile * 0.5, 6 * 4 * 16 * 2048 * 64 * 2)


def test_fused_ce_costs_by_hand():
    sh = {"tokens_per_chip": 8192, "d_model": 1024, "vocab": 250880}
    ndv = 8192 * 1024 * 250880
    assert costs.fused_ce_fwd(sh)[0] == 2 * ndv
    assert costs.fused_ce_dh(sh)[0] == costs.fused_ce_dw(sh)[0] == 4 * ndv
    assert costs.fused_ce_dw(sh)[1] == (8192 * 1024 + 250880 * 1024) * 2 + 250880 * 1024 * 4


def test_roofline_picks_the_higher_roof():
    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    t, roof = costs.roofline_seconds((197e12, 1.0), peak)
    assert (t, roof) == (pytest.approx(1.0), "compute")
    t, roof = costs.roofline_seconds((1.0, 819e9 * 2), peak)
    assert (t, roof) == (pytest.approx(2.0), "memory")
    # one decode step over 20k cached tokens of OPT-1.3B is memory-bound
    t, roof = costs.roofline_seconds(costs.paged_decode_attention(
        {"live_kv_tokens": 20000, "n_head": 32, "n_kv_head": 32, "head_dim": 64}), peak)
    assert roof == "memory" and t == pytest.approx(20000 * 2 * 32 * 64 * 2 / 819e9)


# ------------------------------------------- costs and sizes found by name

def test_a_cost_function_is_found_by_module_and_name(tmp_path, monkeypatch):
    from readers import roofline
    from readers._common import cost_function

    assert cost_function("flash_fwd") is costs.flash_fwd
    assert cost_function("costs:flash_fwd") is costs.flash_fwd
    # a new kernel's operations and bytes arrive in a file beside costs.py
    (tmp_path / "moe_costs.py").write_text(
        "def grouped_matmul(shapes):\n"
        "    return 197e12 * shapes['calls_s'], 1.0\n"
        "def active_flops_per_token(dims, seq):\n"
        "    return 6.0 * dims['active_params']\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    assert cost_function("moe_costs:grouped_matmul")({"calls_s": 2}) == (394e12, 1.0)
    with pytest.raises(AttributeError):
        cost_function("moe_costs:absent")

    peak = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    dev = {"ops": [["fusion.7", 0.0, 4.0, "jit(f)/experts/dot_general"]],
           "programs": [], "async": []}
    facts = {"trace": {"devices": {"/device:TPU:0": dev}, "host": []},
             "peak": peak, "shapes": {"calls_s": 2}}
    # 2 s at the roof over the 4 s the call took
    assert roofline.read({"kernels": {"^fusion\\.7$": "moe_costs:grouped_matmul"}},
                         facts) == pytest.approx(50.0)


def test_train_mfu_counts_what_the_map_names(tmp_path, monkeypatch):
    from readers import model_flops_utilization as mfu

    (tmp_path / "sparse_costs.py").write_text(
        "def active_flops_per_token(dims, seq):\n"
        "    return 6.0 * dims['active_params']\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    facts = {"window": {"tokens_per_s": 1e4}, "chips": 1, "map": {},
             "peak": {"bf16_tflops": 197.0}, "dims": {**BLOOM, "active_params": 1e9},
             "shapes": {"seq": 2048}}
    dense = 100 * 1e4 * costs.train_flops_per_token(BLOOM, 2048) / 197e12
    assert mfu.read({}, facts) == pytest.approx(dense)
    facts["map"] = {"train_flops": "sparse_costs:active_flops_per_token"}
    assert mfu.read({}, facts) == pytest.approx(100 * 1e4 * 6e9 / 197e12)


def test_model_dims_of_the_configurations_that_exist():
    import json
    import os

    import correctness
    import run as harness

    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def dims(name):
        with open(os.path.join(bench, "configs", name + ".json")) as f:
            return harness.model_dims(json.load(f), correctness.load_map(name))

    # as recorded at PR 24, key for key
    assert dims("bloom-560m") == {
        "d_model": 1024, "n_layer": 24, "n_head": 16, "n_kv_head": 16,
        "head_dim": 64, "d_ff": 4096, "vocab": 250880, "positions": "alibi",
        "max_seq": 2048, "embed_layernorm": True}
    assert dims("opt-1.3b") == {
        "d_model": 2048, "n_layer": 24, "n_head": 32, "n_kv_head": 32,
        "head_dim": 64, "d_ff": 8192, "vocab": 50272, "positions": "learned",
        "max_seq": 2048, "embed_layernorm": False}
    # a size the map declares passes through, and overrides a convention
    with open(os.path.join(bench, "configs", "opt-1.3b.json")) as f:
        config = {**json.load(f), "num_experts": 64, "kv": 8}
    name_map = correctness.load_map("opt-1.3b")
    name_map = {**name_map,
                "from_config": {**name_map["from_config"],
                                "n_experts": "num_experts", "n_kv_head": "kv"},
                "fixed": {**name_map["fixed"], "experts_per_token": 8,
                          "head_dim": 128}}
    got = harness.model_dims(config, name_map)
    assert (got["n_experts"], got["experts_per_token"], got["n_kv_head"],
            got["head_dim"], got["d_ff"]) == (64, 8, 8, 128, 8192)
    assert "eps" not in got and "activation" not in got
