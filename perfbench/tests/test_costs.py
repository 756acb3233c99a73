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
