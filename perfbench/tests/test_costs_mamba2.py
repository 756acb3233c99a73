"""``costs_mamba2.py`` by hand, at granite-4.0-h-micro's sizes (the sizes its
map hands the readers)."""

import costs
import costs_mamba2

SHAPES = {"ssm_heads": 64, "ssm_head_dim": 64, "ssm_state": 128, "ssm_chunk": 256}
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def test_the_decode_update_moves_each_live_rows_state_once_each_way():
    # 64 rows x 64 heads x 64 channels x 128 = 33,554,432 float32 elements
    flops, nbytes = costs_mamba2.ssd_decode_update({**SHAPES, "state_rows": 64.0})
    assert nbytes == 2 * 33_554_432 * 4 == 268_435_456
    assert flops == 6 * 33_554_432
    # 0.33 ms a layer at 819 GB/s, memory-bound by two hundred times
    t, roof = costs.roofline_seconds((flops, nbytes), PEAK)
    assert roof == "memory" and abs(t - 268_435_456 / 819e9) < 1e-12
    # the count goes with the live rows: an idle row costs nothing
    half = costs_mamba2.ssd_decode_update({**SHAPES, "state_rows": 32.0})
    assert half == (flops / 2, nbytes / 2)
    assert costs_mamba2.ssd_decode_update({**SHAPES, "state_rows": 0.0}) == (0, 0)


def test_the_chunk_scan_of_a_prompt():
    # 1,024 tokens are four chunks of 256: C B^T 2 x 256 x 256 x 128 once,
    # and a head 2 x 256 x 256 x 64 + 4 x 256 x 64 x 128
    flops, nbytes = costs_mamba2.ssd_chunk_scan({**SHAPES, "prompt_tokens": 1024})
    assert flops == 4 * (16_777_216 + 64 * (8_388_608 + 8_388_608)) == 4_362_076_160
    # a chunk: the state in and out (2 x 2 MB) and 256 x (2 x 4,096 + 256) bf16
    assert nbytes == 4 * (4_194_304 + 256 * 8448 * 2) == 34_078_720
    # a prompt short of a chunk is one chunk of its own length
    flops, nbytes = costs_mamba2.ssd_chunk_scan({**SHAPES, "prompt_tokens": 128})
    assert flops == 2 * 128 * 128 * 128 + 64 * (2 * 128 * 128 * 64 + 4 * 128 * 64 * 128)
    assert nbytes == 4_194_304 + 128 * 8448 * 2
    # 384 tokens: a chunk of 256 and one of the 128 left, as the program runs it
    assert costs_mamba2.ssd_chunk_scan({**SHAPES, "prompt_tokens": 384}) \
        == (16_777_216 + 64 * 16_777_216 + flops, 4_194_304 + 256 * 8448 * 2 + nbytes)
