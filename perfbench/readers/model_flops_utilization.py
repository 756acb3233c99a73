"""Tokens per second x the benchmark's own operations per token (6N +
12 L d S, recomputation not counted: ``costs.train_flops_per_token``) over
chips x the chip's published bf16 peak, in %."""

import costs


def read(params, facts):
    w = facts["window"]
    if "tokens_per_s" not in w or facts["peak"] is None:
        return None
    flops = costs.train_flops_per_token(facts["dims"], facts["shapes"]["seq"])
    return 100.0 * w["tokens_per_s"] * flops / (
        facts["chips"] * facts["peak"]["bf16_tflops"] * 1e12)
