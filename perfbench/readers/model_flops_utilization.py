"""Tokens per second x the benchmark's own operations per token over chips x
the chip's published bf16 peak, in %. The operations are the cost function
the configuration's map names as ``"train_flops"`` (``<module>:<function>``
of ``(dims, seq)``: a sparse model counts the parameters a token touches),
else ``costs.train_flops_per_token`` (6N + 12 L d S, recomputation not
counted)."""

from ._common import cost_function


def read(params, facts):
    w = facts["window"]
    if "tokens_per_s" not in w or facts["peak"] is None:
        return None
    per_token = cost_function(
        facts["map"].get("train_flops", "train_flops_per_token"))
    flops = per_token(facts["dims"], facts["shapes"]["seq"])
    return 100.0 * w["tokens_per_s"] * flops / (
        facts["chips"] * facts["peak"]["bf16_tflops"] * 1e12)
