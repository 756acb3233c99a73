"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick's own arithmetic: nothing here asks the program what it did.
A cost is ``(flops, bytes)`` for ONE call of a kernel that covers one chip's
whole micro-batch (flash: all heads and sequences of a layer; fused CE: all
tokens of the step). ``shapes`` is the dict the runner builds from the
configuration file and the traffic file (see ``runners/train.py``).

Copied in spirit from ``bench.py`` ``_run_metric`` / ``CausalLM.flops_per_token``
(6N + 12 L d S); the originals stay in the program (PERF.md, open questions).
"""

from __future__ import annotations

BF16 = 2


def param_count(dims: dict) -> int:
    """Parameters of a pre-LN decoder with biases, LayerNorm, a tied head and
    (optionally) learned positions and an embedding LayerNorm."""
    d, L, f, v = dims["d_model"], dims["n_layer"], dims["d_ff"], dims["vocab"]
    per_layer = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    n = v * d + L * per_layer + 2 * d
    if dims.get("positions") == "learned":
        n += dims["max_seq"] * d
    if dims.get("embed_layernorm"):
        n += 2 * d
    return n


def train_flops_per_token(dims: dict, seq: int) -> float:
    """Forward + backward operations a token requires: 6 N for the matrix
    multiplications (tied head counted once, as a matmul) + 12 L d S for
    attention scores and values. Recomputation is not counted."""
    return 6.0 * param_count(dims) + 12.0 * dims["n_layer"] * dims["d_model"] * seq


def _attn(shapes, passes, tensors):
    b, h, s, hd = (shapes["batch_per_chip"], shapes["n_head"], shapes["seq"],
                   shapes["head_dim"])
    # causal: half of the S x S tile grid is needed
    flops = passes * 2.0 * b * h * s * s * hd * 0.5
    return flops, tensors * b * h * s * hd * BF16


def flash_fwd(shapes):
    """s = q k^T, o = p v: two matmuls; reads q, k, v, writes o."""
    return _attn(shapes, 2, 4)


def flash_dq(shapes):
    """recomputes s, then dp = do v^T, dq = ds k: three matmuls; reads q, k,
    v, do, writes dq."""
    return _attn(shapes, 3, 5)


def flash_dkv(shapes):
    """recomputes s, then dp, dv = p^T do, dk = ds^T q: four matmuls; reads
    q, k, v, do, writes dk, dv."""
    return _attn(shapes, 4, 6)


def _ce(shapes, matmuls, extra_bytes):
    n, d, v = shapes["tokens_per_chip"], shapes["d_model"], shapes["vocab"]
    return matmuls * 2.0 * n * d * v, (n * d + v * d) * BF16 + extra_bytes


def fused_ce_fwd(shapes):
    """logits = h W^T once; reads h and W, writes two floats a token."""
    return _ce(shapes, 1, 8 * shapes["tokens_per_chip"])


def fused_ce_dh(shapes):
    """recomputes the logits, dh = dlogits W; writes dh."""
    return _ce(shapes, 2, shapes["tokens_per_chip"] * shapes["d_model"] * BF16)


def fused_ce_dw(shapes):
    """recomputes the logits, dW = dlogits^T h; writes dW in float32."""
    return _ce(shapes, 2, shapes["vocab"] * shapes["d_model"] * 4)


def paged_decode_attention(shapes):
    """One decode step's attention over ``live_kv_tokens`` cached tokens in
    all rows together: reads each cached key and value once."""
    t, h, hd = shapes["live_kv_tokens"], shapes["n_head"], shapes["head_dim"]
    return 4.0 * t * h * hd, 2 * t * shapes["n_kv_head"] * hd * BF16


def roofline_seconds(cost, peak: dict):
    """The least time the chip could take, and which roof sets it."""
    flops, nbytes = cost
    t_c = flops / (peak["bf16_tflops"] * 1e12)
    t_m = nbytes / (peak["hbm_gbps"] * 1e9)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
