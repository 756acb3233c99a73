"""Attention ops.

The XLA einsum path below is the default; ``deepspeed_tpu.ops.flash_attention``
(Pallas, TPU) replaces it for long sequences when available. This mirrors the
reference's split between its CUDA softmax/attention kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax_context``) and
the torch fallbacks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def mha_attention(q, k, v, mask_bias=None, causal: bool = True, alibi_slopes=None, scale: Optional[float] = None,
                  causal_block: int = 1, window: int = 0):
    """q: [B, S, H, Hd]; k,v: [B, S, KV, Hd] with KV | H → [B, S, H, Hd]
    (v may have a width of its own, Dv: the result's).

    GQA-native: when KV < H the query heads are reshaped into [KV, G] groups
    (query head h reads kv head ``h // G`` — ``jnp.repeat`` order, matching
    the flash/decode kernels' index maps) and contracted against the
    UNREPEATED kv, so no H/KV× HBM copy of k/v is ever materialised.

    Computed in fp32 accumulators (softmax in fp32) with inputs in compute
    dtype; XLA fuses scale+bias+mask+softmax into the attention matmuls.
    ``causal_block`` > 1: position i sees j iff j // causal_block <=
    i // causal_block (the flash kernel's staircase; its XLA twin).
    ``window`` > 0 (with ``causal``): position i sees i - window < j <= i
    (the flash kernel's band; its XLA twin).
    """
    B, S, H, Hd = q.shape
    KV = k.shape[2]
    scale = scale if scale is not None else Hd**-0.5
    G = H // KV

    # [B, S, KV, G, Hd]: head h = c*G + g, so h // G = c — repeat order
    q5 = q.reshape(B, S, KV, G, Hd)
    logits = jnp.einsum("bqcgd,bkcd->bcgqk", q5, k,
                        preferred_element_type=jnp.float32) * scale

    if alibi_slopes is not None:
        # additive linear biases per head: slope * -(q_pos - k_pos)
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        dist = (kpos - qpos).astype(jnp.float32)  # <= 0 in causal region
        slopes5 = alibi_slopes.reshape(KV, G)
        logits = logits + slopes5[None, :, :, None, None] * dist[None, None, None, :, :]

    if causal:
        causal_mask = jnp.tril(jnp.ones((S, S), bool))
        if causal_block > 1:  # dslint: disable=DS004 (a static Python int)
            grp = jnp.arange(S) // causal_block
            causal_mask = grp[:, None] >= grp[None, :]
        if window:  # dslint: disable=DS004 (a static Python int)
            causal_mask = causal_mask & ~jnp.tril(
                jnp.ones((S, S), bool), -window)
        logits = jnp.where(causal_mask[None, None, None, :, :], logits, -1e9)
    if mask_bias is not None:
        logits = logits + mask_bias[:, None]  # [B,1,1,S] -> [B,1,1,1,S] broadcast

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bcgqk,bkcd->bqcgd", probs, v)
    # the values' own width (a latent-attention head's is not its keys')
    return out.reshape(B, S, H, v.shape[-1])
