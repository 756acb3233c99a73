"""Sparse self-attention op (reference
``ops/sparse_attention/sparse_self_attention.py`` + the Triton matmul/
softmax kernels it drives, ``matmul.py``/``softmax.py``).

Two execution paths, both exactly computing softmax over the layout's
support and both differentiable:

- **pallas** (TPU): block-sparse flash attention — zero layout blocks are
  skipped in fwd and bwd (``flash_attention(block_layout=...)``); compute
  and HBM traffic scale with the density of the layout.
- **dense** (CPU/tests): the layout expanded to a token-level additive mask
  over the einsum attention.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (FixedSparsityConfig,
                                                                SparsityConfig)


def layout_to_token_bias(layout, block: int, seq_len: int):
    """[H, nb, nb] 0/1 layout → additive bias [H, S, S] (0 keep / -1e9 drop)."""
    nb = seq_len // block
    lay = jnp.asarray(layout)[:, :nb, :nb]
    tok = jnp.repeat(jnp.repeat(lay, block, axis=1), block, axis=2)
    return jnp.where(tok > 0, 0.0, -1e9).astype(jnp.float32)


class SparseSelfAttention:
    """Callable module (reference ``:24``): q/k/v [B, S, H, Hd] → [B, S, H, Hd].

    ``sparsity_config`` decides the layout; causal masking composes with the
    layout for "unidirectional" configs.
    """

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None,
                 key_padding_mask_mode: str = "add", attn_mask_mode: str = "mul",
                 max_seq_length: int = 2048, backend: str = "auto"):
        self.sparsity_config = sparsity_config or FixedSparsityConfig(num_heads=4)
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self.max_seq_length = max_seq_length
        self.backend = backend
        self._layouts = {}

    def get_layout(self, seq_len: int) -> np.ndarray:
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def _use_pallas(self) -> bool:
        if self.backend == "pallas":
            return True
        if self.backend == "dense":
            return False
        return dispatch.on_tpu() and self.sparsity_config.block >= 128

    def __call__(self, query, key, value, rpe=None, key_padding_mask=None, attn_mask=None):
        B, S, H, Hd = query.shape
        layout = self.get_layout(S)
        causal = getattr(self.sparsity_config, "attention", "bidirectional") == "unidirectional"

        mask_bias = None
        if key_padding_mask is not None:
            # [B, S] 1=keep (or additive when mode == "add" with float input)
            if key_padding_mask.dtype in (jnp.float32, jnp.bfloat16, jnp.float16):
                mask_bias = key_padding_mask.astype(jnp.float32)
            else:
                mask_bias = jnp.where(key_padding_mask > 0, 0.0, -1e9).astype(jnp.float32)

        extra = None
        if attn_mask is not None:
            extra = jnp.where(attn_mask > 0, 0.0, -1e9).astype(jnp.float32)
        return sparse_attention_core(
            query, key, value, layout, self.sparsity_config.block, causal,
            mask_bias, use_pallas=self._use_pallas(), attn_bias=extra)


# beyond this, the exact dense fallback's [B, H, S, S] logits defeat the
# purpose of sparsity — reject loudly (matches models/transformer.py's
# DENSE_STREAM_THRESHOLD for the non-sparse fallbacks)
DENSE_SPARSE_MAX_SEQ = 4096


def sparse_attention_core(q, k, v, layout, block: int, causal: bool,
                          mask_bias=None, *, scale: Optional[float] = None,
                          use_pallas: bool, attn_bias=None):
    """Shared execution core: q/k/v [B, S, H, Hd] + [H, nb, nb] layout →
    [B, S, H, Hd]. Drives the block-sparse flash kernel when ``use_pallas``
    (zero blocks skipped fwd+bwd), else the exact dense token-bias einsum
    (pure jnp — vmappable and partitionable, the pipeline/CPU path). Used by
    :class:`SparseSelfAttention` and the model-level ``sparse_attention``
    config (models/transformer.py)."""
    B, S, H, Hd = q.shape
    if use_pallas and attn_bias is None:
        from deepspeed_tpu.ops.pallas import flash_attention
        return flash_attention(q, k, v, mask_bias=mask_bias, causal=causal,
                               scale=scale,
                               block_layout=jnp.asarray(layout, jnp.float32))
    if S > DENSE_SPARSE_MAX_SEQ:
        # the dense form materialises [B, H, S, S] f32 logits — at the long
        # sequences sparsity exists for, that defeats the point; reject
        # loudly rather than OOM (the kernel path streams by block; a dense
        # attn_bias is incompatible with it, pre-fold it into the layout or
        # key-side mask instead)
        raise NotImplementedError(
            f"sparse attention at S={S} > {DENSE_SPARSE_MAX_SEQ} needs the "
            "block-sparse kernel path (TPU, block >= 128, no dense "
            "attn_mask); the exact dense fallback would materialise the "
            "full score matrix")

    bias = layout_to_token_bias(layout, block, S)  # [H, S, S]
    scale = Hd**-0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = logits + bias[None, :, :, :]
    if causal:
        cm = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(cm[None, None], logits, -1e9)
    if mask_bias is not None:
        logits = logits + mask_bias[:, None, None, :]
    if attn_bias is not None:
        logits = logits + attn_bias
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
