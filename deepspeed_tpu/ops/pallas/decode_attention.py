"""Pallas decode attention: one new token per sequence against the KV cache.

TPU-native replacement for the reference's fused ``softmax_context`` decode
kernel (KV-append + attention over the cached keys,
``csrc/transformer/inference/csrc/pt_binding.cpp:1668-1793``; workspace
``csrc/transformer/inference/includes/inference_context.h:49``).

Decode attention is HBM-bandwidth-bound: the cost is streaming the KV cache
once. The einsum fallback pays H/KV times that for GQA models when it
materialises a repeated copy of both cache halves before the dot. This
kernel:

* streams k/v blocks straight from the ``[B, Smax, KV, Hd]`` cache layout
  (no repeat, no transpose) — every cache block is fetched exactly once and
  ALL kv-head groups are consumed while it sits in VMEM (a static unrolled
  loop over the KV groups; KV is small). Keeping the full ``(KV, Hd)``
  minor dims in the block is also what Mosaic's tiling requires: a
  kv-head-sliced block of sublane extent 1 over a KV>1 array is not a legal
  TPU block shape;
* keeps the running (m, l, acc) streaming-softmax state in VMEM scratch
  across the sequence-block grid dimension, writing the ``[KV, P, Hd]``
  output tile once;
* masks ``kpos > pos`` blocks entirely (``pl.when``) and clamps the block
  index map at the last live block, so the dead cache tail costs neither
  DMA nor FLOPs;
* supports ALiBi slopes and an additive key-side pad bias ``[B, Smax]``
  (left-padded prompt slots).

Grid: ``(B, Smax/bk)`` — sequence blocks innermost so scratch carries.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dispatch import resolve_interpret

_NEG = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, bias_ref, slope_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bk, n_blocks, kv, group,
            has_bias, has_alibi):
    i = pl.program_id(1)
    pos = pos_ref[0]

    @pl.when(i == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    koff = i * bk
    run = koff <= pos  # whole block beyond the cached prefix → skip

    @pl.when(run)
    def _():
        kpos1 = koff + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if has_bias:
            bias = bias_ref[0, 0][None, :]
        # static unroll over kv groups: each group reads its own sublane of
        # the shared k/v block and its own row-slice of the scratch state
        for g in range(kv):
            rows = pl.ds(g * group, group)
            q = q_ref[0, g].astype(jnp.float32)          # [P, Hd] (pre-scaled)
            k = k_ref[0, :, g].astype(jnp.float32)       # [bk, Hd]
            v = v_ref[0, :, g].astype(jnp.float32)       # [bk, Hd]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            kpos = jnp.broadcast_to(kpos1, s.shape)      # [P, bk]
            if has_alibi:
                s = s + slope_ref[g][:, None] * (kpos - pos).astype(jnp.float32)
            if has_bias:
                s = s + bias
            s = jnp.where(kpos <= pos, s, _NEG)

            m_prev = m_ref[rows, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            # m/l live lane-broadcast in (H, 128) scratch (full-vreg stores)
            l_ref[rows, :] = l_ref[rows, :] * alpha[:, None] \
                + jnp.sum(p, axis=1)[:, None]
            m_ref[rows, :] = jnp.broadcast_to(m_new[:, None], (group, 128))
            acc_ref[rows, :] = acc_ref[rows, :] * alpha[:, None] + p @ v

    @pl.when(i == n_blocks - 1)
    def _():
        for g in range(kv):
            rows = pl.ds(g * group, group)
            o_ref[0, g] = (acc_ref[rows, :]
                           / l_ref[rows, 0][:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "has_bias", "has_alibi",
                                             "interpret"))
def _decode_call(q, ck, cv, pos, bias, slopes, *, bk, has_bias, has_alibi,
                 interpret):
    B, KV, P, Hd = q.shape
    Smax = ck.shape[1]
    n_blocks = Smax // bk
    grid = (B, n_blocks)

    # clamp the sequence-block index at the last block containing pos: dead
    # tail iterations revisit that block, which the pipeline does NOT
    # re-fetch — the kernel is bandwidth-bound, so with a workspace much
    # larger than the live prefix this is the dominant saving (the pl.when
    # guard then skips their FLOPs too)
    def kv_idx(b, i, sc):
        return (b, jnp.minimum(i, sc[0] // bk), 0, 0)

    in_specs = [
        pl.BlockSpec((1, KV, P, Hd), lambda b, i, sc: (b, 0, 0, 0)),
        pl.BlockSpec((1, bk, KV, Hd), kv_idx),
        pl.BlockSpec((1, bk, KV, Hd), kv_idx),
        # [B, 1, Smax]: the singleton keeps the sublane block extent equal to
        # its array dim (Mosaic forbids sublane-1 blocks over a larger dim)
        pl.BlockSpec((1, 1, bk),
                     lambda b, i, sc: (b, 0, jnp.minimum(i, sc[0] // bk))),
        pl.BlockSpec((KV, P), lambda b, i, sc: (0, 0)),  # alibi slopes
    ]
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, n_blocks=n_blocks, kv=KV, group=P,
                          has_bias=has_bias, has_alibi=has_alibi),
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, P, Hd), lambda b, i, sc: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV * P, 128), jnp.float32),  # running max
                pltpu.VMEM((KV * P, 128), jnp.float32),  # running denom
                pltpu.VMEM((KV * P, Hd), jnp.float32),   # running numerator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, P, Hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, ck, cv,
      bias.reshape(B, 1, Smax), slopes)
    return out


def decode_attention(q, ck, cv, pos, *, pad_bias=None, alibi_slopes=None,
                     scale: Optional[float] = None,
                     interpret: Optional[bool] = None):
    """Attention of one new token per sequence against the KV cache.

    q ``[B, H, Hd]`` (the single new token's heads, rope already applied);
    ck/cv ``[B, Smax, KV, Hd]`` with the new k/v already written at ``pos``;
    ``pos`` [] int32 — the new token's 0-based position (attends ``<= pos``).
    GQA head h reads kv head ``h // (H // KV)`` (``jnp.repeat`` order).
    Returns ``[B, H, Hd]``.

    Returns None when the shape is outside the kernel's envelope (caller
    falls back to the einsum path): Smax not divisible by the 128 block,
    or head_dim not lane-aligned.
    """
    B, H, Hd = q.shape
    Smax, KV = ck.shape[1], ck.shape[2]
    if H % KV != 0 or Hd % 64 != 0:
        return None
    bk = next((b for b in (512, 256, 128) if Smax % b == 0), None)
    if bk is None:
        return None
    interpret = resolve_interpret("decode_attention", interpret)
    P = H // KV
    scale = Hd**-0.5 if scale is None else scale
    qg = (q * scale).reshape(B, KV, P, Hd)
    if pad_bias is None:
        bias = jnp.zeros((B, Smax), jnp.float32)
    else:
        bias = pad_bias.astype(jnp.float32)
    if alibi_slopes is None:
        slopes = jnp.zeros((KV, P), jnp.float32)
    else:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(KV, P)
    out = _decode_call(qg, ck, cv, pos, bias, slopes, bk=bk,
                       has_bias=pad_bias is not None,
                       has_alibi=alibi_slopes is not None,
                       interpret=bool(interpret))
    return out.reshape(B, H, Hd)
