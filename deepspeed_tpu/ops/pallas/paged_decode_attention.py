"""Pallas paged decode attention: block-table KV gather inside the kernel.

The serving-side companion of :mod:`decode_attention` (vLLM PagedAttention
re-expressed for TPU): the KV cache is not one contiguous ``[B, Smax, ...]``
workspace but a POOL of fixed-size blocks ``[num_blocks, block_size, KV*Hd]``
(a token's kv heads merged into one lane-dense row, which is the device's
own row-major layout: the pool reaches the kernel's DMA without a copy)
shared by every in-flight request, and each request owns a *block table* —
the list of pool blocks holding its logical token positions. Continuous
batching retires/admits requests per step, so physical KV placement is
arbitrary; the kernel follows the table instead of a dense stride.

Design (mirrors ``decode_attention``, which documents the TPU reasoning):

* grid ``(num_requests, max_blocks_per_request)`` — block index innermost so
  the running (m, l, acc) streaming-softmax scratch carries across a
  request's blocks;
* the k/v BlockSpec index map reads the block table (scalar prefetch) to
  turn the logical block index ``i`` into a pool block id — the gather
  happens in the DMA engine, never materialising a contiguous per-request
  cache copy;
* a pool block lands in VMEM as ``[block_size, KV*Hd]``; kv head ``g`` is
  the static lane slice ``[:, g*Hd:(g+1)*Hd]`` of it;
* per-request positions: ``pos[b]`` is the 0-based position of request
  ``b``'s new token (attends ``kpos <= pos[b]``) — requests at different
  depths decode in the same fused step (iteration-level batching);
* the block index is clamped at the request's last live block, so the dead
  tail of the table costs neither DMA nor FLOPs (``pl.when`` guards the
  compute);
* ALiBi slopes and an additive key-side ``pad_bias`` over LOGICAL positions
  keep parity with the dense kernel.

Interpret mode on CPU — the unit tier pins parity vs ``decode_attention``
on randomized block tables.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dispatch import resolve_interpret
from deepspeed_tpu.utils.logging import warn_once

_NEG = -1e30


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, bias_ref, slope_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs, n_blocks, kv, group,
            has_bias, has_alibi):
    b = pl.program_id(0)
    i = pl.program_id(1)
    pos = pos_ref[b]
    hd = q_ref.shape[3]

    @pl.when(i == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    koff = i * bs
    run = koff <= pos  # whole block beyond the request's prefix → skip

    @pl.when(run)
    def _():
        # LOGICAL key positions of this block — the table gather only moved
        # the physical storage; attention geometry stays logical
        kpos1 = koff + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        if has_bias:
            bias = bias_ref[0, 0][None, :]
        for g in range(kv):
            rows = pl.ds(g * group, group)
            q = q_ref[0, g].astype(jnp.float32)          # [P, Hd] (pre-scaled)
            lanes = pl.ds(g * hd, hd)                    # kv head g's lanes
            k = k_ref[0, :, lanes].astype(jnp.float32)   # [bs, Hd]
            v = v_ref[0, :, lanes].astype(jnp.float32)   # [bs, Hd]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            kpos = jnp.broadcast_to(kpos1, s.shape)      # [P, bs]
            if has_alibi:
                s = s + slope_ref[g][:, None] * (kpos - pos).astype(jnp.float32)
            if has_bias:
                s = s + bias
            s = jnp.where(kpos <= pos, s, _NEG)

            m_prev = m_ref[rows, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
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


@functools.partial(jax.jit, static_argnames=("bs", "has_bias", "has_alibi",
                                             "interpret"))
def _paged_call(q, kp, vp, bt, pos, bias, slopes, *, bs, has_bias, has_alibi,
                interpret):
    B, KV, P, Hd = q.shape
    n_blocks = bt.shape[1]
    grid = (B, n_blocks)

    # clamp the block index at the request's last LIVE table entry: dead
    # tail iterations revisit that pool block (no re-fetch — same index)
    # and the pl.when guard skips their FLOPs
    def kv_idx(b, i, bt_s, pos_s):
        return (bt_s[b, jnp.minimum(i, pos_s[b] // bs)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KV, P, Hd), lambda b, i, bt_s, pos_s: (b, 0, 0, 0)),
        pl.BlockSpec((1, bs, KV * Hd), kv_idx),
        pl.BlockSpec((1, bs, KV * Hd), kv_idx),
        # bias over LOGICAL positions rides [B, 1, n_blocks * bs] like the
        # dense kernel's (a sublane-1 block over a larger dim is not a legal
        # Mosaic block); the lane-block index follows the clamped logical
        # block, not the pool id
        pl.BlockSpec((1, 1, bs),
                     lambda b, i, bt_s, pos_s:
                     (b, 0, jnp.minimum(i, pos_s[b] // bs))),
        pl.BlockSpec((KV, P), lambda b, i, bt_s, pos_s: (0, 0)),
    ]
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_blocks=n_blocks, kv=KV, group=P,
                          has_bias=has_bias, has_alibi=has_alibi),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, P, Hd),
                                   lambda b, i, bt_s, pos_s: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV * P, 128), jnp.float32),  # running max
                pltpu.VMEM((KV * P, 128), jnp.float32),  # running denom
                pltpu.VMEM((KV * P, Hd), jnp.float32),   # running numerator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, P, Hd), q.dtype),
        interpret=interpret,
    )(bt, pos, q, kp, vp, bias.reshape(B, 1, n_blocks * bs), slopes)
    return out


def paged_envelope_ok(H: int, KV: int, Hd: int, bs: int) -> bool:
    """Whether a (heads, kv_heads, head_dim, block_size) shape sits inside
    the kernel's envelope. The ONE home of the envelope — the transformer's
    shard_map dispatch checks it against PER-SHARD shapes before entering a
    manual region (a shard_map body cannot fall back per-shard), and
    :func:`paged_decode_attention` checks it to decide None-vs-kernel."""
    return (H % KV == 0 and Hd % 64 == 0 and (KV * Hd) % 128 == 0
            and bs % 128 == 0)


def paged_decode_attention(q, kp, vp, block_tables, pos, *, pad_bias=None,
                           alibi_slopes=None, scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Attention of one new token per request against a PAGED KV cache.

    q ``[B, H, Hd]`` (one new token per running request, rope applied);
    kp/vp ``[num_blocks, block_size, KV*Hd]`` — the shared block pools (a
    token's kv heads merged, head ``g`` at lanes ``[g*Hd, (g+1)*Hd)``; KV
    is read off ``kp.shape[2] // Hd``), with each request's new k/v already
    written at its slot;
    ``block_tables`` ``[B, max_blocks]`` int32 pool block ids (logical block
    ``j`` of request ``b`` lives in pool block ``block_tables[b, j]``; dead
    tail entries may be anything — they are clamped away);
    ``pos`` ``[B]`` int32 per-request 0-based position of the new token
    (request ``b`` attends logical positions ``<= pos[b]``).
    ``pad_bias`` ``[B, max_blocks * block_size]`` additive f32 bias over
    logical positions. GQA head h reads kv head ``h // (H // KV)``.
    Returns ``[B, H, Hd]``.

    Returns None when the shape is outside the kernel's envelope (caller
    falls back to a gather + einsum path): block_size not a multiple of
    128, head_dim not lane-aligned, a pool row ``KV*Hd`` that is not a
    multiple of 128 lanes (MQA at Hd 64), or H % KV != 0.
    """
    B, H, Hd = q.shape
    bs, KV = kp.shape[1], kp.shape[2] // Hd
    if not paged_envelope_ok(H, KV, Hd, bs):
        warn_once(f"paged_decode_attention: heads={H} kv_heads={KV} "
                  f"head_dim={Hd} block_size={bs} is outside the kernel "
                  "envelope (H % KV == 0, head_dim % 64 == 0, kv_heads * "
                  "head_dim % 128 == 0, block_size % 128 == 0); the caller "
                  "takes its gather + einsum form")
        return None
    interpret = resolve_interpret("paged_decode_attention", interpret)
    P = H // KV
    scale = Hd**-0.5 if scale is None else scale
    qg = (q * scale).reshape(B, KV, P, Hd)
    n_blocks = block_tables.shape[1]
    if pad_bias is None:
        bias = jnp.zeros((B, n_blocks * bs), jnp.float32)
    else:
        bias = pad_bias.astype(jnp.float32)
    if alibi_slopes is None:
        slopes = jnp.zeros((KV, P), jnp.float32)
    else:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(KV, P)
    out = _paged_call(qg, kp, vp,
                      jnp.asarray(block_tables, jnp.int32),
                      jnp.asarray(pos, jnp.int32).reshape(B),
                      bias, slopes, bs=bs,
                      has_bias=pad_bias is not None,
                      has_alibi=alibi_slopes is not None,
                      interpret=bool(interpret))
    return out.reshape(B, H, Hd)
