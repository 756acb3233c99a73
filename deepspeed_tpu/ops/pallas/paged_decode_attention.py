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

Design: the kernel's work is (rows) x (a small fixed cost) + (live blocks)
x (about a block's copy time); the table's width costs nothing.

* grid ``(num_requests,)``, rows in order. The pools enter whole, where
  they lie in HBM (``pl.ANY``: no BlockSpec moves them); the block table and
  ``pos`` are scalar-prefetched. Row ``b`` has ``pos[b] // block_size + 1``
  live blocks, and a loop with that trip count walks its table in groups of
  G blocks. A dead table entry costs no grid step, no iteration and no copy,
  and what it names is never read (it may be anything);
* each live block is brought in by the kernel's own ``make_async_copy``
  (pool block ``bt[b, j]`` -> a VMEM slot), double-buffered: the next
  group's copies are in flight under this group's arithmetic, and the NEXT
  ROW's first group under this row's last, so only the call's first copy
  is waited for in the open. G comes from the shapes (``_group_blocks``):
  groups of about 0.75 MB a pool, one block at 2,048 bf16 lanes, four at 768;
* all heads of a group in one product. The row's query is laid
  block-diagonally once a row (``[H, KV*Hd]``: head ``h``'s values at the
  lanes of its kv head ``h // P``, zeros elsewhere), so scores are
  ``q_bd [H, KV*Hd] . k [G*bs, KV*Hd]^T`` and the numerator accumulates
  ``p [H, G*bs] @ v [G*bs, KV*Hd]`` whole in float32; each head's own
  ``Hd`` lanes (the diagonal) are taken once, at the end of the row. The
  MXU does KV times the arithmetic needed, still under the copy's time;
* no precision is given up for that: bf16 operands meet on the MXU's bf16
  path with float32 sums (a bf16 x bf16 product is exact in float32), and
  the float32 probabilities go there as three bf16 terms whose sum is
  exact (``_split3``), stacked as rows of one product. m, l, p and the
  numerator are float32. float32 pools take float32 products in full;
* per-request positions: ``pos[b]`` is the 0-based position of request
  ``b``'s new token (attends ``kpos <= pos[b]``) — requests at different
  depths decode in the same fused step (iteration-level batching);
* ALiBi slopes and an additive key-side ``pad_bias`` over LOGICAL positions
  keep parity with the dense kernel; GQA head ``h`` reads kv head ``h // P``.

Interpret mode on CPU — the unit tier pins parity vs ``decode_attention``
and a float32 reference on randomized block tables; the kernel's times by
state and G are ``benchmarks/paged_decode_bench.py``'s.
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
# VMEM the streamed k and v blocks may take: 2 pools x 2 slots x G blocks.
# Measured on a v5e (benchmarks/paged_decode_bench.py, PERF.md section 6,
# PR 27): rows of 2,048 bf16 lanes (0.5 MB a block and pool) are fastest one
# block an iteration (a block's copy covers its products; a larger group
# only computes on blocks a short row does not have), rows of 768 lanes at
# four (an iteration's fixed cost against 0.19 MB). This budget gives both,
# and leaves most of a kernel's default 16 MiB to everything else.
_STREAM_VMEM_BYTES = 3 * 1024 * 1024


def _group_blocks(bs: int, row: int, itemsize: int, n_blocks: int) -> int:
    """Blocks a loop iteration takes together (G): as many as the stream
    buffers hold, never more than a table is wide."""
    fit = _STREAM_VMEM_BYTES // (4 * bs * row * itemsize)
    return int(max(1, min(fit, n_blocks)))


def _split3(p):
    """float32 ``p`` as three bf16-exact float32 terms whose sum is ``p`` to
    its last bit: what lets a float32 probability meet a bf16 value on the
    MXU's bf16 path without being rounded."""
    hi = p.astype(jnp.bfloat16).astype(jnp.float32)
    r = p - hi
    mid = r.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, r - mid


def _kernel(bt_ref, pos_ref, q_ref, kp_hbm, vp_hbm, *rest, bs, G, group,
            has_bias, has_alibi):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    slope_ref = rest.pop(0) if has_alibi else None
    o_ref, kbuf, vbuf, sem, qbd_ref, acc_ref, slot_ref = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    n_blocks = bt_ref.shape[1]
    hp, hd = q_ref.shape[1:]
    kv = kbuf.shape[2] // hd
    bf16 = kbuf.dtype == jnp.bfloat16 and q_ref.dtype == jnp.bfloat16
    mxu = jnp.bfloat16 if bf16 else jnp.float32
    # operands of another precision than bf16 meet in float32, in full
    exact = None if bf16 else jax.lax.Precision.HIGHEST

    def live_blocks(row):
        return jnp.minimum(pos_ref[row] // bs + 1, n_blocks)

    def copies(row, j, slot, wait=False):
        """Start, or wait for, the copies of group ``j`` of ``row``: one per
        LIVE block and pool. A dead table entry is never read."""
        live = live_blocks(row)
        for i in range(G):
            blk = j * G + i

            @pl.when(blk < live)
            def _():
                src = bt_ref[row, blk]
                for s, (pool, buf) in enumerate(((kp_hbm, kbuf),
                                                 (vp_hbm, vbuf))):
                    cp = pltpu.make_async_copy(
                        pool.at[src], buf.at[slot, pl.ds(i * bs, bs)],
                        sem.at[s, slot])
                    cp.wait() if wait else cp.start()

    @pl.when(b == 0)
    def _():
        if G > 1:  # dslint: disable=DS004 (G is a static Python int)
            # a group's unfilled blocks are masked out of the scores, but
            # their probability 0 still multiplies what the value buffer
            # holds there: zeros, or an earlier block's finite values,
            # never stale VMEM
            vbuf[:] = jnp.zeros_like(vbuf)
        qbd_ref[:] = jnp.zeros_like(qbd_ref)
        slot_ref[0] = 0
        copies(0, 0, 0)

    pos = pos_ref[b]
    n_groups = pl.cdiv(live_blocks(b), G)
    slot0 = slot_ref[0]

    # the row's query laid block-diagonally: head h's Hd values at the
    # lanes of ITS kv head, zeros elsewhere, so ONE product with a block's
    # [tokens, KV*Hd] rows gives every head's scores
    q32 = q_ref[0].astype(jnp.float32)
    for g in range(kv):
        qbd_ref[g * group:(g + 1) * group, g * hd:(g + 1) * hd] = \
            q32[g * group:(g + 1) * group, :]
    qbd = qbd_ref[:].astype(mxu)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    if has_alibi:
        slope = slope_ref[:]                                   # [Hp, 1]

    def body(j, carry):
        m_prev, l_prev = carry
        slot = (slot0 + j) % 2

        # the next group's blocks fly under this group's arithmetic: this
        # row's, or the next row's first group under this row's last
        @pl.when(j + 1 < n_groups)
        def _():
            copies(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_and(j + 1 == n_groups, b + 1 < nb))
        def _():
            copies(b + 1, 0, 1 - slot)

        copies(b, j, slot, wait=True)
        k = kbuf[slot].astype(mxu)                             # [G*bs, KV*Hd]
        v = vbuf[slot].astype(mxu)
        s = jax.lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=exact)               # [Hp, G*bs]
        # LOGICAL key positions: the table only moved the storage
        kpos = j * (G * bs) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if has_alibi:
            s = s + slope * (kpos - pos).astype(jnp.float32)
        if has_bias:
            s = s + bias_ref[0, pl.ds(j, 1), :]
        s = jnp.where(kpos <= pos, s, _NEG)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                 # float32
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if bf16:
            # three bf16 terms of p stacked as rows: one pass of v through
            # the MXU, float32 sums, p exact
            pv = jnp.dot(jnp.concatenate(_split3(p), axis=0)
                         .astype(jnp.bfloat16), v,
                         preferred_element_type=jnp.float32)
            pv = pv[:hp] + pv[hp:2 * hp] + pv[2 * hp:]
        else:
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32,
                         precision=exact)
        acc_ref[:] = acc_ref[:] * alpha + pv                   # [Hp, KV*Hd]
        return m_new, l_new

    _, l = jax.lax.fori_loop(
        0, n_groups, body,
        (jnp.full((hp, 1), _NEG, jnp.float32), jnp.zeros((hp, 1), jnp.float32)))
    slot_ref[0] = (slot0 + n_groups) % 2

    # the product gave every head the values of every kv head: keep each
    # head's own (the diagonal), once a row
    for g in range(kv):
        rows = slice(g * group, (g + 1) * group)
        o_ref[0, rows, :] = (acc_ref[rows, g * hd:(g + 1) * hd]
                             / l[rows]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "G", "interpret"))
def _paged_call(q, kp, vp, bt, pos, bias, slopes, *, group, G, interpret):
    """q ``[B, Hp, Hd]`` (pre-scaled, heads padded to a multiple of 8);
    ``bias`` ``[B, n_groups, G*bs]`` or None; ``slopes`` ``[Hp, 1]`` or None."""
    B, hp, hd = q.shape
    bs, row = kp.shape[1:]
    in_specs = [
        pl.BlockSpec((1, hp, hd), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),      # the pools stay in HBM: the
        pl.BlockSpec(memory_space=pl.ANY),      # kernel copies live blocks
    ]
    args = [q, kp, vp]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1,) + bias.shape[1:],
                                     lambda b, *_: (b, 0, 0)))
        args.append(bias)
    if slopes is not None:
        in_specs.append(pl.BlockSpec((hp, 1), lambda b, *_: (0, 0)))
        args.append(slopes)
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, G=G, group=group,
                          has_bias=bias is not None,
                          has_alibi=slopes is not None),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hp, hd), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, G * bs, row), kp.dtype),   # k blocks, 2 slots
                pltpu.VMEM((2, G * bs, row), vp.dtype),   # v blocks, 2 slots
                pltpu.SemaphoreType.DMA((2, 2)),          # [pool, slot]
                pltpu.VMEM((hp, row), jnp.float32),       # block-diagonal q
                pltpu.VMEM((hp, row), jnp.float32),       # running numerator
                pltpu.SMEM((1,), jnp.int32),              # slot of next group
            ],
        ),
        # rows in order: a row starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((B, hp, hd), q.dtype),
        interpret=interpret,
    )(bt, pos, *args)


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
    tail entries may be anything — they are never read);
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
    n_blocks = block_tables.shape[1]
    G = _group_blocks(bs, KV * Hd, kp.dtype.itemsize, n_blocks)
    pad_h = -H % 8          # whole float32 sublane tiles of heads
    qs = jnp.pad(q * scale, ((0, 0), (0, pad_h), (0, 0)))
    bias = slopes = None
    if pad_bias is not None:
        # one group's logical positions a row: [B, groups, G*bs]
        n_groups = -(-n_blocks // G)
        bias = jnp.pad(pad_bias.astype(jnp.float32),
                       ((0, 0), (0, (n_groups * G - n_blocks) * bs))
                       ).reshape(B, n_groups, G * bs)
    if alibi_slopes is not None:
        slopes = jnp.pad(jnp.asarray(alibi_slopes, jnp.float32).reshape(H),
                         (0, pad_h)).reshape(H + pad_h, 1)
    out = _paged_call(qs, kp, vp,
                      jnp.asarray(block_tables, jnp.int32),
                      jnp.asarray(pos, jnp.int32).reshape(B),
                      bias, slopes, group=P, G=G,
                      interpret=bool(interpret))
    return out[:, :H]
