"""Pallas flash attention for TPU (training: forward + custom-VJP backward).

Blockwise streaming-softmax attention that never materialises the [S, S]
score matrix: HBM traffic is O(S·Hd) instead of O(S²), q/k/v blocks are
DMA'd into VMEM by the pallas pipeline and every matmul lands on the MXU.
Replaces the reference's fused CUDA attention/softmax kernels
(``csrc/transformer/softmax_kernels.cu``, training layer
``csrc/transformer/ds_transformer_cuda.cpp``; inference ``softmax_context``
in ``csrc/transformer/inference/csrc/pt_binding.cpp``) with the
TPU-idiomatic design.

Grid layout (forward): ``(B, H, Sq/bq, Sk/bk)`` — the kv dimension is
innermost, so the (m, l, acc) running-softmax state lives in VMEM scratch
across kv steps and the output block is written once on the last step.
Backward recomputes p from the saved logsumexp (no S² residuals): one
kernel accumulates dq over kv blocks, a second accumulates dk/dv over q
blocks. Both WALK a causal diagonal block in chunks of ``_DIAG_CHUNK`` query
rows and compute, a chunk, only the keys up to its own (``_walk``): 9/16 of a
1,024 x 1,024 block's scores at a chunk of 128 where the mask keeps 1/2, so
the masked half of the block is never multiplied; a grid step above the
diagonal names blocks the kernel already has (``_skipped_block_maps``), and
dk/dv compute their scores TRANSPOSED, (keys, rows), so that p^T and ds^T are
the products' left sides as they come. The forward takes a diagonal block
whole.

Two VPU optimisations matter on TPU (softmax is VPU-bound while the dots
ride the MXU):

* the streaming softmax runs in the **log2 domain** (logits pre-scaled by
  log2(e), ``exp2`` instead of ``exp``) — the VPU evaluates exp2 faster;
* the common case (causal, no user mask, no alibi, no padding) takes a
  **plain fast path**: fully-visible blocks below the diagonal skip masking
  entirely, and diagonal blocks add one precomputed triangular bias block
  instead of running per-element iota/compare/select (the forward: the whole
  (bq, bk) block's; the backward kernels' walk: its (chunk, chunk) corner,
  added to a query chunk's OWN keys alone).

The kernel's forward outputs (o, lse) carry ``checkpoint_name`` tags
("flash_o"/"flash_lse") so activation-checkpoint policies can save the
attention residuals and run the backward kernels without re-running the
forward kernel: the model zoo's ``remat="dots"`` and ``remat="selective"``
both name them (a policy that goes by primitive cannot see a matmul result
inside a ``pallas_call``). Kept, they cost tokens x H x Hd x 2 B (bf16; twice
that where Hd = 64 pads to 128 lanes in the general kernel's [B, H, S, Hd]
layout) plus H x tokens x 4 B a layer.

Supports causal masking, an additive key-side mask bias [B, S], and ALiBi
slopes. Runs compiled on TPU, interpreted elsewhere (CPU unit tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dispatch import record, resolve_interpret

_MASKED = -1e30  # large-negative for masked logits (exp2 underflows to 0)
_LOG2E = 1.4426950408889634
#: query rows a chunk of the BACKWARD kernels' walk over a diagonal block
#: (``_diag_chunk``; chosen on the chip, ``benchmarks/flash_train_bench.py``:
#: PERF.md section 6, PR 46: 128 and 256 within 2-4% of each other, 512 8-13%
#: behind). A multiple of 128 on a TPU (the chunks slice the lse / delta /
#: mask rows along lanes), and a 1,024 block is 8 chunks, the longest walk
#: unrolled; the CPU tests patch it small.
_DIAG_CHUNK = 128
#: the ``checkpoint_name`` tags of the forward outputs (o, lse): what a remat
#: policy lists to keep them
RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _named(o, lse):
    return (checkpoint_name(o, RESIDUAL_NAMES[0]),
            checkpoint_name(lse, RESIDUAL_NAMES[1]))


def _block_bias(qoff, koff, bq, bk, seq_len, causal, slope, mask_blk,
                stair=1, window=0, keys_first=False):
    """Additive log2-domain bias for a (bq, bk) score block from GLOBAL
    positions: alibi + causal/pad masking + user key mask. ``stair`` > 1:
    the causal triangle is a staircase of that step (a query sees all of
    its own group of ``stair`` positions). ``window`` > 0: a query sees the
    ``window`` keys up to its own and none before them."""
    if keys_first:
        # the (bk, bq) bias of a block of TRANSPOSED scores (dk/dv)
        qpos = qoff + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        kpos = koff + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    else:
        qpos = qoff + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = koff + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    bias = (slope * _LOG2E) * (kpos - qpos).astype(jnp.float32)  # slope==0 → no-op
    valid = kpos < seq_len
    if causal and stair > 1:
        valid = valid & (qpos // stair >= kpos // stair)
    elif causal:
        valid = valid & (qpos >= kpos)
    if window:
        valid = valid & (kpos > qpos - window)
    bias = jnp.where(valid, bias, _MASKED)
    mask_blk = mask_blk[:, None] if keys_first else mask_blk[None, :]
    return bias + mask_blk * _LOG2E


def _dispatch(run, i, j, plain, causal, update, logits, tri_ref, bias,
              low=None):
    """Apply ``update`` to the block's log2-domain logits with the cheapest
    masking that is correct: nothing for fully-visible plain blocks, one
    precomputed triangular block on the plain diagonal (i == j), or the
    general computed bias. Shared by the forward and both backward kernels.
    ``low`` = n: a plain band whose lower edge crosses the blocks ``n`` below
    the diagonal. Key c of such a block is inside query r's window iff c >
    r: the complement of the diagonal block's triangle, so its bias is read
    off ``tri_ref`` (a second precomputed block would be 4 MB more of VMEM
    at 1,024 x 1,024, twice with its double buffer)."""
    if plain and causal and low is not None:
        diag, edge = i == j, i - j == low

        @pl.when(jnp.logical_and(run, diag))
        def _():
            update(logits() + tri_ref[:])

        @pl.when(jnp.logical_and(run, edge))
        def _():
            update(logits() + jnp.where(tri_ref[:] < 0.0, 0.0, _MASKED))

        @pl.when(jnp.logical_and(run, jnp.logical_not(
            jnp.logical_or(diag, edge))))
        def _():
            update(logits())
    elif plain and causal:
        @pl.when(jnp.logical_and(run, i == j))
        def _():
            update(logits() + tri_ref[:])

        @pl.when(jnp.logical_and(run, i != j))
        def _():
            update(logits())
    elif plain:
        @pl.when(run)
        def _():
            update(logits())
    else:
        @pl.when(run)
        def _():
            update(logits() + bias())


def _make_tri(bq, bk, stair=1):
    """Precomputed (bq, bk) diagonal-block causal bias: 0 keep / -1e30 drop;
    a staircase of step ``stair`` in place of the triangle where > 1."""
    r = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if stair > 1:
        r, c = r // stair, c // stair
    return jnp.where(r >= c, 0.0, _MASKED).astype(jnp.float32)


def _diag_chunk(causal, bq, bk):
    """Query rows a chunk of the backward kernels' walk over a diagonal
    block, or 0 where they take every block whole (``_dispatch``, as the
    forward does): non-causal, ``bq != bk``, or a block that is no two whole
    chunks."""
    c = _DIAG_CHUNK
    return c if causal and bq == bk and bq % c == 0 and bq >= 2 * c else 0


def _skipped_block_maps(causal, bq, bk):
    """(key block, query block) index maps ``f(i, j)`` of the backward
    kernels' in-specs. Causal with bq == bk, a step above the diagonal
    (j > i) is skipped: ``dq`` names the key block it has (the diagonal's,
    min(j, i)) and ``dk/dv`` the query block it is about to need (max(i,
    j)), so a skipped step copies nothing it will not read and the step
    after it need not wait for its blocks (as the forward band's
    ``_band_kv_spec``). Anything else: the step's own blocks."""
    if causal and bq == bk:
        return (lambda i, j: jnp.minimum(j, i)), (lambda i, j: jnp.maximum(i, j))
    return (lambda i, j: j), (lambda i, j: i)


def _walk(run, i, j, bq, chunk, pair):
    """The backward kernels' analogue of :func:`_dispatch` where a diagonal
    block is walked in chunks (``_diag_chunk`` > 0, so causal and bq == bk).
    On block i == j: ``pair(rows, keys, True)`` once a chunk of ``chunk`` query
    rows, with the keys up to and including the chunk's own (static slices
    of the block): every score on or below the diagonal once, nothing right
    of the chunk's own keys, and the causal edge crosses the LAST ``chunk``
    keys of a pair only (a staircase's too: its step divides 8). On a block
    below the diagonal: the whole of it, ``pair(all rows, all keys, False)``.
    Query chunk by query chunk for ``dq`` AND ``dk/dv`` (PERF.md section 6,
    PR 46: key chunk by key chunk, every pair a chunk wide, read 1.3-1.75 x
    ``dq``'s time at a chunk of 128 and 1.0-1.3 x ``dk/dv``'s, and a chunk's
    own keys as a product of their own up to 1.37 x)."""
    @pl.when(jnp.logical_and(run, i == j))
    def _():
        for lo in range(0, bq, chunk):
            pair(slice(lo, lo + chunk), slice(0, lo + chunk), True)

    @pl.when(jnp.logical_and(run, i != j))
    def _():
        pair(slice(0, bq), slice(0, bq), False)


def _walk_bias(s, rs, ks, diag, below, edge, keys_first=False):
    """The scores of a walk's pair plus their bias. ``diag``: a pair of a
    diagonal block, whose keys end with the query chunk's own: those take
    ``edge(rows, keys)``, the bias of a chunk pair the causal edge crosses,
    and the keys before them ``below(rows, keys)``, as the whole of a block
    below the diagonal does (``below`` None: no bias there)."""
    def clear(x, keys):
        return x if below is None else x + below(rs, keys)
    if not diag:
        return clear(s, ks)
    own = slice(rs.start, ks.stop)
    if own == ks:
        return s + edge(rs, own)
    lo = own.start - ks.start
    if keys_first:
        return jnp.concatenate([clear(s[:lo], slice(ks.start, own.start)),
                                s[lo:] + edge(rs, own)], axis=0)
    return jnp.concatenate([clear(s[:, :lo], slice(ks.start, own.start)),
                            s[:, lo:] + edge(rs, own)], axis=1)


def _general_walk(run, i, j, bq, chunk, plain, update, logits, tri_ref, bias,
                  keys_first=False):
    """:func:`_walk` for the general kernels: a plain call's bias is tri on
    a chunk's own keys and nothing before them, another's is computed a
    pair, with the causal compare on a chunk's own keys alone."""
    if plain:
        below, edge = None, lambda rs, ks: tri_ref[:]
    else:
        below, edge = (functools.partial(bias, edge=e) for e in (False, True))

    def pair(rs, ks, diag):
        update(_walk_bias(logits(rs, ks), rs, ks, diag, below, edge, keys_first),
               rs, ks)

    _walk(run, i, j, bq, chunk, pair)


def _packed_dispatch(run, i, j, causal, step, logits, tri_ref, P):
    """Packed-kernel analogue of :func:`_dispatch`: per packed head p, run
    ``step(p, logits(p) [+ tri])`` with the diagonal tri only where needed."""
    if causal:
        @pl.when(jnp.logical_and(run, i == j))
        def _():
            for p in range(P):
                step(p, logits(p) + tri_ref[:])

        @pl.when(jnp.logical_and(run, i != j))
        def _():
            for p in range(P):
                step(p, logits(p))
    else:
        @pl.when(run)
        def _():
            for p in range(P):
                step(p, logits(p))


def _parse_rest(rest, plain, has_layout):
    # tri: the forward's (bq, bk) diagonal-block bias; in a backward kernel
    # that walks (``chunk`` > 0) the (chunk, chunk) bias of a diagonal pair
    idx = 0
    tri_ref = None
    if plain:
        tri_ref, idx = rest[0], 1
    layout_ref = None
    if has_layout:
        layout_ref, idx = rest[idx], idx + 1
    return tri_ref, layout_ref, rest[idx:]


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, slope_ref, *rest,
                scale, causal, seq_len, bq, bk, plain, has_layout, stair=1,
                window=0):
    # a plain band (``_build``: bq == bk, a whole number of blocks wide)
    low = window // bk if window and plain else None
    tri_ref, layout_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = \
        _parse_rest(rest, plain, has_layout)
    # refs (leading dims squeezed): q/o (bq, Hd); k/v (bk, Hd); mask (bk,);
    # lse (bq,); slope (1, 1) in SMEM
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _MASKED)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qoff, koff = i * bq, j * bk
    # skip blocks above the causal diagonal AND blocks the sparsity layout
    # zeroes out (block-sparse attention, reference ops/sparse_attention/)
    needed = True if not causal else (koff <= qoff + bq - 1)
    if window:
        # ... and blocks wholly below the band: their last key lies before
        # the first query's window
        needed = jnp.logical_and(needed, koff + bk - 1 > qoff - window)
    run = needed if layout_ref is None else jnp.logical_and(needed, layout_ref[0, 0] > 0)

    def logits():
        # keep q/k in their storage dtype (bf16) for the MXU dot — f32
        # operands run at a fraction of the MXU's bf16 rate; f32 accumulate
        return jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * (scale * _LOG2E)

    def update(s):
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _dispatch(run, i, j, plain, causal, update, logits, tri_ref,
              lambda: _block_bias(qoff, koff, bq, bk, seq_len, causal,
                                  slope_ref[0, 0], mask_ref[0].astype(jnp.float32),
                                  stair, window), low)

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[:] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # log2-domain "safe" logsumexp: +big for fully-masked rows so bwd
        # p=exp2(s-lse)=0
        lse_ref[0] = jnp.where(l[:, 0] > 0, m_scr[:, 0] + jnp.log2(safe_l[:, 0]), -_MASKED)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, slope_ref,
               *rest, scale, causal, seq_len, bq, bk, plain, has_layout, stair=1,
               chunk=0):
    # ``chunk`` > 0: a diagonal block is walked in chunks (``_walk``) and
    # tri_ref is the (chunk, chunk) bias of a pair on the diagonal
    tri_ref, layout_ref, (dq_ref, dq_scr) = _parse_rest(rest, plain, has_layout)
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    qoff, koff = i * bq, j * bk
    needed = True if not causal else (koff <= qoff + bq - 1)
    run = needed if layout_ref is None else jnp.logical_and(needed, layout_ref[0, 0] > 0)
    # rows / keys of the block a call covers: all of it, or a walk's pair
    rows, keys = slice(0, bq), slice(0, bk)

    def logits(rs=rows, ks=keys):
        return jax.lax.dot_general(q_ref[rs, :], k_ref[ks, :], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * (scale * _LOG2E)

    def update(s, rs=rows, ks=keys):
        k = k_ref[ks, :]
        p = jnp.exp2(s - lse_ref[0, rs][:, None])
        dp = jax.lax.dot_general(do_ref[rs, :], v_ref[ks, :], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, rs][:, None]) * scale).astype(k.dtype)
        dq_scr[rs, :] = dq_scr[rs, :] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def bias(rs=rows, ks=keys, edge=causal):
        # ``edge``: the causal edge crosses these rows and keys
        return _block_bias(qoff + rs.start, koff + ks.start, rs.stop - rs.start,
                           ks.stop - ks.start, seq_len, edge, slope_ref[0, 0],
                           mask_ref[0, ks].astype(jnp.float32), stair)

    if chunk:
        _general_walk(run, i, j, bq, chunk, plain, update, logits, tri_ref, bias)
    else:
        _dispatch(run, i, j, plain, causal, update, logits, tri_ref, bias)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, slope_ref,
                *rest, scale, causal, seq_len, bq, bk, plain, has_layout, stair=1,
                chunk=0):
    tri_ref, layout_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = \
        _parse_rest(rest, plain, has_layout)
    # grid (B, KV, nk, G, nq): q blocks innermost, then the G query heads of
    # the kv group — dk/dv for one kv block accumulate in scratch across BOTH
    # inner axes, which is what makes the kernel GQA-native (kv gradients sum
    # over the group's query heads without ever materialising repeated kv)
    i = pl.program_id(4)
    nq = pl.num_programs(4)
    g = pl.program_id(3)
    ng = pl.num_programs(3)
    j = pl.program_id(2)

    @pl.when(jnp.logical_and(i == 0, g == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qoff, koff = i * bq, j * bk
    needed = True if not causal else (koff <= qoff + bq - 1)
    run = needed if layout_ref is None else jnp.logical_and(needed, layout_ref[0, 0] > 0)
    rows, keys = slice(0, bq), slice(0, bk)

    # the scores TRANSPOSED, (keys, rows): p^T and ds^T then ARE the left
    # sides of the dv and dk products (no transpose of a score-sized array),
    # the lse / delta rows broadcast along sublanes as they lie, and all
    # four products stream the keys (PERF.md section 6, PR 46)
    def logits(rs=rows, ks=keys):
        return jax.lax.dot_general(k_ref[ks, :], q_ref[rs, :], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * (scale * _LOG2E)

    def update(s, rs=rows, ks=keys):
        q, do = q_ref[rs, :], do_ref[rs, :]
        p = jnp.exp2(s - lse_ref[0, rs][None, :]).astype(do.dtype)
        dv_scr[ks, :] = dv_scr[ks, :] + jax.lax.dot_general(
            p, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[ks, :], do,
                                 (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - delta_ref[0, rs][None, :]) * scale).astype(q.dtype)
        dk_scr[ks, :] = dk_scr[ks, :] + jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def bias(rs=rows, ks=keys, edge=causal):
        return _block_bias(qoff + rs.start, koff + ks.start, rs.stop - rs.start,
                           ks.stop - ks.start, seq_len, edge, slope_ref[0, 0],
                           mask_ref[0, ks].astype(jnp.float32), stair,
                           keys_first=True)

    if chunk:
        _general_walk(run, i, j, bq, chunk, plain, update, logits, tri_ref, bias, True)
    else:
        _dispatch(run, i, j, plain, causal, update, logits, tri_ref, bias)

    @pl.when(jnp.logical_and(i == nq - 1, g == ng - 1))
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# packed-heads layout (Hd < 128): q/k/v stay [B, S, H*Hd] — the natural
# projection output layout — and each program covers P = 128//Hd heads, so
# every VMEM block is a full 128-lane tile (no lane padding) and NO XLA-side
# transpose is needed on inputs or outputs in either pass. Plain-causal
# only; masked/alibi/sparse shapes use the general [B, H, S, Hd] kernels.

def _packed_fwd_kernel(q_ref, k_ref, v_ref, tri_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, P, Hd):
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _MASKED)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True if not causal else (j * bk <= i * bq + bq - 1)

    def step(p, s):
        sl = slice(p * Hd, (p + 1) * Hd)
        m_prev = m_scr[:, p * Hd:p * Hd + 1]
        l_prev = l_scr[:, p * Hd:p * Hd + 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        pmat = jnp.exp2(s - m_new)
        l_new = l_prev * alpha + jnp.sum(pmat, axis=1, keepdims=True)
        acc_scr[:, sl] = acc_scr[:, sl] * alpha + jax.lax.dot_general(
            pmat.astype(v_ref.dtype), v_ref[:, sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, sl] = jnp.broadcast_to(m_new, (m_new.shape[0], Hd))
        l_scr[:, sl] = jnp.broadcast_to(l_new, (l_new.shape[0], Hd))

    def logits(p):
        sl = slice(p * Hd, (p + 1) * Hd)
        return jax.lax.dot_general(q_ref[:, sl], k_ref[:, sl], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * (scale * _LOG2E)

    _packed_dispatch(run, i, j, causal, step, logits, tri_ref, P)

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[:] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        for p in range(P):
            c = p * Hd
            lse_ref[p] = jnp.where(l[:, c] > 0, m_scr[:, c] + jnp.log2(safe_l[:, c]),
                                   -_MASKED)


def _packed_walk(run, i, j, bq, chunk, step, logits, tri_ref, P, keys_first=False):
    """:func:`_walk` for the packed kernels: every pair once a packed head."""
    def pair(rs, ks, diag):
        for p in range(P):
            step(p, _walk_bias(logits(p, rs, ks), rs, ks, diag, None,
                               lambda rs, ks: tri_ref[:], keys_first), rs, ks)

    _walk(run, i, j, bq, chunk, pair)


def _packed_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, tri_ref,
                      dq_ref, dq_scr, *, scale, causal, bq, bk, P, Hd, chunk=0):
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True if not causal else (j * bk <= i * bq + bq - 1)
    rows, keys = slice(0, bq), slice(0, bk)

    def logits(p, rs=rows, ks=keys):
        sl = slice(p * Hd, (p + 1) * Hd)
        return jax.lax.dot_general(q_ref[rs, sl], k_ref[ks, sl], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * (scale * _LOG2E)

    def step(p, s, rs=rows, ks=keys):
        sl = slice(p * Hd, (p + 1) * Hd)
        k = k_ref[ks, sl]
        pmat = jnp.exp2(s - lse_ref[p, rs][:, None])
        dp = jax.lax.dot_general(do_ref[rs, sl], v_ref[ks, sl], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (pmat * (dp - delta_ref[p, rs][:, None]) * scale).astype(k.dtype)
        dq_scr[rs, sl] = dq_scr[rs, sl] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if chunk:
        _packed_walk(run, i, j, bq, chunk, step, logits, tri_ref, P)
    else:
        _packed_dispatch(run, i, j, causal, step, logits, tri_ref, P)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _packed_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, tri_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, bq, bk, P, Hd,
                       chunk=0):
    # grid (B, H2, nk, nq): q blocks innermost
    i = pl.program_id(3)
    nq = pl.num_programs(3)
    j = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True if not causal else (j * bk <= i * bq + bq - 1)
    rows, keys = slice(0, bq), slice(0, bk)

    # the scores transposed, (keys, rows), as ``_dkv_kernel``'s
    def logits(p, rs=rows, ks=keys):
        sl = slice(p * Hd, (p + 1) * Hd)
        return jax.lax.dot_general(k_ref[ks, sl], q_ref[rs, sl], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * (scale * _LOG2E)

    def step(p, s, rs=rows, ks=keys):
        sl = slice(p * Hd, (p + 1) * Hd)
        q, do = q_ref[rs, sl], do_ref[rs, sl]
        pmat = jnp.exp2(s - lse_ref[p, rs][None, :]).astype(do.dtype)
        dv_scr[ks, sl] = dv_scr[ks, sl] + jax.lax.dot_general(
            pmat, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[ks, sl], do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (pmat.astype(jnp.float32) * (dp - delta_ref[p, rs][None, :]) * scale).astype(q.dtype)
        dk_scr[ks, sl] = dk_scr[ks, sl] + jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if chunk:
        _packed_walk(run, i, j, bq, chunk, step, logits, tri_ref, P, True)
    else:
        _packed_dispatch(run, i, j, causal, step, logits, tri_ref, P)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=32)
def _build_packed(causal: bool, scale: float, bq: int, bk: int, interpret: bool,
                  P: int, Hd: int, chunk: int = 0):
    """Custom-VJP flash on [B, S, H*Hd] inputs, P heads per program.
    ``chunk`` (``_diag_chunk``) > 0: the backward kernels walk a diagonal
    block in chunks of that many query rows."""
    lanes = P * Hd

    def xq_spec():
        # block (bq, P*Hd) over [B, S, D] at head-group h
        return pl.BlockSpec((None, bq, lanes), lambda b, h, i, j: (b, i, h))

    def xkv_spec():
        return pl.BlockSpec((None, bk, lanes), lambda b, h, i, j: (b, j, h))

    tri_spec = pl.BlockSpec((bq, bk), lambda b, h, i, j: (0, 0))
    row_spec = pl.BlockSpec((None, None, P, bq), lambda b, h, i, j: (b, h, 0, i))

    def fwd_call(q, k, v, tri):
        B, Sp, D = q.shape
        H2 = D // lanes
        nq, nk = Sp // bq, Sp // bk
        kernel = functools.partial(_packed_fwd_kernel, scale=scale, causal=causal,
                                   bq=bq, bk=bk, P=P, Hd=Hd)
        o, lse = pl.pallas_call(
            kernel,
            name="flash_packed_fwd",
            grid=(B, H2, nq, nk),
            in_specs=[xq_spec(), xkv_spec(), xkv_spec(), tri_spec],
            out_specs=[xq_spec(), row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, Sp, D), q.dtype),
                jax.ShapeDtypeStruct((B, H2, P, Sp), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, lanes), jnp.float32),
                pltpu.VMEM((bq, lanes), jnp.float32),
                pltpu.VMEM((bq, lanes), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, tri)
        return _named(o, lse)

    @jax.custom_vjp
    def flash(q, k, v, tri):
        return fwd_call(q, k, v, tri)[0]

    def flash_fwd(q, k, v, tri):
        o, lse = fwd_call(q, k, v, tri)
        return o, (q, k, v, tri, o, lse)

    def flash_bwd(res, g):
        q, k, v, tri, o, lse = res
        B, Sp, D = q.shape
        H2 = D // lanes
        nq, nk = Sp // bq, Sp // bk
        # per-head delta rows: sum g*o over each head's lane group
        delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            B, Sp, H2, P, Hd).sum(-1).transpose(0, 2, 3, 1)  # [B, H2, P, Sp]
        record("flash_bwd_diag", "chunks" if chunk else "whole", f"bq={bq} c={chunk}")
        # a walk's bias is that of a pair ON the diagonal: the block's own
        # top-left corner (a staircase's step divides the chunk)
        bias = tri[:chunk, :chunk] if chunk else tri
        key_of, query_of = _skipped_block_maps(causal, bq, bk)
        dq_kv_spec = pl.BlockSpec((None, bk, lanes),
                                  lambda b, h, i, j: (b, key_of(i, j), h))

        dq_kernel = functools.partial(_packed_dq_kernel, scale=scale, causal=causal,
                                      bq=bq, bk=bk, P=P, Hd=Hd, chunk=chunk)
        dq = pl.pallas_call(
            dq_kernel,
            name="flash_packed_dq",
            grid=(B, H2, nq, nk),
            in_specs=[xq_spec(), dq_kv_spec, dq_kv_spec, xq_spec(),
                      row_spec, row_spec,
                      pl.BlockSpec(bias.shape, lambda b, h, i, j: (0, 0))],
            out_specs=xq_spec(),
            out_shape=jax.ShapeDtypeStruct((B, Sp, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, lanes), jnp.float32)],
            interpret=interpret,
        )(q, k, v, g, lse, delta, bias)

        kq_spec = pl.BlockSpec((None, bq, lanes),
                               lambda b, h, j, i: (b, query_of(i, j), h))
        kkv_spec = pl.BlockSpec((None, bk, lanes), lambda b, h, j, i: (b, j, h))
        krow_spec = pl.BlockSpec((None, None, P, bq),
                                 lambda b, h, j, i: (b, h, 0, query_of(i, j)))
        # dk/dv take their scores (keys, rows): the bias transposed
        ktri_spec = pl.BlockSpec(bias.T.shape, lambda b, h, j, i: (0, 0))

        dkv_kernel = functools.partial(_packed_dkv_kernel, scale=scale, causal=causal,
                                       bq=bq, bk=bk, P=P, Hd=Hd, chunk=chunk)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            name="flash_packed_dkv",
            grid=(B, H2, nk, nq),
            in_specs=[kq_spec, kkv_spec, kkv_spec, kq_spec, krow_spec, krow_spec,
                      ktri_spec],
            out_specs=[kkv_spec, kkv_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, Sp, D), q.dtype),
                jax.ShapeDtypeStruct((B, Sp, D), q.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, lanes), jnp.float32),
                pltpu.VMEM((bk, lanes), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, g, lse, delta, bias.T)

        return dq, dk, dv, jnp.zeros_like(tri)

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def _q_spec(bq, Hd):
    return pl.BlockSpec((None, None, bq, Hd), lambda b, h, i, j: (b, h, i, 0))


def _kv_spec(bk, Hd, G=1):
    # GQA: query head h reads kv head h // G — the index map IS the repeat,
    # so the group's shared kv block is DMA'd once per program with no
    # H/KV-times-larger HBM copy (replaces the jnp.repeat the dispatch
    # used to do; reference analogue: softmax_context's kv-head indexing in
    # csrc/transformer/inference/csrc/pt_binding.cpp)
    return pl.BlockSpec((None, None, bk, Hd), lambda b, h, i, j: (b, h // G, j, 0))


def _band_kv_spec(bk, Hd, G, n):
    # a plain band n blocks wide below the diagonal: a step outside it names
    # the band's nearest block, which the pipeline then has already (a
    # skipped block is not copied in)
    return pl.BlockSpec(
        (None, None, bk, Hd),
        lambda b, h, i, j: (b, h // G, jnp.clip(j, jnp.maximum(i - n, 0), i), 0))


def _row_spec(bq):
    # rows ride as [B, H, 1, Sp] so the trailing block dims (1, bq) tile
    return pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i))


def _mask_spec(bk):
    # mask rides as [B, 1, Sp]
    return pl.BlockSpec((None, 1, bk), lambda b, h, i, j: (b, 0, j))


def _slope_spec():
    # slopes ride as [H, 8, 128] (value broadcast) so each head's block
    # meets the (8, 128) tile minimum; kernels read slope_ref[0, 0]
    return pl.BlockSpec((None, 8, 128), lambda b, h, i, j: (h, 0, 0))


def _tri_spec(bq, bk):
    # the (bq, bk) diagonal-block causal bias, same block for every program
    return pl.BlockSpec((bq, bk), lambda b, h, i, j: (0, 0))


def _layout_spec():
    # block layout rides as [H, nq*8, nk*128] f32 (each (h,i,j) entry
    # broadcast over an (8,128) tile); kernels read layout_ref[0, 0]
    return pl.BlockSpec((None, 8, 128), lambda b, h, i, j: (h, i, j))


@functools.lru_cache(maxsize=32)
def _build(causal: bool, scale: float, bq: int, bk: int, seq_len: int, interpret: bool,
           has_layout: bool = False, plain: bool = False, kv_group: int = 1,
           stair: int = 1, window: int = 0, chunk: int = 0):
    """Build the custom-VJP flash function for one static configuration.

    Operates on padded [B, H, Sp, Hd] q / [B, KV, Sp, Hd] k,v
    (KV = H // kv_group; GQA is native — query head h reads kv head
    h // kv_group via the BlockSpec index map), mask [B, Sp] additive f32,
    slopes [H, 1] f32 (zeros ⇒ no alibi). ``plain`` is the no-mask/no-alibi/
    no-padding fast path (tri = precomputed diagonal-block causal bias).
    ``window`` > 0: the forward is the band ``i - window < j <= i`` (a plain
    one reads its lower edge's bias off the diagonal's: the caller sees to
    ``bq == bk`` and a band of whole blocks); its backward raises.
    ``chunk`` (``_diag_chunk``) > 0: the backward kernels walk a diagonal
    block in chunks of that many query rows, and a plain call hands them
    the (chunk, chunk) bias of a chunk's own keys in tri's place.
    """

    G = kv_group
    maybe_tri = [_tri_spec(bq, bk)] if plain else []
    maybe_layout = [_layout_spec()] if has_layout else []
    statics = dict(scale=scale, causal=causal, seq_len=seq_len, bq=bq, bk=bk,
                   plain=plain, has_layout=has_layout, stair=stair)

    def fwd_call(q, k, v, mask, slopes, *extra):
        B, H, Sp, Hd = q.shape
        nq, nk = Sp // bq, Sp // bk
        kv_spec = _kv_spec(bk, Hd, G)
        fwd_statics = statics
        if window:
            fwd_statics = {**statics, "window": window}
            if plain:
                kv_spec = _band_kv_spec(bk, Hd, G, window // bk)
        kernel = functools.partial(_fwd_kernel, **fwd_statics)
        o, lse = pl.pallas_call(
            kernel,
            name="flash_fwd_band" if window else "flash_fwd",
            grid=(B, H, nq, nk),
            in_specs=[_q_spec(bq, Hd), kv_spec, kv_spec,
                      _mask_spec(bk), _slope_spec()] + maybe_tri + maybe_layout,
            out_specs=[_q_spec(bq, Hd), _row_spec(bq)],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Sp, Hd), q.dtype),
                jax.ShapeDtypeStruct((B, H, 1, Sp), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, Hd), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, mask, slopes, *extra)
        # named so remat policies can save the attention residuals and skip
        # re-running the forward kernel inside the backward pass
        return _named(o, lse)

    @jax.custom_vjp
    def flash(q, k, v, mask, slopes, *extra):
        return fwd_call(q, k, v, mask, slopes, *extra)[0]

    def flash_fwd(q, k, v, mask, slopes, *extra):
        o, lse = fwd_call(q, k, v, mask, slopes, *extra)
        return o, (q, k, v, mask, slopes, extra, o, lse)

    def bwd_impl(res, g, glse):
        """Shared backward: ``glse`` (cotangent of the log2-domain lse
        [B, H, 1, Sp], or None) folds into delta — d s_k gains
        p_k * d lse_nat and lse2 = log2(e) * lse_nat, so
        delta' = delta - log2(e) * glse reuses the dq/dkv kernels unchanged."""
        if window:
            raise NotImplementedError(
                "flash_attention(window=...) has no backward: the dq and "
                "dk/dv kernels do not know the band")
        q, k, v, mask, slopes, extra, o, lse = res
        B, H, Sp, Hd = q.shape
        nq, nk = Sp // bq, Sp // bk
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]
        if glse is not None:
            delta = delta - _LOG2E * glse.astype(jnp.float32)
        record("flash_bwd_diag", "chunks" if chunk else "whole", f"bq={bq} c={chunk}")
        bwd_statics = {**statics, "chunk": chunk}
        operands = (q, k, v, g, lse, delta, mask, slopes)
        dq_extra = dkv_extra = extra        # the layout, or nothing
        if plain:
            # extra is (tri,). A walk's bias is that of a pair ON the
            # diagonal: the block's own top-left corner (a staircase's step
            # divides the chunk). dk/dv take their scores (keys, rows): the
            # bias transposed
            bias = extra[0][:chunk, :chunk] if chunk else extra[0]
            dq_extra, dkv_extra = (bias,), (bias.T,)
        key_of, query_of = _skipped_block_maps(causal, bq, bk)
        dq_kv_spec = pl.BlockSpec((None, None, bk, Hd),
                                  lambda b, h, i, j: (b, h // G, key_of(i, j), 0))
        dq_mask_spec = pl.BlockSpec((None, 1, bk),
                                    lambda b, h, i, j: (b, 0, key_of(i, j)))

        dq_kernel = functools.partial(_dq_kernel, **bwd_statics)
        dq = pl.pallas_call(
            dq_kernel,
            name="flash_dq",
            grid=(B, H, nq, nk),
            in_specs=[_q_spec(bq, Hd), dq_kv_spec, dq_kv_spec,
                      _q_spec(bq, Hd), _row_spec(bq), _row_spec(bq),
                      dq_mask_spec, _slope_spec()]
            + ([_tri_spec(*dq_extra[0].shape)] if plain else []) + maybe_layout,
            out_specs=_q_spec(bq, Hd),
            out_shape=jax.ShapeDtypeStruct((B, H, Sp, Hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, Hd), jnp.float32)],
            interpret=interpret,
        )(*operands, *dq_extra)

        # grid (B, KV, nk, G, nq): q blocks innermost, then the group's query
        # heads — one dk/dv block accumulates across both in scratch
        KV = H // G
        kq_spec = pl.BlockSpec(
            (None, None, bq, Hd),
            lambda b, kv, j, gg, i: (b, kv * G + gg, query_of(i, j), 0))
        kk_spec = pl.BlockSpec((None, None, bk, Hd),
                               lambda b, kv, j, gg, i: (b, kv, j, 0))
        krow_spec = pl.BlockSpec(
            (None, None, 1, bq),
            lambda b, kv, j, gg, i: (b, kv * G + gg, 0, query_of(i, j)))
        kmask_spec = pl.BlockSpec((None, 1, bk), lambda b, kv, j, gg, i: (b, 0, j))
        kslope_spec = pl.BlockSpec((None, 8, 128),
                                   lambda b, kv, j, gg, i: (kv * G + gg, 0, 0))
        kmaybe_tri = ([pl.BlockSpec(dkv_extra[0].shape, lambda b, kv, j, gg, i: (0, 0))]
                      if plain else [])
        kmaybe_layout = ([pl.BlockSpec((None, 8, 128),
                                       lambda b, kv, j, gg, i: (kv * G + gg, i, j))]
                         if has_layout else [])

        dkv_kernel = functools.partial(_dkv_kernel, **bwd_statics)
        dk, dv = pl.pallas_call(
            dkv_kernel,
            name="flash_dkv",
            grid=(B, KV, nk, G, nq),
            in_specs=[kq_spec, kk_spec, kk_spec, kq_spec, krow_spec, krow_spec,
                      kmask_spec, kslope_spec] + kmaybe_tri + kmaybe_layout,
            out_specs=[kk_spec, kk_spec],
            out_shape=[
                jax.ShapeDtypeStruct((B, KV, Sp, Hd), q.dtype),
                jax.ShapeDtypeStruct((B, KV, Sp, Hd), q.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, Hd), jnp.float32),
                pltpu.VMEM((bk, Hd), jnp.float32),
            ],
            interpret=interpret,
        )(*operands, *dkv_extra)

        return (dq, dk, dv, jnp.zeros_like(mask), jnp.zeros_like(slopes),
                *(jnp.zeros_like(l) for l in extra))

    def flash_bwd(res, g):
        return bwd_impl(res, g, None)

    flash.defvjp(flash_fwd, flash_bwd)

    # (o, lse) variant for callers that combine partial attentions across
    # blocks (ring attention): lse is the raw log2-domain [B, H, 1, Sp]
    # kernel output; its cotangent rides the same backward kernels
    @jax.custom_vjp
    def flash_lse(q, k, v, mask, slopes, *extra):
        return fwd_call(q, k, v, mask, slopes, *extra)

    def flash_lse_fwd(q, k, v, mask, slopes, *extra):
        o, lse = fwd_call(q, k, v, mask, slopes, *extra)
        return (o, lse), (q, k, v, mask, slopes, extra, o, lse)

    def flash_lse_bwd(res, cot):
        g, glse = cot
        return bwd_impl(res, g, glse)

    flash_lse.defvjp(flash_lse_fwd, flash_lse_bwd)
    return flash, flash_lse


def flash_attention(q, k, v, mask_bias=None, causal: bool = True, alibi_slopes=None,
                    scale: Optional[float] = None, block_q: Optional[int] = None,
                    block_k: Optional[int] = None, block_layout=None,
                    interpret: Optional[bool] = None, return_lse: bool = False,
                    causal_block: int = 1, window: int = 0):
    """Flash attention on [B, S, H, Hd] q/k/v (same contract as
    :func:`deepspeed_tpu.ops.attention.mha_attention`; mask_bias is the
    additive key-side [B, S] bias). Pads S up to the block size internally.

    ``block_layout``: optional [H, nb, nb] 0/1 block-sparsity layout (from
    :mod:`deepspeed_tpu.ops.sparse_attention`); the kernel block size then
    follows the layout's block size S/nb, and zero blocks are skipped in
    forward AND backward — true block-sparse flash attention.

    GQA is native: k/v may carry KV = H / group kv heads ([B, S, KV, Hd]);
    query head h attends kv head ``h // (H // KV)`` (``jnp.repeat`` order)
    via BlockSpec index maps — no repeated kv copy in HBM or VMEM, and
    dk/dv come back at [B, S, KV, Hd] (summed over the group in-kernel).

    ``return_lse=True`` returns ``(out, lse)`` with lse the **log2-domain**
    logsumexp [B, H, S] (fully-masked rows carry +1e30); both outputs are
    differentiable — ring attention combines partial blocks through it.
    Uses the general kernel (no packed-heads fast path).

    ``causal_block`` > 1 (with ``causal``): the causal triangle becomes a
    staircase of that step. Position ``i`` sees position ``j`` iff
    ``j // causal_block <= i // causal_block``: all of its own group of
    ``causal_block`` positions, both directions, and every earlier one (a
    block-diffusion model's prefill). The kernel blocks are whole groups
    (multiples of 8: a step of 2, 4 or 8), so which blocks are skipped does
    not change and the diagonal block's bias is the one thing that does,
    in the forward and in both backward kernels alike.

    ``window`` > 0 (with ``causal``, FORWARD ONLY: the backward raises):
    position ``i`` sees ``i - window < j <= i``, its own key and the
    ``window - 1`` before it. Key blocks wholly below the band are skipped
    like those above the diagonal, and where the band is whole blocks wide
    they are not copied in either; the blocks its lower edge crosses take
    the complement of the diagonal's precomputed bias.
    """
    B, S, H, Hd = q.shape
    if window and (not causal or causal_block > 1 or block_layout is not None
                   or window < 0):
        raise ValueError(f"window={window} needs causal=True, causal_block 1 "
                         "and no block_layout")
    if causal_block > 1 and (not causal or 8 % causal_block
                             or block_layout is not None):
        raise ValueError(
            f"causal_block={causal_block} needs causal=True, a step that "
            "divides the kernel's 8-row tiles (2, 4 or 8) and no block_layout")
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    kv_group = H // KV
    scale = float(scale if scale is not None else Hd**-0.5)
    interpret = resolve_interpret("flash_attention", interpret)
    # default blocks: one program per (b, h) when the whole sequence fits
    # (fewest program launches — measured fastest at S ≤ 1024); for longer
    # sequences 1024² blocks: chip-measured 8.7% faster than 512² at S=2048
    # on the GQA bench shape (fewer launches beats the finer causal
    # block-skip), while the f32 logits tile (4 MB) still fits VMEM at any
    # S — EXCEPT when 1024 would pad the sequence more than 512 does
    # (e.g. S=1536/2560), where the extra causal-legal padded rows cost
    # more than the launch savings
    if block_q is None or block_k is None:
        _s8 = -(-max(8, S) // 8) * 8
        if _s8 <= 1024:
            _default = _s8            # whole sequence, 8-aligned, one block
        else:
            _default = 1024 if (-(-S // 1024)) * 1024 <= (-(-S // 512)) * 512 \
                else 512
        block_q = block_q or _default
        block_k = block_k or _default

    if block_layout is not None:
        nb = block_layout.shape[-1]
        if S % nb != 0:
            raise ValueError(f"seq len {S} not divisible by layout blocks {nb}")
        lb = S // nb
        if lb < 8 or lb % 8 != 0:
            raise ValueError(
                f"layout block size {lb} (= S/{nb}) must be a multiple of 8 for "
                f"TPU tiling; use a coarser SparsityConfig block")
        block_q = block_k = lb

    # block sizes: multiples of 8 (TPU sublane tiling) — unaligned S gets a
    # single rounded-up block absorbed by the padding below
    s8 = -(-max(8, S) // 8) * 8
    bq = min(block_q, s8)
    bk = min(block_k, s8)
    if block_layout is None:
        # when the sequence spans multiple blocks, the (1, bq)/(1, bk) row
        # and mask blocks tile the lane dim and must be 128-aligned (the
        # layout path instead requires bq == the layout's block size)
        if s8 > bq and bq % 128:
            bq = -(-bq // 128) * 128
        if s8 > bk and bk % 128:
            bk = -(-bk // 128) * 128
    # pad S to a common multiple of both block sizes
    lcm = bq * bk // _gcd(bq, bk)
    Sp = -(-S // lcm) * lcm

    # fast path: no user mask, no alibi, no sparsity layout, no padding —
    # masking reduces to one precomputed triangular bias on diagonal blocks
    plain = (mask_bias is None and alibi_slopes is None and block_layout is None
             and Sp == S and (not causal or bq == bk))
    if window:  # dslint: disable=DS004 (a static Python int)
        # a band that is not whole blocks wide takes the computed bias
        plain = plain and window % bk == 0

    # packed-heads fastest path: small head_dim packs P heads into one full
    # 128-lane tile and q/k/v stay in their natural [B, S, H*Hd] layout —
    # no transposes, no lane padding, P× fewer programs. MHA only: GQA's
    # shared kv heads break the per-head lane-group pairing, and GQA models
    # are Hd=128-class anyway (general kernel, zero lane padding)
    if (plain and kv_group == 1 and not return_lse and Hd < 128
            and 128 % Hd == 0 and H % (128 // Hd) == 0 and not window):
        P128 = 128 // Hd
        fn = _build_packed(causal, scale, bq, bk, interpret, P128, Hd,
                           _diag_chunk(causal, bq, bk))
        tri = _make_tri(bq, bk, causal_block)
        out = fn(q.reshape(B, S, H * Hd), k.reshape(B, S, H * Hd),
                 v.reshape(B, S, H * Hd), tri)
        return out.reshape(B, S, H, Hd)

    def pad_s(x, axis):
        if Sp == S:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, Sp - S)
        return jnp.pad(x, widths)

    qt = pad_s(jnp.transpose(q, (0, 2, 1, 3)), 2)
    kt = pad_s(jnp.transpose(k, (0, 2, 1, 3)), 2)
    vt = pad_s(jnp.transpose(v, (0, 2, 1, 3)), 2)

    mask = (jnp.zeros((B, 1, Sp), jnp.float32) if mask_bias is None
            else pad_s(mask_bias.astype(jnp.float32), 1)[:, None, :])
    slopes = (jnp.zeros((H,), jnp.float32) if alibi_slopes is None
              else jnp.asarray(alibi_slopes, jnp.float32).reshape(H))
    slopes = jnp.broadcast_to(slopes[:, None, None], (H, 8, 128))

    extra = ()
    if plain:
        extra = (_make_tri(bq, bk, causal_block),)
    if block_layout is not None:
        nq, nk = Sp // bq, Sp // bk
        layout = jnp.asarray(block_layout, jnp.float32)
        if layout.ndim == 2:
            layout = jnp.broadcast_to(layout[None], (H,) + layout.shape)
        # pad blocks (attend nowhere / never attended)
        layout = jnp.pad(layout, ((0, 0), (0, nq - layout.shape[1]), (0, nk - layout.shape[2])))
        # each (h,i,j) entry broadcast over an (8,128) tile for BlockSpec tiling
        layout = jnp.repeat(jnp.repeat(layout, 8, axis=1), 128, axis=2)
        extra = extra + (layout,)

    fn, fn_lse = _build(causal, scale, bq, bk, S, interpret, block_layout is not None,
                        plain, kv_group, causal_block, window,
                        _diag_chunk(causal, bq, bk))
    if return_lse:
        out, lse = fn_lse(qt, kt, vt, mask, slopes, *extra)
        return (jnp.transpose(out[:, :, :S, :], (0, 2, 1, 3)),
                lse[:, :, 0, :S])
    out = fn(qt, kt, vt, mask, slopes, *extra)
    return jnp.transpose(out[:, :, :S, :], (0, 2, 1, 3))


def _gcd(a: int, b: int) -> int:
    while b:
        a, b = b, a % b
    return a
