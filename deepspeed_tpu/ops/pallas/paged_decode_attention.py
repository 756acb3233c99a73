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

Design: the kernel's work is (rows) x (a grid step) + (live rows) x (a
small fixed cost) + (live blocks) x (about a block's copy time); the table's
width costs nothing, and the arithmetic follows the work it is given.

* grid ``(num_requests,)``, rows in order. The pools enter whole, where
  they lie in HBM (``pl.ANY``: no BlockSpec moves them); the block table and
  ``pos`` are scalar-prefetched. A row tells the kernel whether it holds a
  request: the FIRST table entry it would read (entry 0; under ``window``
  the entry of its first live block) names a block of its own, or the
  call's ``dummy_block``: ``DUMMY_BLOCK`` (0), which ``BlockAllocator``
  never hands out and the engine leaves in an idle row's zeroed table (the
  convention ``state_phases`` has for slot 0), moved with the table to the
  layer's first block where the pools are stacked over layers (it rides
  the prefetched table as a last row). A row without a request costs its grid
  step and nothing else: no copy started or waited for, no query loaded, no
  step of the softmax, and ZEROS for its output. A live row ``b`` has
  ``pos[b] // block_size + 1`` live blocks, and a loop with that trip count
  walks its table in groups of G blocks. A dead table entry costs no
  iteration and no copy, and what it names is never read (past a live
  row's first entry it may be anything);
* each live block is brought in by the kernel's own ``make_async_copy``
  (pool block ``bt[b, j]`` -> a VMEM slot), double-buffered: the next
  group's copies are in flight under this group's arithmetic, and under a
  row's last group the NEXT ROW's first group where that row holds a
  request (an idle row starts its successor's in its own grid step, the
  call's first step row 0's), so with the live rows first, as the engine
  packs them, only the call's first copy is waited for in the open; a live
  row behind an idle one waits for its first group. G comes from the
  shapes (``_group_blocks``): groups of about 0.75 MB a pool, one block at
  2,048 bf16 lanes, four at 768. A group is what is COPIED together; the
  arithmetic walks a group's live blocks one at a time (a step of the
  running softmax over a block's ``bs`` keys), so a row pays for the blocks
  it holds, not for G. A block is copied WHOLE, a row's newest too (its
  live quarters alone were 2-5% of a call and kept out: PERF.md section 6,
  PR 49);
* a query is ``[B, H, Hd]`` or ``[B, Q, H, Hd]``: Q positions of a row that
  read the SAME keys (a generation block's), in one read of the row's KV.
  A kv head then has ``Q x P`` query rows (P = H / KV), and the products
  take one of two forms, chosen from those static shapes alone
  (``_per_kv_head``; recorded at ``ops.dispatch`` site
  ``paged_decode_attention``):

  - ``per_kv_head``, where a kv head's rows fill whole sublane tiles (from
    ``_PER_KV_HEAD_MIN_ROWS`` of them) and its ``Hd`` lanes a whole lane
    tile: scores ``q_g [Q*P, Hd] . k[:, g's lanes]^T`` and the numerator
    ``p_g @ v[:, g's lanes]`` a kv head, accumulated in that head's own
    rows: the arithmetic the scores need and no more;
  - ``block_diagonal``, below that (one position of MHA is ONE query row a
    kv head, a matrix-vector product the MXU does not want): the row's
    query laid block-diagonally once a row (``[Q*H, KV*Hd]``: head ``h``'s
    values at the lanes of its kv head ``h // P``, zeros elsewhere), so
    scores are ``q_bd . k [bs, KV*Hd]^T`` and the numerator accumulates
    ``p @ v [bs, KV*Hd]`` whole; each head's own ``Hd`` lanes (the
    diagonal) are taken once, at the end of the row. The MXU does KV times
    the arithmetic needed, which stays under the copy's time at a decode
    step's few query rows and does not at a block's 128 (PERF.md section
    6, PR 36);
* no precision is given up for that: bf16 operands meet on the MXU's bf16
  path with float32 sums (a bf16 x bf16 product is exact in float32), and
  the float32 probabilities go there as three bf16 terms whose sum is
  exact (``_split3``), stacked as rows of one product. m, l, p and the
  numerator are float32. float32 pools take float32 products in full;
* per-request positions: ``pos[b]`` is the 0-based position of request
  ``b``'s new token, the last of them where there are Q (attends ``kpos <=
  pos[b]``) — requests at different depths decode in the same fused step
  (iteration-level batching);
* ALiBi slopes and an additive key-side ``pad_bias`` over LOGICAL positions
  keep parity with the dense kernel; GQA head ``h`` reads kv head ``h // P``;
* ``window`` (a static W; 0 compiles the kernel above and nothing else): a
  row attends ``pos - W < kpos <= pos`` and its table is a RING, logical
  block ``j`` at entry ``j % width``. Its loop starts at its FIRST LIVE block
  ``max(0, pos - W + 1) // bs``, the first copy and the next row's prefetch
  likewise, so a row costs ``W / bs + 1`` copies at most however long it is;
  the keys of the first block that lie before the window are masked.

Interpret mode on CPU — the unit tier pins parity vs ``decode_attention``
and a float32 reference on randomized block tables; the kernel's times by
state, G and form are ``benchmarks/paged_decode_bench.py``'s.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.inference.block_allocator import DUMMY_BLOCK
from deepspeed_tpu.ops import dispatch
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
# Query rows a kv head (positions x heads of its group) from which the
# products are taken a kv head. Measured on a v5e (paged_decode_bench.py
# --forms, PERF.md section 6, PR 36; ms a call, per_kv_head | block_diagonal
# under the same copies and steps): ``sdar_block`` (32 rows a kv head, 4 kv
# heads) 0.1009 | 0.1392; ``solar_gqa`` (8 rows a kv head, 8 kv heads) 0.803 |
# 0.881: already at ONE sublane tile of rows the kv head's own product wins
# (the numerator is [rows, Hd] a kv head and not [rows, KV*Hd]). Under 8 rows
# a tile is partly empty and a kv head's rows cannot be stacked on tile
# borders: one position of MHA (OPT, OLMoE) or a group of 4 stays whole.
_PER_KV_HEAD_MIN_ROWS = 8


def _group_blocks(bs: int, row: int, itemsize: int, n_blocks: int) -> int:
    """Blocks a loop iteration takes together (G): as many as the stream
    buffers hold, never more than a table is wide."""
    fit = _STREAM_VMEM_BYTES // (4 * bs * row * itemsize)
    return int(max(1, min(fit, n_blocks)))


def _step_blocks(G: int):
    """How many of a group's live blocks a step of the running softmax takes
    at once, largest first: the powers of two up to G, so every count of
    live blocks is walked in full steps with none computed dead. A step is a
    chain (scores, row maximum, ``exp``, values) the next cannot start
    under, ~0.2 us each whatever its size, so the fewer the better:
    ``sdar_block`` 0.1307 ms a call one block a step, 0.1092 in pairs + one,
    0.1009 so (its copies alone take 0.0841); ``narrow_decode`` 0.0842,
    0.0748, 0.0729 (paged_decode_bench.py --steps 1 21, PR 36)."""
    return tuple(1 << e for e in reversed(range(G.bit_length())))


def _per_kv_head(per: int, hd: int) -> bool:
    """Whether the products are taken a kv head (``per`` query rows of a kv
    head against that head's ``hd`` lanes) or once over everything against
    the block-diagonal query. From static shapes only."""
    return per % 8 == 0 and per >= _PER_KV_HEAD_MIN_ROWS and hd % 128 == 0


def _split3(p):
    """float32 ``p`` as three bf16-exact float32 terms whose sum is ``p`` to
    its last bit: what lets a float32 probability meet a bf16 value on the
    MXU's bf16 path without being rounded."""
    hi = p.astype(jnp.bfloat16).astype(jnp.float32)
    r = p - hi
    mid = r.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, r - mid


def _kernel(bt_ref, pos_ref, q_ref, kp_hbm, vp_hbm, *rest, bs, G, steps,
            group, per_head, has_bias, has_alibi, window=0):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    slope_ref = rest.pop(0) if has_alibi else None
    o_ref, kbuf, vbuf, sem, qs_ref, acc_ref, slot_ref = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    n_blocks = bt_ref.shape[1]
    nq, hp, hd = q_ref.shape[1:]
    kv = kbuf.shape[3] // hd
    per = nq * group            # query rows a kv head
    rows = acc_ref.shape[0]
    bf16 = kbuf.dtype == jnp.bfloat16 and q_ref.dtype == jnp.bfloat16
    mxu = jnp.bfloat16 if bf16 else jnp.float32
    # operands of another precision than bf16 meet in float32, in full
    exact = None if bf16 else jax.lax.Precision.HIGHEST

    def first_block(row):
        """The first logical block a row reads: 0 without a window."""
        if not window:
            return 0
        return jnp.maximum(pos_ref[row] - (window - 1), 0) // bs

    def live_blocks(row):
        return jnp.minimum(pos_ref[row] // bs + 1 - first_block(row),
                           n_blocks)

    def alive(row):
        """Whether ``row`` holds a request: the first table entry it would
        read names a block of its own and not the dummy block, which the
        table's last row (no request's) names."""
        return bt_ref[row, first_block(row) % n_blocks] != bt_ref[nb, 0]

    def first_copies(row, slot):
        """Start the first group of ``row`` where there is such a row and it
        holds a request: what the row before it does, under its last group
        or, idle itself, in its own grid step."""
        @pl.when(jnp.logical_and(row < nb, alive(jnp.minimum(row, nb - 1))))
        def _():
            copies(row, 0, slot)

    def copies(row, j, slot, wait=False):
        """Start, or wait for, the copies of group ``j`` of ``row``: one per
        LIVE block and pool. A dead table entry is never read."""
        live, first = live_blocks(row), first_block(row)
        for i in range(G):
            blk = j * G + i

            @pl.when(blk < live)
            def _():
                src = bt_ref[row, (first + blk) % n_blocks] if window \
                    else bt_ref[row, blk]
                for s, (pool, buf) in enumerate(((kp_hbm, kbuf),
                                                 (vp_hbm, vbuf))):
                    cp = pltpu.make_async_copy(
                        pool.at[src], buf.at[slot, i], sem.at[s, slot])
                    cp.wait() if wait else cp.start()

    def place(g, t):
        """Where kv head ``g``'s ``group`` heads at position ``t`` sit in the
        query scratch and the numerator: (rows, lanes). A kv head: its own
        ``per`` rows over ``Hd`` lanes. Block-diagonal: the heads' natural
        rows, at the lanes of their kv head in the pool's row."""
        if per_head:
            r0 = g * per + t * group
            return slice(r0, r0 + group), slice(0, hd)
        r0 = t * hp + g * group
        return slice(r0, r0 + group), slice(g * hd, (g + 1) * hd)

    @pl.when(b == 0)
    def _():
        if not per_head:
            # the zeros off the diagonal are written once a call
            qs_ref[:] = jnp.zeros_like(qs_ref)
        slot_ref[0] = 0
        first_copies(0, 0)

    holds_request = alive(b)

    @pl.when(jnp.logical_not(holds_request))
    def _():
        # no copy of its own, no query, no step
        o_ref[:] = jnp.zeros_like(o_ref)
        first_copies(b + 1, slot_ref[0])

    @pl.when(holds_request)
    def _():
        pos = pos_ref[b]
        live = live_blocks(b)
        first = first_block(b)
        n_groups = pl.cdiv(live, G)
        slot0 = slot_ref[0]
        q32 = q_ref[0].astype(jnp.float32)                     # [Q, Hp, Hd]
        for g in range(kv):
            for t in range(nq):
                r, c = place(g, t)
                qs_ref[r, c] = q32[t, g * group:(g + 1) * group, :]
        qs = qs_ref[:].astype(mxu)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if has_alibi:
            slope = slope_ref[:]                               # [rows, 1]

        # the products' parts, (rows of the scratch, lanes of the pool's
        # row): a kv head each, or ONE over everything against the
        # block-diagonal query
        parts = [(slice(g * per, (g + 1) * per),
                  slice(g * hd, (g + 1) * hd)) for g in range(kv)] \
            if per_head else [(slice(None), slice(None))]

        def scores(k):
            return jnp.concatenate([
                jax.lax.dot_general(qs[r], k[:, c], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=exact)
                for r, c in parts], axis=0)                    # [rows, bs]

        def values(p, v):
            """``p @ v`` a part. Against bf16 values the float32 p goes as
            three bf16 terms stacked as rows: one pass of v through the MXU,
            float32 sums, p exact."""
            terms = _split3(p) if bf16 else (p,)
            out = []
            for r, c in parts:
                pv = jnp.dot(jnp.concatenate([t[r] for t in terms], axis=0)
                             .astype(mxu), v[:, c],
                             preferred_element_type=jnp.float32,
                             precision=exact)
                n = pv.shape[0] // len(terms)
                out.append(functools.reduce(
                    jnp.add,
                    [pv[i * n:(i + 1) * n] for i in range(len(terms))]))
            return jnp.concatenate(out, axis=0)                # [rows, lanes]

        def step(slot, blk, i, n, carry):
            """One step of the running softmax over ``n`` blocks of the row
            from block ``blk`` on, the ``i``-th and following of the group in
            ``slot``: ``n * bs`` keys."""
            m_prev, l_prev = carry
            k = kbuf[slot, pl.ds(i, n)].reshape(n * bs, -1).astype(mxu)
            v = vbuf[slot, pl.ds(i, n)].reshape(n * bs, -1).astype(mxu)
            s = scores(k)                                      # [rows, n*bs]
            # LOGICAL key positions: the table only moved the storage
            kpos = (first + blk) * bs + jax.lax.broadcasted_iota(
                jnp.int32, (1, n * bs), 1)
            if has_alibi:
                s = s + slope * (kpos - pos).astype(jnp.float32)
            if has_bias:
                s = s + jnp.concatenate(
                    [bias_ref[0, pl.ds(blk + t, 1), :] for t in range(n)],
                    axis=1)
            keep = kpos <= pos
            if window:
                keep = jnp.logical_and(keep, kpos > pos - window)
            s = jnp.where(keep, s, _NEG)

            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                             # float32
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + values(p, v)
            return m_new, l_new

        def body(j, carry):
            slot = (slot0 + j) % 2

            # the next group's blocks fly under this group's arithmetic:
            # this row's, or under this row's last the first group of the
            # next row that holds a request
            @pl.when(j + 1 < n_groups)
            def _():
                copies(b, j + 1, 1 - slot)

            @pl.when(j + 1 == n_groups)
            def _():
                first_copies(b + 1, 1 - slot)

            copies(b, j, slot, wait=True)
            if G == 1:  # dslint: disable=DS004 (G is a static Python int)
                return step(slot, j, 0, 1, carry)
            # the group's LIVE blocks only, the most at a time first: the
            # arithmetic follows the row, in as few steps as its blocks allow
            done, left = 0, jnp.minimum(G, live - j * G)
            for n in steps:
                carry = jax.lax.fori_loop(
                    0, left // n,
                    lambda t, c, n=n, done=done: step(
                        slot, j * G + done + t * n, done + t * n, n, c),
                    carry)
                done, left = done + left // n * n, left % n
            return carry

        _, l = jax.lax.fori_loop(
            0, n_groups, body,
            (jnp.full((rows, 1), _NEG, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32)))
        slot_ref[0] = (slot0 + n_groups) % 2

        for g in range(kv):
            for t in range(nq):
                r, c = place(g, t)
                o_ref[0, t, g * group:(g + 1) * group, :] = \
                    (acc_ref[r, c] / l[r]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("group", "G", "steps", "per_head",
                                    "interpret", "window"))
def _paged_call(q, kp, vp, bt, pos, bias, slopes, *, group, G, steps,
                per_head, interpret, window=0):
    """q ``[B, Q, Hp, Hd]`` (pre-scaled, heads padded to a multiple of 8);
    ``bt`` ``[B + 1, n_blocks]``, its last row the block an idle row's table
    names; ``bias`` ``[B, n_blocks, bs]`` or None; ``slopes`` ``[rows, 1]``
    in the kernel's row order or None."""
    B, nq, hp, hd = q.shape
    bs, row = kp.shape[1:]
    # a kv head's rows over its own Hd lanes | every head over the pool's row
    rows, lanes = ((row // hd) * nq * group, hd) if per_head \
        else (nq * hp, row)
    in_specs = [
        pl.BlockSpec((1, nq, hp, hd), lambda b, *_: (b, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),      # the pools stay in HBM: the
        pl.BlockSpec(memory_space=pl.ANY),      # kernel copies live blocks
    ]
    args = [q, kp, vp]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1,) + bias.shape[1:],
                                     lambda b, *_: (b, 0, 0)))
        args.append(bias)
    if slopes is not None:
        in_specs.append(pl.BlockSpec((rows, 1), lambda b, *_: (0, 0)))
        args.append(slopes)
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, G=G, steps=steps, group=group,
                          per_head=per_head, has_bias=bias is not None,
                          has_alibi=slopes is not None, window=window),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nq, hp, hd),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, G, bs, row), kp.dtype),    # k blocks, 2 slots
                pltpu.VMEM((2, G, bs, row), vp.dtype),    # v blocks, 2 slots
                pltpu.SemaphoreType.DMA((2, 2)),          # [pool, slot]
                pltpu.VMEM((rows, lanes), jnp.float32),   # the row's query
                pltpu.VMEM((rows, lanes), jnp.float32),   # running numerator
                pltpu.SMEM((1,), jnp.int32),              # slot of next group
            ],
        ),
        # rows in order: a row starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((B, nq, hp, hd), q.dtype),
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
                           interpret: Optional[bool] = None,
                           window: int = 0, dummy_block=DUMMY_BLOCK):
    """Attention of each request's new positions against a PAGED KV cache.

    q ``[B, H, Hd]`` (one new token per running request, rope applied) or
    ``[B, Q, H, Hd]``: Q positions of a request that read the SAME keys
    (a generation block's: all of ``kpos <= pos[b]``, in ONE read of the
    request's KV);
    kp/vp ``[num_blocks, block_size, KV*Hd]`` — the shared block pools (a
    token's kv heads merged, head ``g`` at lanes ``[g*Hd, (g+1)*Hd)``; KV
    is read off ``kp.shape[2] // Hd``), with each request's new k/v already
    written at its slots;
    ``block_tables`` ``[B, max_blocks]`` int32 pool block ids (logical block
    ``j`` of request ``b`` lives in pool block ``block_tables[b, j]``). The
    FIRST entry a row would read says whether it holds a request:
    ``dummy_block`` there (an int or a traced scalar; the allocator's
    ``DUMMY_BLOCK`` 0, which it never hands out, and under pools stacked over
    layers the layer's first block, where its block 0 lies) makes it an idle
    row, which is copied nothing and returns zeros; a live row's dead tail
    entries may be anything — they are never read;
    ``pos`` ``[B]`` int32 per-request 0-based position of the new token, the
    LAST of them where there are Q (request ``b`` attends logical positions
    ``<= pos[b]``; ALiBi distances are taken from it).
    ``pad_bias`` ``[B, max_blocks * block_size]`` additive f32 bias over
    logical positions. GQA head h reads kv head ``h // (H // KV)``.
    ``window`` W: request ``b`` attends ``pos[b] - W < kpos <= pos[b]`` and
    its table is a ring of at least ``W / block_size + 1`` entries (logical
    block ``j`` at ``block_tables[b, j % max_blocks]``; a table as long as
    the request is one too; the entry that says whether the row lives is
    its first live block's); one query position a request, no ``pad_bias``.
    Returns q's shape. Which form the products took (module docstring) is
    static a shape and recorded: ``ops.dispatch`` site
    ``paged_decode_attention``, ``per_kv_head`` | ``block_diagonal``.

    Returns None when the shape is outside the kernel's envelope (caller
    falls back to a gather + einsum path): block_size not a multiple of
    128, head_dim not lane-aligned, a pool row ``KV*Hd`` that is not a
    multiple of 128 lanes (MQA at Hd 64), or H % KV != 0.
    """
    one = q.ndim == 3
    if one:
        q = q[:, None]
    B, Q, H, Hd = q.shape
    bs, KV = kp.shape[1], kp.shape[2] // Hd
    if window < 0 or window and (
            Q > 1 or pad_bias is not None
            or block_tables.shape[1] < -(-window // bs) + 1):
        raise ValueError(
            f"window={window}: needs one query position a request, no "
            "pad_bias, and a table of at least window / block_size + 1 "
            f"entries (got {block_tables.shape[1]})")
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
    per_head = _per_kv_head(Q * P, Hd)
    dispatch.record("paged_decode_attention",
                    "per_kv_head" if per_head else "block_diagonal",
                    f"Q={Q} P={P} Hd={Hd} G={G}")
    pad_h = -H % 8          # whole float32 sublane tiles of heads
    qs = jnp.pad(q * scale, ((0, 0), (0, 0), (0, pad_h), (0, 0)))
    bias = slopes = None
    if pad_bias is not None:
        # one block's logical positions a row: [B, blocks, bs]
        bias = pad_bias.astype(jnp.float32).reshape(B, n_blocks, bs)
    if alibi_slopes is not None:
        # a slope a query row, in the kernel's order of rows (``place``)
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(H)
        if per_head:
            slopes = jnp.broadcast_to(slopes.reshape(KV, 1, P), (KV, Q, P))
        else:
            slopes = jnp.broadcast_to(jnp.pad(slopes, (0, pad_h)),
                                      (Q, H + pad_h))
        slopes = slopes.reshape(-1, 1)
    # the dummy rides the table as one more row: no operand of its own, and
    # where the caller derives both from a layer's offset, one fusion
    tables = jnp.concatenate([
        jnp.asarray(block_tables, jnp.int32),
        jnp.full((1, n_blocks), dummy_block, jnp.int32)])
    out = _paged_call(qs, kp, vp, tables,
                      jnp.asarray(pos, jnp.int32).reshape(B),
                      bias, slopes, group=P, G=G, steps=_step_blocks(G),
                      per_head=per_head, interpret=bool(interpret),
                      window=window)[:, :, :H]
    return out[:, 0] if one else out
