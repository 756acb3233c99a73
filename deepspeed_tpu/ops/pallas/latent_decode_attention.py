"""Pallas paged decode attention over a LATENT cache (multi-head latent
attention with the key/value expansion absorbed into the query and the
output).

The sibling of :mod:`paged_decode_attention` for a pool that has no k/v pair
and no head axis: ``cp [num_blocks, block_size, row]``, a token's row the
normed latent (``R`` values) followed by the roped key part all heads share
(``Dr``) and zeros to whole lane tiles (``row`` = 640 for 512 + 64: the
device tiles memory 128 lanes wide, so the 576 values take 640 lanes either
way). Every one of the H query heads reads the SAME row, as its key
(all ``row`` lanes) and as its value (the first ``R``), so a live block
is copied ONCE and serves both products: through the k-pool / v-pool kernel
it would be copied twice. A row of the step is then ``H`` query rows against
one "kv head" of ``R + Dr`` lanes: scores ``q [H, R + Dr] . c^T``, numerator
``p [H, keys] @ c[:, :R]``, the MXU's natural shapes at H = 64.

The walk is the paged kernel's (its docstring has the measurements behind
it): grid ``(rows,)`` in order, the pool whole in HBM, table and positions
scalar-prefetched, a row's LIVE blocks copied in groups of G by
``make_async_copy`` into two slots, the next group (or the next row's
first) in flight under this group's arithmetic, a group's live blocks walked
in steps of the largest power of two that fits. The probabilities meet the
bf16 rows as bf16 (float32 sums), as in the flash kernels; m, l and the
numerator are float32; float32 pools take float32 products in full.

Interpret mode on the CPU is held to the plain gather + einsum form by
``tests/unit/ops/test_latent_decode_attention.py``; the kernel's time beside
its copies' is ``benchmarks/latent_decode_bench.py``'s.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dispatch import resolve_interpret
from deepspeed_tpu.ops.pallas.paged_decode_attention import (
    _STREAM_VMEM_BYTES, _step_blocks)
from deepspeed_tpu.utils.logging import warn_once

_NEG = -1e30


def _group_blocks(bs: int, row: int, itemsize: int, n_blocks: int) -> int:
    """Blocks a loop iteration copies together: what the stream buffers hold
    (ONE pool, two slots), a power of two, never more than a table is wide."""
    fit = _STREAM_VMEM_BYTES // (2 * bs * row * itemsize)
    fit = max(1, min(fit, n_blocks))
    return 1 << (fit.bit_length() - 1)


def _kernel(bt_ref, pos_ref, q_ref, cp_hbm, o_ref, cbuf, sem, acc_ref,
            slot_ref, *, bs, G, steps, latent):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    n_blocks = bt_ref.shape[1]
    rows = q_ref.shape[1]
    bf16 = cbuf.dtype == jnp.bfloat16
    mxu = jnp.bfloat16 if bf16 else jnp.float32
    exact = None if bf16 else jax.lax.Precision.HIGHEST

    def live_blocks(row):
        return jnp.minimum(pos_ref[row] // bs + 1, n_blocks)

    def copies(row, j, slot, wait=False):
        """Start, or wait for, the copies of group ``j`` of ``row``: one a
        LIVE block. A dead table entry is never read."""
        live = live_blocks(row)
        for i in range(G):
            @pl.when(j * G + i < live)
            def _():
                cp = pltpu.make_async_copy(
                    cp_hbm.at[bt_ref[row, j * G + i]], cbuf.at[slot, i],
                    sem.at[slot])
                cp.wait() if wait else cp.start()

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        copies(0, 0, 0)

    pos = pos_ref[b]
    live = live_blocks(b)
    n_groups = pl.cdiv(live, G)
    slot0 = slot_ref[0]
    q = q_ref[0].astype(mxu)                                   # [H, R + Dr]
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(slot, blk, i, n, carry):
        """One step of the running softmax over ``n`` blocks of the row from
        block ``blk`` on, the ``i``-th and following of the group in
        ``slot``."""
        m_prev, l_prev = carry
        c = cbuf[slot, pl.ds(i, n)].reshape(n * bs, -1).astype(mxu)
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=exact)               # [H, n*bs]
        kpos = blk * bs + jax.lax.broadcasted_iota(jnp.int32, (1, n * bs), 1)
        s = jnp.where(kpos <= pos, s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(mxu), c[:, :latent],
            preferred_element_type=jnp.float32, precision=exact)
        return m_new, l_new

    def body(j, carry):
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < n_groups)
        def _():
            copies(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_and(j + 1 == n_groups, b + 1 < nb))
        def _():
            copies(b + 1, 0, 1 - slot)

        copies(b, j, slot, wait=True)
        if G == 1:  # dslint: disable=DS004 (G is a static Python int)
            return step(slot, j, 0, 1, carry)
        done, left = 0, jnp.minimum(G, live - j * G)
        for n in steps:
            carry = jax.lax.fori_loop(
                0, left // n,
                lambda t, c, n=n, done=done: step(
                    slot, j * G + done + t * n, done + t * n, n, c), carry)
            done, left = done + left // n * n, left % n
        return carry

    _, l = jax.lax.fori_loop(
        0, n_groups, body,
        (jnp.full((rows, 1), _NEG, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32)))
    slot_ref[0] = (slot0 + n_groups) % 2
    o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("latent", "G", "interpret"))
def _latent_call(q, cp, bt, pos, *, latent, G, interpret):
    B, H, row = q.shape
    bs = cp.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, G=G, steps=_step_blocks(G),
                          latent=latent),
        name="latent_paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, row), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, latent), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, G, bs, row), cp.dtype),    # live blocks, 2 slots
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, latent), jnp.float32),     # running numerator
                pltpu.SMEM((1,), jnp.int32),              # slot of next group
            ],
        ),
        # rows in order: a row starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        interpret=interpret,
    )(bt, pos, q, cp)


def latent_envelope_ok(H: int, latent: int, row: int, bs: int) -> bool:
    """Whether (heads, latent width, row width, block size) sits inside the
    kernel's envelope: whole sublane tiles of heads, the row and its value
    part whole lane tiles (the device tiles memory 128 lanes wide: a copy
    is whole tiles)."""
    return (H % 8 == 0 and latent % 128 == 0 and row % 128 == 0
            and row > latent and bs % 128 == 0)


def latent_decode_attention(q, cp, block_tables, pos, *, latent: int,
                            scale: float,
                            interpret: Optional[bool] = None):
    """Attention of each request's new token against a paged LATENT cache.

    q ``[B, H, row]``: a head's query with the key expansion absorbed
    (``q_nope Wk`` over the latent's ``R`` lanes) followed by its roped part
    and zeros to the pool's row;
    cp ``[num_blocks, block_size, row]`` the pool, each request's new row
    already written; ``block_tables`` ``[B, max_blocks]``, ``pos`` ``[B]`` as
    :func:`paged_decode_attention` takes them (request ``b`` attends logical
    positions ``<= pos[b]``; dead table entries are never read);
    ``latent`` = R; ``scale`` multiplies the scores. Returns ``[B, H, R]``:
    each head's probabilities over the latents, for the value expansion to
    take. None when the shape is outside :func:`latent_envelope_ok` (the
    caller takes its gather + einsum form)."""
    B, H, row = q.shape
    bs = cp.shape[1]
    if not latent_envelope_ok(H, latent, row, bs):
        warn_once(f"latent_decode_attention: heads={H} latent={latent} "
                  f"row={row} block_size={bs} is outside the kernel envelope "
                  "(heads % 8 == 0, latent % 128 == 0, row % 128 == 0, "
                  "block_size % 128 == 0); the caller takes its gather + "
                  "einsum form")
        return None
    interpret = resolve_interpret("latent_decode_attention", interpret)
    G = _group_blocks(bs, row, cp.dtype.itemsize, block_tables.shape[1])
    return _latent_call(q * scale, cp, jnp.asarray(block_tables, jnp.int32),
                        jnp.asarray(pos, jnp.int32).reshape(B),
                        latent=latent, G=G, interpret=bool(interpret))
