"""Pallas Mamba-2 decode update: the one-token state-space step on the state
pool where it lives.

A Mamba-2 layer keeps a float32 state ``S [P, N]`` a head and request in a
SLOT of a pool ``[periods * slots, H, P, N]`` beside the KV pools. A decode
step is, for every live row and head,

    S = exp(dt A) S + (dt x) B^T        y = S C

(``ssd_recurrent_step`` of ``models/transformer.py`` without its ``D x``,
which the caller adds; that function stays what the tests compare against).
The arithmetic is ~6 operations an element; the step is the state's HBM
traffic, so the kernel moves each live row's state ONCE each way:

* grid ``(rows,)``, a row's whole state (``[H, P, N]``: 2 MB at the
  published sizes) one block. The pool is the kernel's input AND its output
  (``input_output_aliases``): no second buffer exists. A block is addressed
  ``row -> pool row`` through scalar prefetch, as ``paged_decode_attention``
  addresses blocks through its table, and Pallas's own pipeline brings row
  ``i + 1`` in and takes row ``i - 1`` out while row ``i`` is worked on;
* the live rows come FIRST (``order``, a stable sort by "holds the dummy
  slot"). A step past them names the last live row's block again: the
  pipeline fetches nothing, the body is skipped, and the block goes out once,
  as its row left it. So an inactive row (slot 0, the dummy) moves no state
  and its ``y`` is zero; distinct live rows hold distinct slots, so no block
  is visited twice. With no live row at all every step names the dummy's
  block: the first copies it from the input's buffer to the output's, since
  a body that is skipped writes nothing and the output's buffer goes out
  all the same;
* float32 throughout, on the VPU: a head's ``[P, N]`` tile times its decay (a
  scalar in SMEM) plus the outer product of ``dt x`` (a column: the rows'
  ``[H, P]`` vectors arrive turned, ``P`` on sublanes) and ``B`` (a lane
  row), then ``y`` the lane sum of the new tile times ``C``. Nothing is
  rounded that ``ssd_recurrent_step`` does not round.

Shapes outside the envelope (``P % 8``, ``N % 128``, a state that is not
float32) return None and the caller takes its plain-XLA form. Interpret mode
on CPU: the unit tier pins the kernel against ``ssd_recurrent_step``.
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


def mamba2_envelope_ok(P: int, N: int) -> bool:
    """Whether a head's ``[P, N]`` float32 state tiles: whole sublanes of
    ``P``, whole lanes of ``N``."""
    return P % 8 == 0 and N % 128 == 0


def _kernel(order_ref, rows_ref, nlive_ref, dec_ref, dtx_ref, b_ref, c_ref,
            s_in, y_ref, s_out):
    i = pl.program_id(0)
    H = s_in.shape[1]

    @pl.when(i < nlive_ref[0])
    def _():
        dtx = dtx_ref[0]                                   # [P, H]
        b, c = b_ref[0], c_ref[0]                          # [1, N]
        for h in range(H):
            s = s_in[0, h] * dec_ref[0, 0, h] + dtx[:, h:h + 1] * b
            s_out[0, h] = s
            y_ref[0, :, h:h + 1] = jnp.sum(s * c, axis=-1, keepdims=True)

    @pl.when(i >= nlive_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.logical_and(i == 0, nlive_ref[0] == 0))
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, order, rows, n_live, dec, dtx_t, Bm, Cm, *, interpret):
    B, P, H = dtx_t.shape
    N = Bm.shape[-1]

    def live(i, order, n_live):
        # a step past the live rows names the last live row again
        return order[jnp.minimum(i, jnp.maximum(n_live[0] - 1, 0))]

    def by_row(i, order, rows, n_live):
        return (live(i, order, n_live), 0, 0)

    def by_slot(i, order, rows, n_live):
        return (rows[live(i, order, n_live)], 0, 0, 0)

    block = pl.BlockSpec((1, H, P, N), by_slot)
    y, state = pl.pallas_call(
        _kernel,
        name="mamba2_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 1, H), by_row, memory_space=pltpu.SMEM),
                pl.BlockSpec((1, P, H), by_row),
                pl.BlockSpec((1, 1, N), by_row),
                pl.BlockSpec((1, 1, N), by_row),
                block,
            ],
            out_specs=[
                pl.BlockSpec((1, P, H), lambda i, order, *_: (order[i], 0, 0)),
                block,
            ],
        ),
        # operand 7 (after the three prefetched scalars): the pool is output 1
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * H * P * N * 4 + (8 << 20)),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        interpret=interpret,
    )(order, rows, n_live, dec, dtx_t, Bm, Cm, state)
    return y, state


def mamba2_decode_update(state, x, dt, A, Bm, Cm, slots, base, *,
                         interpret: Optional[bool] = None):
    """One token of the Mamba-2 recurrence for every LIVE row, in place.

    ``state`` ``[pool_rows, H, P, N]`` float32, the pool of one position of
    the period (every period's slots in one leading axis); ``x`` ``[B, H,
    P]``, ``dt`` ``[B, H]``, ``Bm``, ``Cm`` ``[B, N]`` float32, the rows'
    vectors BY ROW, ``A`` ``[H]``; ``slots`` ``[B]`` int32 each row's state
    slot (0, the dummy, for an inactive row) and ``base`` the layer's first
    pool row, so row ``b`` lives at ``state[base + slots[b]]``. Live rows
    hold distinct slots. Returns ``(y [B, H, P] = S C of the new state, the
    pool updated)``: the pool is the call's input and output in one buffer
    (a program that donates it holds no copy of it), an inactive row's ``y``
    is zero and no pool row but the live rows' changes. Returns None where
    the shape is outside the kernel's envelope (``mamba2_envelope_ok``).
    """
    B, H, P = x.shape
    N = Bm.shape[-1]
    if not mamba2_envelope_ok(P, N) or state.dtype != jnp.float32:
        warn_once(f"mamba2_decode_update: a {state.dtype} state of P={P} "
                  f"N={N} is outside the kernel envelope (float32, P % 8 == "
                  "0, N % 128 == 0); the caller takes its plain-XLA form")
        return None
    interpret = resolve_interpret("mamba2_decode_update", interpret)
    f32 = jnp.float32
    slots = jnp.asarray(slots, jnp.int32).reshape(B)
    live = slots != 0
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    rows = jnp.asarray(base, jnp.int32) + slots
    dec = jnp.exp(dt * A).astype(f32)[:, None]               # [B, 1, H]
    dtx_t = jnp.swapaxes(dt[:, :, None] * x, 1, 2).astype(f32)   # [B, P, H]
    y, state = _call(state, order, rows, n_live, dec, dtx_t,
                     Bm.astype(f32)[:, None], Cm.astype(f32)[:, None],
                     interpret=bool(interpret))
    return jnp.swapaxes(y, 1, 2), state
