"""Pallas Mamba-2 decode update: the one-token state-space step on the state
pool where it lives.

A Mamba-2 layer keeps a float32 state ``S [P, N]`` a head and request in a
SLOT of a pool beside the KV pools. A decode step is, for every live row
and head,

    S = exp(dt A) S + (dt x) B^T        y = S C

(``ssd_recurrent_step`` of ``models/state_mixers.py`` without its ``D x``,
which the caller adds; that function stays what the tests compare against).
The arithmetic is ~5 operations an element; the step is the state's HBM
traffic, so the kernel moves each live row's state ONCE each way, and lays
the state out so that the arithmetic hides under the copies:

* the pool is ``[periods * slots, N, H * P]``: a row's state with ``N`` on
  SUBLANES and the ``H * P`` channels ``(h, p)`` on LANES (dense ``(8, 128)``
  tiles, 2 MB a row at the published sizes; ``models/transformer.py``
  ``_ssd_to_pool``). The update is then ``S[n, :] = dec * S[n, :] + B[n] *
  dtx`` with ``dec`` (a head's decay over its P channels) and ``dtx = dt x``
  lane rows ``[1, H * P]``, and ``y = sum_n S[n, :] C[n]`` a sum over
  sublanes: vreg adds and one 8 -> 1 fold a lane tile, ``y`` stored
  lane-dense. ``B`` and ``C`` are turned to columns and spread along the
  lanes ONCE a row; all ``H * P / 128`` lane tiles reuse them. (With ``N`` on
  lanes, as the pool lay until PR 44, each of a row's 512 vregs took a
  cross-lane reduction, a lane broadcast and a share of a one-lane store:
  the arithmetic ran in the open, PERF.md section 6, PR 44.);
* grid ``(rows,)``. The pool enters whole, where it lies in HBM
  (``pl.ANY``), and is the kernel's input AND its output
  (``input_output_aliases``): no second buffer exists. Rows are addressed
  ``row -> pool row`` through scalar prefetch, the live rows FIRST, in
  PHASES of 16 MB of state, one direction at a time: the schedule is
  ``state_phases.py``'s, which the KDA decode kernel runs too;
* an inactive row (pool row ``base``: slot 0, the dummy) issues no copy and
  its ``y`` is zero: the dummy and every slot no live row holds are not
  touched. Distinct live rows hold distinct slots, so no two copies meet;
* float32 throughout, on the VPU: products and sums of float32 values, no
  MXU pass, nothing rounded that ``ssd_recurrent_step`` does not round (the
  sum over ``N`` runs in another order).

Shapes outside the envelope (``N % 8``, ``H * P % 128``, a state that is not
float32) return None and the caller takes its plain-XLA form. Interpret mode
on CPU: the unit tier pins the kernel against ``ssd_recurrent_step``; its
times by live rows are ``benchmarks/mamba2_decode_bench.py``'s.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dispatch import resolve_interpret
from deepspeed_tpu.ops.pallas import state_phases
from deepspeed_tpu.utils.logging import warn_once


_LANES = 128


def mamba2_envelope_ok(HP: int, N: int) -> bool:
    """Whether a row's ``[N, H * P]`` float32 state tiles: whole sublanes of
    ``N``, whole lanes of the ``H * P`` channels."""
    return N % 8 == 0 and HP % _LANES == 0


def _kernel(order_ref, rows_ref, nlive_ref, vec_ref, bc_ref, pool_in, y_ref,
            pool_out, buf, rsem, wsem):
    i = pl.program_id(0)
    n_live = nlive_ref[0]
    N, HP = buf.shape[2:]

    def update(ph, at):
        # B and C with N on sublanes, spread along the lanes ONCE a row:
        # every lane tile of the state takes the same two [N, 128] factors
        cols = bc_ref[0].T                                 # [N, 8]
        b = jnp.broadcast_to(cols[:, 0:1], (N, _LANES))
        c = jnp.broadcast_to(cols[:, 1:2], (N, _LANES))
        row = order_ref[i]
        group = pl.ds(pl.multiple_of(row // 8 * 8, 8), 8)
        mine = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0) == row % 8
        for t in range(HP // _LANES):
            at_t = slice(t * _LANES, (t + 1) * _LANES)
            dec, dtx = vec_ref[0, 0:1, at_t], vec_ref[0, 1:2, at_t]   # [1, 128]
            s = buf[ph % 2, at, :, at_t] * dec + b * dtx
            buf[ph % 2, at, :, at_t] = s
            # the row's y into its sublane of the eight rows it lies among
            y8 = y_ref[group, at_t]
            y_ref[group, at_t] = jnp.where(
                mine, jnp.sum(s * c, axis=0, keepdims=True), y8)

    # y stays in VMEM for the whole call, every row's, and goes out once,
    # dense [B, H * P]: an idle row's is the zero it starts as
    @pl.when(i == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    state_phases.in_phases(i, n_live, order_ref, rows_ref, pool_in, pool_out,
                           buf, rsem, wsem, update)


@functools.partial(jax.jit, static_argnames=("R", "interpret"))
def _call(state, order, rows, n_live, vec, bc, *, R, interpret):
    B, _, HP = vec.shape
    N = bc.shape[-1]
    B8 = -(-B // 8) * 8                  # y in whole tiles of eight rows
    by_row = state_phases.by_live_row
    y, state = pl.pallas_call(
        _kernel,
        name="mamba2_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 2, HP), by_row),
                pl.BlockSpec((1, 8, N), by_row),
                pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
            ],
            out_specs=[
                pl.BlockSpec((B8, HP), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=state_phases.phase_scratch(R, (N, HP)),
        ),
        # operand 5 (after the three prefetched scalars): the pool is output 1
        input_output_aliases={5: 1},
        # rows in order: a row's step starts and lands its phase's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * R * N * HP * 4 + (8 << 20)),
        out_shape=[jax.ShapeDtypeStruct((B8, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        interpret=interpret,
    )(order, rows, n_live, vec, bc, state)
    return y[:B], state


def mamba2_decode_update(state, x, dt, A, Bm, Cm, slots, base, *,
                         interpret: Optional[bool] = None):
    """One token of the Mamba-2 recurrence for every LIVE row, in place.

    ``state`` ``[pool_rows, N, H * P]`` float32, the pool of one position of
    the period (every period's slots in one leading axis; a row's state
    ``S[n, h * P + p]``); ``x`` ``[B, H, P]``, ``dt`` ``[B, H]``, ``Bm``,
    ``Cm`` ``[B, N]`` float32, the rows' vectors BY ROW, ``A`` ``[H]``;
    ``slots`` ``[B]`` int32 each row's state slot (0, the dummy, for an
    inactive row) and ``base`` the layer's first pool row, so row ``b``
    lives at ``state[base + slots[b]]``. Live rows hold distinct slots.
    Returns ``(y [B, H, P] = S C of the new state, the pool updated)``: the
    pool is the call's input and output in one buffer (a program that
    donates it holds no copy of it), an inactive row's ``y`` is zero and no
    pool row but the live rows' is written. Returns None where the shape is
    outside the kernel's envelope (``mamba2_envelope_ok``).
    """
    B, H, P = x.shape
    N = Bm.shape[-1]
    if not mamba2_envelope_ok(H * P, N) or state.dtype != jnp.float32:
        warn_once(f"mamba2_decode_update: a {state.dtype} state of H*P={H * P} "
                  f"N={N} is outside the kernel envelope (float32, N % 8 == "
                  "0, H*P % 128 == 0); the caller takes its plain-XLA form")
        return None
    interpret = resolve_interpret("mamba2_decode_update", interpret)
    f32 = jnp.float32
    order, rows, n_live = state_phases.live_rows(slots, base, B)
    # lane rows [H * P], a head's dt and A over its P channels: the decay
    # beside dt x, and nothing of the step is laid out [H, P] (P = 64 is
    # half a lane tile: every such array costs a relayout each way)
    dtl = jnp.repeat(dt, P, axis=-1)
    vec = jnp.stack([jnp.exp(dtl * jnp.repeat(A, P)),
                     dtl * x.reshape(B, H * P)], axis=1)
    # B and C in a tile of eight sublanes, which the kernel turns
    bc = jnp.pad(jnp.stack([Bm, Cm], axis=1), ((0, 0), (0, 6), (0, 0)))
    y, state = _call(state, order, rows, n_live, vec.astype(f32),
                     bc.astype(f32),
                     R=state_phases.phase_rows(B, N * H * P * 4),
                     interpret=bool(interpret))
    return y.reshape(B, H, P), state
