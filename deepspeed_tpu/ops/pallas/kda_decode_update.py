"""Pallas KDA decode update: the one-token gated delta-rule step on the
state pool where it lives.

A linear-attention (KDA) layer keeps a float32 state ``S [dk, dv]`` a head
and request in a SLOT of a pool ``[periods * slots, H, dk, dv]`` beside the
KV pools. A decode step is, for every live row and head,

    S1 = S * exp(g)[:, None]        r = S1^T kh        p = S1^T qh
    u  = beta (v - r)               o = p + (qh . kh) u
    S  = S1 + kh u^T

(``kda_recurrent_step`` of ``models/state_mixers.py``, which stays what the
tests compare against). The arithmetic is ~7 operations an element; the
step is the state's HBM traffic, so the kernel moves each live row's state
ONCE each way:

* grid ``(rows,)``. The pool enters whole, where it lies in HBM
  (``pl.ANY``), and is the kernel's input AND its output
  (``input_output_aliases``): no second buffer exists. Rows are addressed
  ``row -> pool row`` through scalar prefetch, as ``paged_decode_attention``
  addresses blocks through its table;
* the live rows come FIRST, in PHASES of R rows (16 MB of state), one
  direction at a time: the schedule is ``state_phases.py``'s (``in_phases``:
  the kernel's own ``make_async_copy`` a row, two buffers in turn, never a
  read and a write in flight together; the Mamba-2 decode kernel runs the
  same one). Measured (PERF.md section 6, PR 32): the chip writes at 644
  GB/s and reads at 731, a round trip with both directions in flight at
  once runs at 657, and one direction at a time in 16 MB turns at 692;
* an inactive row (pool row ``base``: slot 0, the dummy) issues no copy and
  its ``o`` is zero: the dummy and every slot no live row holds are not
  touched. Distinct live rows hold distinct slots, so no two copies meet;
* float32 throughout, on the VPU: products and sums of float32 values, no
  MXU pass, nothing rounded that ``kda_recurrent_step`` does not round; the
  arithmetic runs under the copies (with the arithmetic taken out the kernel
  takes as long). The per-channel vectors (``exp(g)``, ``kh``, ``qh``) are
  turned once a row so that ``dk`` lies on sublanes (a head's column
  broadcasts along the lanes of its state); ``beta`` and ``qh . kh`` are
  scalars in SMEM.

Shapes outside the envelope (``dk % 8``, ``dv % 128``) return None and the
caller takes its plain-XLA form. Interpret mode on CPU: the unit tier pins
the kernel against ``kda_recurrent_step``; its times by live rows are
``benchmarks/kda_decode_bench.py``'s.
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


def kda_envelope_ok(dk: int, dv: int) -> bool:
    """Whether a head's ``[dk, dv]`` float32 state tiles: whole sublanes of
    ``dk``, whole lanes of ``dv``."""
    return dk % 8 == 0 and dv % 128 == 0


def _kernel(order_ref, rows_ref, nlive_ref, eg_ref, k_ref, q_ref, v_ref,
            sc_ref, pool_in, o_ref, pool_out, buf, cols, rsem, wsem):
    i = pl.program_id(0)
    n_live = nlive_ref[0]
    H = k_ref.shape[1]

    def update(ph, at):
        # the row's per-channel vectors with dk on sublanes: head h's column
        # then broadcasts along the lanes of its state
        for c, ref in enumerate((eg_ref, k_ref, q_ref)):
            cols[c] = ref[0].T                             # [dk, H]
        for h in range(H):
            eg = cols[0, :, h:h + 1]                       # [dk, 1]
            kc = cols[1, :, h:h + 1]
            qc = cols[2, :, h:h + 1]
            s1 = buf[ph % 2, at, h] * eg                   # [dk, dv]
            r = jnp.sum(s1 * kc, axis=0, keepdims=True)
            p = jnp.sum(s1 * qc, axis=0, keepdims=True)
            u = sc_ref[0, 0, h] * (v_ref[0, h:h + 1, :] - r)
            o_ref[0, h:h + 1, :] = p + sc_ref[0, 0, H + h] * u
            buf[ph % 2, at, h] = s1 + kc * u

    state_phases.in_phases(i, n_live, order_ref, rows_ref, pool_in, pool_out,
                           buf, rsem, wsem, update)

    @pl.when(i >= n_live)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("R", "interpret"))
def _kda_call(state, order, rows, n_live, eg, kh, qh, v, sc, *, R, interpret):
    B, H, dk = kh.shape
    dv = v.shape[-1]

    by_row = state_phases.by_live_row
    buf, *sems = state_phases.phase_scratch(R, (H, dk, dv))
    vec = lambda d: pl.BlockSpec((1, H, d), by_row)         # noqa: E731
    o, state = pl.pallas_call(
        _kernel,
        name="kda_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                vec(dk), vec(dk), vec(dk), vec(dv),
                pl.BlockSpec((1, 1, 2 * H), by_row, memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
            ],
            out_specs=[
                pl.BlockSpec((1, H, dv), lambda i, order, *_: (order[i], 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                buf, pltpu.VMEM((3, dk, H), jnp.float32),    # exp(g), kh, qh
                *sems],
        ),
        # operand 8 (after the three prefetched scalars): the pool is output 1
        input_output_aliases={8: 1},
        # rows in order: a row's step starts and lands its phase's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * R * H * dk * dv * 4 + (8 << 20)),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        interpret=interpret,
    )(order, rows, n_live, eg, kh, qh, v, sc, state)
    return o, state


def kda_decode_update(state, qh, kh, v, g, beta, slots, base, *,
                      interpret: Optional[bool] = None):
    """One token of the gated delta rule for every LIVE row, in place.

    ``state`` ``[pool_rows, H, dk, dv]`` float32, the pool of one position of
    the period (every period's slots in one leading axis); ``qh``, ``kh``,
    ``g`` ``[B, H, dk]``, ``v`` ``[B, H, dv]``, ``beta`` ``[B, H]`` float32,
    the rows' vectors BY ROW; ``slots`` ``[B]`` int32 each row's state slot
    (0, the dummy, for an inactive row) and ``base`` the layer's first pool
    row, so row ``b`` lives at ``state[base + slots[b]]``. Live rows hold
    distinct slots. Returns ``(o [B, H, dv], the pool updated)``: the pool
    is the call's input and output in one buffer (a program that donates it
    holds no copy of it), an inactive row's ``o`` is zero and no pool row
    but the live rows' is written. Returns None where
    the shape is outside the kernel's envelope (``kda_envelope_ok``).
    """
    B, H, dk = kh.shape
    dv = v.shape[-1]
    if not kda_envelope_ok(dk, dv) or state.dtype != jnp.float32:
        warn_once(f"kda_decode_update: a {state.dtype} state of dk={dk} "
                  f"dv={dv} is outside the kernel envelope (float32, dk % 8 "
                  "== 0, dv % 128 == 0); the caller takes its plain-XLA form")
        return None
    interpret = resolve_interpret("kda_decode_update", interpret)
    f32 = jnp.float32
    order, rows, n_live = state_phases.live_rows(slots, base, B)
    sc = jnp.concatenate([beta, jnp.sum(qh * kh, axis=-1)], axis=-1)[:, None]
    return _kda_call(state, order, rows, n_live, jnp.exp(g).astype(f32),
                     kh.astype(f32), qh.astype(f32), v.astype(f32),
                     sc.astype(f32),
                     R=state_phases.phase_rows(B, H * dk * dv * 4),
                     interpret=bool(interpret))
