"""Pallas grouped gated-MLP: the experts a call's rows chose, read once each
from the layer stack where it lies.

A no-drop MoE layer of few rows (a decode step's 16-128) is the HBM traffic
of its experts' weights. XLA's form (``dense_expert_mlp`` below: every held
expert over every row, two einsums) reads ALL of a layer's experts whatever
the rows chose, and takes the layer from a scan as a SLICE of the stacked
weights. This kernel reads what the routing needs and nothing else:

* the three stacks enter WHOLE, ``[G, D, F]`` / ``[G, F, D]`` with ``G =
  layers x E`` (the layer axis merged into the expert axis: a bitcast), and
  layer ``l``'s expert ``e`` is group ``group0 + e``, ``group0 = l * E`` a
  scalar-prefetch operand. No slice of a stack feeds the call, so a program
  that scans its layers holds no copy of a layer's experts;
* grid ``(visit, F tile)``. The visits are the TOUCHED experts (a column of
  ``combine`` with a non-zero weight), compacted in expert order and padded
  by repeating the last one: a step past the touched experts names the block
  the step before it named, the pipeline issues no copy for it, and its
  body does not run. An expert no row chose costs no byte;
* a visit brings its expert's ``w_gate`` and ``w_up`` ``[D, tf]`` and
  ``w_down`` ``[tf, D]`` an F tile (``_f_tile``), double-buffered by the
  pipeline under the products of the tile before. ALL rows ride every visit
  (``[T, D]``, T up to ``MAX_ROWS``, resident): at a decode step's rows the
  MXU's time is the weights' passage through it, not the rows', so no sort,
  no gather and no scatter is paid for;
* products in the operands' dtype with float32 sums, the activation
  (``silu`` or ``relu`` of the gate, times up) in float32, a visit's down
  product summed over its F tiles in a float32 ``[T, D]`` scratch, then
  weighted by the expert's column of ``combine`` IN float32 and added to the
  output, which stays in VMEM for the whole call. (``dense_expert_mlp``
  rounds ``h x combine`` to the operands' dtype before its down product;
  the kernel rounds ``h`` alone, so it rounds less, not more.)

Shapes outside the envelope (more than ``MAX_ROWS`` rows; compiled: ``D`` or
``F`` not whole lanes) return None and the caller keeps its XLA form.
Interpret mode on CPU: the unit tier pins the kernel against
``dense_expert_mlp``; its times are ``benchmarks/moe_dispatch_bench.py``'s.
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

# Rows that ride every visit: one row tile of the MXU. Past it a visit's
# products cost more than its weights' copy and rows have to be sorted to
# their experts first (not built: ROADMAP S13).
MAX_ROWS = 128

# Bytes of ONE of an expert's three matrices a grid step brings (a ``[D,
# tf]`` tile; two buffers each, so six times this in VMEM). Measured on a v5e
# (benchmarks/moe_dispatch_bench.py --forms kernel --f-tile ..., PERF.md
# section 6, PR 40), ms a layer by lanes of F a tile: SmallThinker's 16 rows
# x 50.75 experts of 2,560 x 768, 128 | 256 | 384 | 768: 0.810 | 0.805 |
# 0.804 | 0.803; OLMoE's 64 rows x 64 of 2,048 x 1,024, 256 | 512 | 1,024:
# 1.209 | 1.075 | 1.077; Solar's 128 rows x 38.5 of 4,096 x 1,280, 256 | 640
# | 1,280: 1.729 | 1.712 | 1.705; SDAR's 128 rows x 16 of 2,048 x 768, 256 |
# 384 | 768: 0.212 | 0.213 | 0.215. Fewer, larger steps win or lose nothing
# from ~2 MB a tile on; 6 MB takes the whole F of all but Solar (640).
_TILE_BYTES = 6 * 1024 * 1024


def _f_tile(D: int, F: int, itemsize: int) -> int:
    """Lanes of F a grid step: the largest whole-lane divisor of F whose
    ``[D, tf]`` tile fits ``_TILE_BYTES`` (at least one lane tile), or, at
    toy widths that are not whole lanes, all of F."""
    fits = [tf for tf in range(128, F + 1, 128)
            if F % tf == 0 and D * tf * itemsize <= _TILE_BYTES]
    return max(fits) if fits else 128 if F % 128 == 0 else F


def envelope_ok(T: int, D: int, F: int) -> bool:
    """Whether the compiled kernel takes a call: one row tile, whole lanes."""
    return T <= MAX_ROWS and D % 128 == 0 and F % 128 == 0


def dense_expert_mlp(x, combine, w_gate, w_up, w_down, *, relu: bool = False):
    """The plain-XLA twin, and the form of every call the kernel does not
    take: every expert of ``w_* [E, ...]`` over every row of ``x [T, D]``,
    ``combine [T, E]`` float32 picks. Returns [T, D] float32."""
    ein = lambda w: jnp.einsum(  # noqa: E731
        "td,edf->tef", x, w, preferred_element_type=jnp.float32)
    up, gate = ein(w_up), ein(w_gate)
    h = (jax.nn.relu(gate) if relu else jax.nn.silu(gate)) * up
    return jnp.einsum("tef,efd->td", (h * combine[:, :, None]).astype(x.dtype),
                      w_down, preferred_element_type=jnp.float32)


def _kernel(visit_ref, meta_ref, x_ref, cw_ref, wg_ref, wu_ref, wd_ref,
            o_ref, acc_ref, *, relu):
    v, f = pl.program_id(0), pl.program_id(1)
    last_f = pl.num_programs(1) - 1

    @pl.when(jnp.logical_and(v == 0, f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(v < meta_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jnp.maximum(gate, 0.0) if relu else jax.nn.silu(gate)) * up
        down = jnp.dot(h.astype(x.dtype), wd_ref[...],
                       preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _():
            acc_ref[...] = down

        @pl.when(f > 0)
        def _():
            acc_ref[...] += down

        @pl.when(f == last_f)
        def _():
            # the expert's column of combine: its lane picked by a mask, no
            # dynamic lane index
            cw = cw_ref[...]
            lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
            c = jnp.sum(jnp.where(lane == visit_ref[v], cw, 0.0), axis=1,
                        keepdims=True)
            o_ref[...] += c * acc_ref[...]


@functools.partial(jax.jit, static_argnames=("relu", "tf", "interpret"))
def _call(visits, meta, x, combine, w_gate, w_up, w_down, *, relu, tf,
          interpret):
    T, D = x.shape
    E = combine.shape[1]
    F = w_up.shape[2]
    nf = F // tf
    item = w_up.dtype.itemsize

    def tile(v, f, visits, meta):
        # a visit past the touched experts names the last real step's block
        return meta[1] + visits[v], jnp.where(v < meta[0], f, nf - 1)

    def by_cols(v, f, visits, meta):
        g, f = tile(v, f, visits, meta)
        return g, 0, f

    def by_rows(v, f, visits, meta):
        g, f = tile(v, f, visits, meta)
        return g, f, 0

    whole = lambda *_: (0, 0)                                # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, relu=relu),
        name="grouped_expert_mlp",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, nf),
            in_specs=[
                pl.BlockSpec((T, D), whole),
                pl.BlockSpec((T, E), whole),
                pl.BlockSpec((None, D, tf), by_cols),
                pl.BlockSpec((None, D, tf), by_cols),
                pl.BlockSpec((None, tf, D), by_rows),
            ],
            out_specs=pl.BlockSpec((T, D), whole),
            scratch_shapes=[pltpu.VMEM((T, D), jnp.float32)],
        ),
        # visits in order: the output and a visit's sum live across steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # three weight tiles, two buffers each; rows, output and the
            # visit's sum; the products' float32 values
            vmem_limit_bytes=6 * D * tf * item + 6 * T * D * 4
            + 4 * T * tf * 4 + (8 << 20)),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        interpret=interpret,
    )(visits, meta, x, combine, w_gate, w_up, w_down)


def touched_visits(combine):
    """(visits [E] int32, n): the experts with a non-zero weight in
    ``combine [T, E]``, in order, the list padded by repeating its last
    entry (0 where none is touched), and how many there are."""
    E = combine.shape[1]
    touched = jnp.any(combine != 0, axis=0)
    ids = jnp.arange(E, dtype=jnp.int32)
    # an expert's place in the list: the touched experts before it (an
    # [E, E] compare and a sum: one small fusion; a cumsum is a
    # reduce-window, which is not)
    before = touched[None, :] & (ids[None, :] < ids[:, None])
    rank = jnp.sum(before, axis=1, dtype=jnp.int32)
    at = touched[None, :] & (rank[None, :] == ids[:, None])
    visits = jnp.sum(jnp.where(at, ids[None, :], 0), axis=1, dtype=jnp.int32)
    n = jnp.sum(touched, dtype=jnp.int32)
    last = jnp.max(jnp.where(touched, ids, 0))
    return jnp.where(ids < n, visits, last), n


def grouped_expert_mlp(x, combine, w_gate, w_up, w_down, group0=0, *,
                       relu: bool = False, visits=None,
                       f_tile: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """The gated experts of one MoE layer over a call's rows, touched
    experts only.

    ``x`` ``[T, D]`` the rows as they are; ``combine`` ``[T, E]`` float32, a
    row's weight for each of the layer's E experts (0 where it did not
    choose it, all 0 for a padding row); ``w_gate``, ``w_up`` ``[G, D, F]``
    and ``w_down`` ``[G, F, D]`` the stacks of ``G >= E`` groups WHOLE, the
    layer's experts at groups ``group0 .. group0 + E`` (``group0`` a traced
    scalar or an int). ``relu``: the gate's activation is ReLU (``reglu``),
    else SiLU. ``visits``: ``touched_visits(combine)`` where the caller has
    taken it already (under a scope of its own). Returns ``sum_e combine[:,
    e] * (act(x w_gate[e]) * (x w_up[e])) w_down[e]`` ``[T, D]`` float32
    over the touched experts, or None where the shape is outside the
    kernel's envelope (``envelope_ok``)."""
    T, D = x.shape
    E = combine.shape[1]
    F = w_up.shape[2]
    if T <= MAX_ROWS:
        interpret = resolve_interpret("grouped_expert_mlp", interpret)
    if T > MAX_ROWS or not (interpret or envelope_ok(T, D, F)):
        warn_once(f"grouped_expert_mlp: {T} rows of D={D} F={F} are outside "
                  f"the kernel envelope (at most {MAX_ROWS} rows, D % 128 == "
                  "0, F % 128 == 0); the caller takes its plain-XLA form")
        return None
    # whole sublane tiles of the operands' dtype (16 rows of bf16)
    pad = -T % (32 // x.dtype.itemsize)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        combine = jnp.pad(combine, ((0, pad), (0, 0)))
    visits, n = visits or touched_visits(combine)
    meta = jnp.stack([n, jnp.asarray(group0, jnp.int32)])
    out = _call(visits, meta, x, combine.astype(jnp.float32), w_gate, w_up,
                w_down, relu=relu, interpret=bool(interpret),
                tf=f_tile or _f_tile(D, F, w_up.dtype.itemsize))
    return out[:T] if pad else out
