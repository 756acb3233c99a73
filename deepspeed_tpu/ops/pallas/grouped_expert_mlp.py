"""Pallas grouped gated-MLP: the experts a call's rows chose, read once each
from the layer stack where it lies.

A no-drop MoE layer of few rows (a decode step's 16-512) is the HBM traffic
of its experts' weights. XLA's form (``dense_expert_mlp`` below: every held
expert over every row, two einsums) reads ALL of a layer's experts whatever
the rows chose, takes the layer from a scan as a SLICE of the stacked
weights, and computes rows x E expert-rows where rows x k are wanted: past
~128 rows that arithmetic outlasts the bytes. This kernel reads what the
routing needs and nothing else:

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
  pipeline under the products of the tile before;
* WHICH ROWS a visit computes goes by the call's static rows (``row_tile``).
  Up to ``RIDE_ROWS`` (one MXU row tile) ALL rows ride every visit (``[T,
  D]`` resident): the MXU's time is the weights' passage through it, not the
  rows', so no sort, no gather and no scatter is paid for. Past it (up to
  ``MAX_ROWS``) a visit computes its expert's OWN rows, ``ROW_TILE`` at a
  time, inside the one grid step that holds the expert's weights (a second
  tile is a second passage through the MXU and no second copy): the rows
  stay resident as they are, each row's place among its expert's rows comes
  with the call (``own_rows``: a triangular 0 / 1 product outside), a tile's
  rows are gathered by a 0 / 1 ``[tm, T]`` product (exact) and its weighted
  result is added to those rows of the output by the transposed 0 / 1
  product over three bf16 pieces that sum to the float32 value (exact
  again: ``_exact_pieces``). Every assignment is computed, at any
  imbalance, up to all ``T`` rows on one expert;
* products in the operands' dtype with float32 sums, the activation
  (``silu`` or ``relu`` of the gate, times up) in float32, the down product
  in float32 weighted by the expert's column of ``combine`` IN float32 and
  added to the output, which stays in VMEM for the whole call (up to
  ``RIDE_ROWS`` a visit's F tiles are summed in a float32 scratch first).
  (``dense_expert_mlp`` rounds ``h x combine`` to the operands' dtype
  before its down product; the kernel rounds ``h`` alone, so it rounds
  less, not more.)

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
# products cost more than its weights' copy, so a visit computes its
# expert's OWN rows, ``ROW_TILE`` at a time (``row_tile``). Measured on a
# v5e (benchmarks/moe_dispatch_bench.py --forms dense kernel --row-tile 32
# 64 128, PERF.md section 6, PR 53, call 1), ms a layer, dense | kernel at
# tm 32 | 64 | 128: LFM2's 64 experts of 2,048 x 1,536, top-4, 256 rows
# 1.935 | 1.623 | 1.627 | 1.634, 384 rows 2.623 | 1.632 | 1.636 | 1.643, 512
# rows 3.288 | 1.640 | 1.643 | 1.651 (128 rows, all riding: 1.626 | 1.619);
# SDAR's 16 held of 128 of 2,048 x 768, top-8, 256 rows 0.2443 | 0.2154 |
# 0.2176 | 0.2217, 512 rows 0.4293 | 0.2237 | 0.2250 | 0.2363; Solar's 40
# held of 320 of 4,096 x 1,280, 256 rows 1.962 | 1.800 | 1.804 | 1.811, 512
# rows 3.631 | 1.888 | 1.893 | 2.067; OLMoE's 64 of 2,048 x 1,024, top-8, 256
# rows 1.243 | 1.082 | 1.081 | 1.086, 512 rows (64 rows an expert: two tiles
# of 32) 2.219 | 1.133 | 1.106 | 1.093. The smallest tile wins wherever an
# expert's rows fit one (the gather, the scatter and the activation go with
# the tile, the three products do not) and loses 2-4% where they take two.
RIDE_ROWS = 128
ROW_TILE = 32
# The largest call taken. ``vmem_limit_bytes`` at 512 rows of Solar's d
# 4,096: rows 4 MB and output 8 MB, two buffers each, and the rows' finite
# copy (28 MB), six 5.2 MB weight tiles (31.5 MB), a row tile's values (~3
# MB) and 8 MB spare: 71 MB of the chip's 128.
MAX_ROWS = 512
# Lanes of the output one scatter product adds to: the product's float32
# value stays [T, 512], not [T, D].
_SCATTER_LANES = 512

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


def row_tile(T: int) -> int:
    """Rows of an expert's own a visit computes at a time in a call of ``T``
    rows, or 0 where all rows ride every visit (one MXU row tile)."""
    return ROW_TILE if T > RIDE_ROWS else 0


def envelope_ok(T: int, D: int, F: int) -> bool:
    """Whether the compiled kernel takes a call: its rows, whole lanes."""
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


def _weight_specs(D: int, tf: int, nf: int):
    """The blocks of a grid step's three weight tiles, ``w_gate``, ``w_up``
    ``[D, tf]`` and ``w_down`` ``[tf, D]`` of the visit's expert; the
    scalar-prefetch operands are ``visits`` first and ``meta`` last."""
    def tile(v, f, visits, *rest):
        meta = rest[-1]
        # a visit past the touched experts names the last real step's block
        return meta[1] + visits[v], jnp.where(v < meta[0], f, nf - 1)

    def by_cols(*step):
        g, f = tile(*step)
        return g, 0, f

    def by_rows(*step):
        g, f = tile(*step)
        return g, f, 0

    return [pl.BlockSpec((None, D, tf), by_cols),
            pl.BlockSpec((None, D, tf), by_cols),
            pl.BlockSpec((None, tf, D), by_rows)]


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
                *_weight_specs(D, tf, nf),
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


def _exact_pieces(v):
    """Three bf16 arrays that sum to float32 ``v`` exactly (8 + 8 + 8 bits
    of its 24), stacked along axis 0: a 0 / 1 matrix times them, summed in
    float32, moves float32 values through bf16 products unrounded."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, low], axis=0)


def _own_rows_kernel(visit_ref, tiles_ref, meta_ref, x_ref, rank_ref,
                     rank_t_ref, cw_t_ref, wg_ref, wu_ref, wd_ref, o_ref,
                     rows_ref, *, relu, tm):
    v, f = pl.program_id(0), pl.program_id(1)
    T, D = x_ref.shape

    @pl.when(jnp.logical_and(v == 0, f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        # the gather is a product over ALL rows: 0 x inf would carry one
        # row's overflow into every other row, so the rows it reads hold 0
        # there (the row itself stays what it was in the caller's residual)
        x = x_ref[...].astype(jnp.float32)      # (no bf16 compare on a v5e)
        rows_ref[...] = jnp.where(jnp.abs(x) < jnp.inf, x,
                                  0.0).astype(rows_ref.dtype)

    @pl.when(v < meta_ref[0])
    def _():
        e = visit_ref[v]
        # a row's place among its expert's rows (-1: not one of them), along
        # lanes for the gather and along sublanes for the scatter
        rank_row = rank_t_ref[pl.ds(e, 1), :]
        cw_row = cw_t_ref[pl.ds(e, 1), :]
        ranks = rank_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, ranks.shape, 1)
        rank_col = jnp.sum(jnp.where(lane == e, ranks, 0), axis=1,
                           keepdims=True)
        x = rows_ref[...]
        # the two 0 / 1 products state their precision (a caller's
        # default_matmul_precision is for the products with the weights)
        one_pass = jax.lax.Precision.DEFAULT
        exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 \
            else one_pass
        # the scatter's three pieces side by side along its contraction
        place = jax.lax.broadcasted_iota(jnp.int32, (T, 3 * tm), 1)
        place = place - tm * ((place >= tm).astype(jnp.int32)
                              + (place >= 2 * tm).astype(jnp.int32))
        slot = jax.lax.broadcasted_iota(jnp.int32, (tm, T), 0)

        def tile(j, carry):
            own = rank_row - j * tm == slot                      # [tm, T]
            # the expert's rows j*tm .. of x, compacted: a 0 / 1 product
            xs = jnp.dot(own.astype(x.dtype), x, precision=exact,
                         preferred_element_type=jnp.float32).astype(x.dtype)
            gate = jnp.dot(xs, wg_ref[...], preferred_element_type=jnp.float32)
            up = jnp.dot(xs, wu_ref[...], preferred_element_type=jnp.float32)
            h = (jnp.maximum(gate, 0.0) if relu else jax.nn.silu(gate)) * up
            down = jnp.dot(h.astype(x.dtype), wd_ref[...],
                           preferred_element_type=jnp.float32)
            c = jnp.sum(jnp.where(own, cw_row, 0.0), axis=1, keepdims=True)
            pieces = _exact_pieces(c * down)                     # [3 tm, D]
            back = (rank_col - j * tm == place).astype(jnp.bfloat16)
            for d0 in range(0, D, _SCATTER_LANES):
                cols = slice(d0, min(d0 + _SCATTER_LANES, D))
                o_ref[:, cols] += jnp.dot(back, pieces[:, cols],
                                          precision=one_pass,
                                          preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, tiles_ref[e], tile, 0)


@functools.partial(jax.jit, static_argnames=("relu", "tf", "tm", "interpret"))
def _call_own_rows(visits, tiles, meta, x, rank, rank_t, combine_t, w_gate,
                   w_up, w_down, *, relu, tf, tm, interpret):
    T, D = x.shape
    E = rank.shape[1]
    F = w_up.shape[2]
    nf = F // tf
    item = w_up.dtype.itemsize

    whole = lambda *_: (0, 0)                                # noqa: E731
    return pl.pallas_call(
        functools.partial(_own_rows_kernel, relu=relu, tm=tm),
        name="grouped_expert_mlp_own_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, nf),
            in_specs=[
                pl.BlockSpec((T, D), whole),
                pl.BlockSpec((T, E), whole),
                pl.BlockSpec((E, T), whole),
                pl.BlockSpec((E, T), whole),
                *_weight_specs(D, tf, nf),
            ],
            out_specs=pl.BlockSpec((T, D), whole),
            scratch_shapes=[pltpu.VMEM((T, D), x.dtype)],
        ),
        # visits in order: the output lives across steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # three weight tiles, rows and output, two buffers each; a row
            # tile's float32 values (MAX_ROWS' comment reckons the largest)
            vmem_limit_bytes=6 * D * tf * item
            + T * D * (3 * x.dtype.itemsize + 2 * 4)
            + 4 * tm * (tf + 2 * D) * 4 + 2 * T * _SCATTER_LANES * 4
            + (8 << 20)),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        interpret=interpret,
    )(visits, tiles, meta, x, rank, rank_t, combine_t, w_gate, w_up, w_down)


def own_rows(combine, tm: int):
    """What a call past ``RIDE_ROWS`` rows hands the kernel beside its
    visits, from ``combine [T, E]``: ``rank [T, E]`` int32, a row's place
    among the rows that chose the expert (-1 where it did not), the same
    ``[E, T]``, ``combine`` ``[E, T]``, and ``tiles [E]`` int32, the row
    tiles of ``tm`` each expert's rows fill."""
    T = combine.shape[0]
    chosen = combine != 0
    ids = jnp.arange(T)
    # the chosen rows before a row: a 0 / 1 triangle times 0 / 1, exact in
    # bf16 with float32 sums (one small product; a cumsum is a reduce-window)
    before = jnp.dot((ids[None, :] < ids[:, None]).astype(jnp.bfloat16),
                     chosen.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    rank = jnp.where(chosen, before, -1)
    tiles = (jnp.sum(chosen, axis=0, dtype=jnp.int32) + tm - 1) // tm
    return rank, rank.T, combine.astype(jnp.float32).T, tiles


def grouped_expert_mlp(x, combine, w_gate, w_up, w_down, group0=0, *,
                       relu: bool = False, visits=None, rows=None,
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
    else SiLU. ``visits``: ``touched_visits(combine)``, and for a call past
    ``RIDE_ROWS`` rows ``rows``: ``own_rows(combine, row_tile(T))``, where
    the caller has taken them already (under a scope of its own). Returns
    ``sum_e combine[:, e] * (act(x w_gate[e]) * (x w_up[e])) w_down[e]``
    ``[T, D]`` float32 over the touched experts, or None where the shape is
    outside the kernel's envelope (``envelope_ok``)."""
    T, D = x.shape
    F = w_up.shape[2]
    if T <= MAX_ROWS:
        interpret = resolve_interpret("grouped_expert_mlp", interpret)
    if T > MAX_ROWS or not (interpret or envelope_ok(T, D, F)):
        warn_once(f"grouped_expert_mlp: {T} rows of D={D} F={F} are outside "
                  f"the kernel envelope (at most {MAX_ROWS} rows, D % 128 == "
                  "0, F % 128 == 0); the caller takes its plain-XLA form")
        return None
    tm = row_tile(T)
    if tm and rows is None:
        rows = own_rows(combine, tm)
    # whole sublane tiles of the operands' dtype (16 rows of bf16)
    pad = -T % (32 // x.dtype.itemsize)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        combine = jnp.pad(combine, ((0, pad), (0, 0)))
    visits, n = visits or touched_visits(combine)
    meta = jnp.stack([n, jnp.asarray(group0, jnp.int32)])
    tf = f_tile or _f_tile(D, F, w_up.dtype.itemsize)
    if not tm:
        out = _call(visits, meta, x, combine.astype(jnp.float32), w_gate,
                    w_up, w_down, relu=relu, interpret=bool(interpret), tf=tf)
    else:
        rank, rank_t, combine_t, tiles = rows
        if pad:
            rank = jnp.pad(rank, ((0, pad), (0, 0)), constant_values=-1)
            rank_t = jnp.pad(rank_t, ((0, 0), (0, pad)), constant_values=-1)
            combine_t = jnp.pad(combine_t, ((0, 0), (0, pad)))
        out = _call_own_rows(visits, tiles, meta, x, rank, rank_t, combine_t,
                             w_gate, w_up, w_down, relu=relu, tf=tf, tm=tm,
                             interpret=bool(interpret))
    return out[:T] if pad else out
