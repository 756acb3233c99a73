"""Fused logits-free cross-entropy Pallas kernel for the vocab head.

Computes ``mean CE(h @ W + b, labels)`` without ever materialising the
``[tokens, V]`` logits: the forward streams ``W`` in vocab blocks and keeps a
running ``(max, logsumexp, label_logit)`` state in VMEM scratch per token
tile, so HBM traffic is O(tokens·D + D·V) instead of O(tokens·V) — the TPU
re-expression of the reference's fused softmax/cross-entropy kernels
(``csrc/transformer/softmax_kernels.cu``, inference fused logits in
``csrc/transformer/inference``). That state is a LANE wide — lane c of a row
holds the max and the sum of the columns ``128 k + c`` seen so far — so a
vocab step is the block's product plus whole-vreg elementwise work, and the
lanes are reduced once a token tile, on the last step: a row reduction a
block had kept the matrix unit waiting on the cross-lane unit (58% of the
product's time at V 250,880). The forward also walks token tiles of its own,
up to 1,024 rows where the backward's accumulators allow 128-256: it streams
all of ``W`` once a token tile and has nothing else to keep in VMEM. The
backward recomputes each vocab block's logits on the fly from the saved
logsumexp (no [tokens, V] residual either) and accumulates
``dh = (softmax - onehot) @ W_blk^T`` and
``dW_blk = h^T @ (softmax - onehot)`` per block.

Like the flash kernels in this package, the streaming softmax runs in the
**log2 domain** (logits pre-scaled by log2(e), ``exp2`` instead of ``exp`` —
the VPU evaluates exp2 faster) and every matmul keeps its storage dtype
(bf16 operands, f32 accumulate) so the dots ride the MXU at full rate.

Vocab padding is handled by pre-biasing: the bias vector is padded with a
large negative on the pad columns, so padded logits underflow to zero
probability in both passes and never pollute the logsumexp — no in-kernel
bounds checks. Ignore-index / masked labels are handled OUTSIDE the
custom_vjp boundary: the kernel returns per-token nll and the (differentiable)
masked mean runs in XLA, so the backward coefficient each kernel consumes is
exactly the cotangent AD hands it (zero on masked and padded tokens).

Wired into the model zoo via ``models/transformer.py vocab_head_ce`` (config
``fused_cross_entropy: auto|on|off``). Runs compiled on TPU, interpreted
elsewhere (the CPU unit tier exercises it numerically via interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.dispatch import record, resolve_interpret

_MASKED = -1e30  # pad-column bias: exp2 underflows to exactly 0
_LOG2E = 1.4426950408889634
_LANES = 128


def _round8(n: int) -> int:
    return -(-max(8, n) // 8) * 8


# --------------------------------------------------------------------- #
# kernels. Shared geometry: h [Np, D] token-tiled (bt rows), w [D, Vp]
# vocab-tiled (bv cols), bias/labels/rows ride as [1, Np] / [1, Vp] so the
# trailing block dims tile lanes (same trick as flash_attention's row specs).


def _block_logits(h_ref, w_ref, b_ref):
    """One (bt, bv) block of log2-domain logits: (h @ w_blk + b_blk)·log2e.
    Storage-dtype operands (bf16 runs the MXU at full rate), f32 accumulate;
    pad columns carry a _MASKED bias and underflow to p=0 downstream."""
    s = jax.lax.dot_general(h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return (s + b_ref[:].astype(jnp.float32)) * _LOG2E


def _fwd_kernel(h_ref, w_ref, b_ref, lab_ref, nll_ref, lse_ref,
                m_scr, l_scr, g_scr, rel_scr, *, bv):
    # grid (nt, nv), vocab innermost. The running (max, sum, gold) state is
    # kept PER LANE in (bt, 128) scratch: lane c of a row holds the max and
    # the sum over the columns 128 k + c seen so far, so a vocab step is the
    # block's product and whole-vreg elementwise work; what crosses lanes
    # runs once a token tile, on the last vocab step
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _MASKED)
        l_scr[:] = jnp.zeros_like(l_scr)
        g_scr[:] = jnp.zeros_like(g_scr)
        # a label's distance from each lane's first column: the one turn of
        # the labels from lanes to sublanes a token tile needs
        lane = jax.lax.broadcasted_iota(jnp.int32, rel_scr.shape, 1)
        rel_scr[:] = lab_ref[0][:, None] - lane

    s = _block_logits(h_ref, w_ref, b_ref)
    chunks = [s[:, k:k + _LANES] for k in range(0, bv, _LANES)]

    m_prev = m_scr[:]
    m_new = functools.reduce(jnp.maximum, chunks, m_prev)
    # gold logit: each token's label falls in exactly one column; lane c of
    # chunk k holds column j bv + 128 k + c, a compare against the stored
    # distance finds it without any dynamic indexing
    rel = rel_scr[:]
    l_new = l_scr[:] * jnp.exp2(m_prev - m_new)
    g_new = g_scr[:]
    for k, s_k in enumerate(chunks):
        l_new = l_new + jnp.exp2(s_k - m_new)
        g_new = g_new + jnp.where(rel == j * bv + k * _LANES, s_k, 0.0)
    m_scr[:] = m_new
    l_scr[:] = l_new
    g_scr[:] = g_new

    @pl.when(j == nv - 1)
    def _():
        # a lane that saw pad columns alone still holds m = _MASKED, l = 0
        # and drops out as exp2(_MASKED - m) = 0; some lane saw a real
        # column, so l >= exp2(max - max) = 1 and the log is safe
        m = jnp.max(m_new, axis=1, keepdims=True)
        l = jnp.sum(l_new * jnp.exp2(m_new - m), axis=1)
        lse2 = m[:, 0] + jnp.log2(l)
        lse_ref[0] = lse2
        # natural-log nll; masked/padded tokens get a finite garbage value
        # that the outer (differentiable) masked mean zeroes out
        nll_ref[0] = (lse2 - jnp.sum(g_new, axis=1)) / _LOG2E


def _softmax_minus_onehot(h_ref, w_ref, b_ref, lab_ref, lse_ref, coef_ref,
                          j, bt, bv):
    """(p - onehot)·coef for one block, recomputed from the saved log2-domain
    logsumexp — the shared core of both backward kernels."""
    s = _block_logits(h_ref, w_ref, b_ref)
    p = jnp.exp2(s - lse_ref[0][:, None])  # pad cols: exp2(-huge) = 0
    lab_local = lab_ref[0] - j * bv
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    onehot = (cols == lab_local[:, None]).astype(jnp.float32)
    return (p - onehot) * coef_ref[0][:, None]


def _dh_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, coef_ref, dh_ref,
               dh_scr, *, bt, bv):
    # grid (nt, nv), vocab innermost: dh for one token tile accumulates over
    # vocab blocks in scratch
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    ds = _softmax_minus_onehot(h_ref, w_ref, b_ref, lab_ref, lse_ref,
                               coef_ref, j, bt, bv).astype(w_ref.dtype)
    dh_scr[:] = dh_scr[:] + jax.lax.dot_general(
        ds, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _():
        dh_ref[:] = dh_scr[:].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, coef_ref,
               dw_ref, db_ref, dw_scr, db_scr, *, bt, bv):
    # grid (nv, nt), tokens innermost: dw/db for one vocab block accumulate
    # over token tiles in scratch
    i = pl.program_id(1)
    nt = pl.num_programs(1)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    ds = _softmax_minus_onehot(h_ref, w_ref, b_ref, lab_ref, lse_ref,
                               coef_ref, j, bt, bv)
    dw_scr[:] = dw_scr[:] + jax.lax.dot_general(
        h_ref[:], ds.astype(h_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_scr[:1] = db_scr[:1] + jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(i == nt - 1)
    def _():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[0] = db_scr[0].astype(db_ref.dtype)


# --------------------------------------------------------------------- #
# tile geometry, from the shapes alone

#: the forward's token tile is the largest of these that the padded token
#: count divides into and whose blocks fit _FWD_VMEM_BYTES
_FWD_TILES = (1024, 512, 256, 128)
_FWD_VMEM_BYTES = 24 << 20
_SCOPED_VMEM_BYTES = 16 << 20   # what a kernel gets when it asks for nothing


def _fwd_vmem_bytes(bt_fwd: int, D: int, bv: int, item: int) -> int:
    """VMEM of one forward step: the (bt_fwd, D) token tile and the (D, bv)
    weight block, two buffers each; the block's float32 logits and their
    exponentials; the three states and the labels' distances."""
    return (2 * (bt_fwd + bv) * D * item + 2 * bt_fwd * bv * 4
            + 4 * bt_fwd * _LANES * 4)


def _tiles(N: int, D: int, V: int, item: int, block_t: Optional[int] = None,
           block_v: Optional[int] = None):
    """``(bt, bt_fwd, bv, bv_dw)`` for N tokens of D features in ``item``
    bytes against V columns."""
    # token tile: whole (8-aligned) token set when it fits one block; else
    # 128-aligned so the [1, Np] row blocks tile lanes legally. Large-D
    # heads (7B-class, D >= 4096) take the finer defaults so the (bt, D)
    # dh accumulator and (D, bv) weight blocks stay within VMEM.
    bt = block_t or (128 if D >= 4096 else 256)
    n8 = _round8(N)
    bt = min(bt, n8)
    if n8 > bt and bt % 128:
        bt = -(-bt // 128) * 128
    Np = -(-N // bt) * bt

    # vocab tile: whole lanes, which the [1, Vp] bias/db rows need to tile
    # and the forward's lane-wide state to mean a column a lane
    bv = block_v or (256 if D >= 4096 else 512)
    bv = -(-min(bv, V) // _LANES) * _LANES
    # dw accumulator (D, bv_dw) f32 must fit VMEM comfortably at large D;
    # halve while it exceeds ~4 MB. Every halving keeps bv_dw = bv / 2^k, a
    # divisor of bv and hence of Vp (Vp = ceil(V/bv)·bv), so the dw grid
    # always tiles exactly.
    bv_dw = bv
    while bv_dw % 2 == 0 and bv_dw > 128 and D * bv_dw * 4 > (4 << 20):
        bv_dw //= 2

    # the forward has no (bt, D) or (D, bv) float32 accumulator to tie its
    # token tile to the backward's, and streams all of W once a token tile:
    # it takes the largest tile that adds no padding and fits its budget
    bt_fwd = next((t for t in _FWD_TILES
                   if t > bt and t % bt == 0 and Np % t == 0
                   and _fwd_vmem_bytes(t, D, bv, item) <= _FWD_VMEM_BYTES), bt)
    return bt, bt_fwd, bv, bv_dw


# --------------------------------------------------------------------- #
# custom-VJP wrapper (one cached build per static geometry)


@functools.lru_cache(maxsize=32)
def _build(D: int, bt: int, bt_fwd: int, bv: int, bv_dw: int, interpret: bool):
    """Per-token-nll CE with custom VJP on padded [Np, D] / [D, Vp] operands.
    The forward walks token tiles of ``bt_fwd`` rows, both backward kernels
    tiles of ``bt``.

    Returns ``nll [1, Np]`` f32; the (masked, differentiable) mean runs in
    XLA outside, so AD delivers each token's loss coefficient — including
    valid-mask zeros and the 1/count scale — as the nll cotangent, which the
    backward kernels consume directly.
    """

    def h_spec(rows=bt):
        return pl.BlockSpec((rows, D), lambda i, j: (i, 0))

    def w_spec(bvx=bv):
        return pl.BlockSpec((D, bvx), lambda i, j: (0, j))

    def vrow_spec(bvx=bv):
        # bias rides [1, Vp]
        return pl.BlockSpec((1, bvx), lambda i, j: (0, j))

    def trow_spec(rows=bt):
        # labels / lse / coef / nll ride [1, Np]
        return pl.BlockSpec((1, rows), lambda i, j: (0, i))

    def fwd_call(hp, wp, bp, labp):
        Np, D = hp.shape
        Vp = wp.shape[1]
        nll, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, bv=bv),
            name="fused_ce_fwd",
            grid=(Np // bt_fwd, Vp // bv),
            in_specs=[h_spec(bt_fwd), w_spec(), vrow_spec(),
                      trow_spec(bt_fwd)],
            out_specs=[trow_spec(bt_fwd), trow_spec(bt_fwd)],
            out_shape=[jax.ShapeDtypeStruct((1, Np), jnp.float32),
                       jax.ShapeDtypeStruct((1, Np), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bt_fwd, _LANES), jnp.float32),
                            pltpu.VMEM((bt_fwd, _LANES), jnp.float32),
                            pltpu.VMEM((bt_fwd, _LANES), jnp.float32),
                            pltpu.VMEM((bt_fwd, _LANES), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(_SCOPED_VMEM_BYTES, _fwd_vmem_bytes(
                    bt_fwd, D, bv, hp.dtype.itemsize) + (8 << 20))),
            interpret=interpret,
        )(hp, wp, bp, labp)
        return nll, lse

    @jax.custom_vjp
    def ce_nll(hp, wp, bp, labp):
        return fwd_call(hp, wp, bp, labp)[0]

    def ce_fwd(hp, wp, bp, labp):
        nll, lse = fwd_call(hp, wp, bp, labp)
        return nll, (hp, wp, bp, labp, lse)

    def ce_bwd(res, g):
        hp, wp, bp, labp, lse = res
        Np, D = hp.shape
        Vp = wp.shape[1]
        coef = g.astype(jnp.float32)  # [1, Np]: valid·ĝ/count from the mean

        dh = pl.pallas_call(
            functools.partial(_dh_kernel, bt=bt, bv=bv),
            name="fused_ce_dh",
            grid=(Np // bt, Vp // bv),
            in_specs=[h_spec(), w_spec(), vrow_spec(), trow_spec(),
                      trow_spec(), trow_spec()],
            out_specs=h_spec(),
            out_shape=jax.ShapeDtypeStruct((Np, D), hp.dtype),
            scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
            interpret=interpret,
        )(hp, wp, bp, labp, lse, coef)

        # transposed grid: token tiles innermost so one (D, bv_dw) dw block
        # accumulates across them in scratch (bv_dw may be finer than the
        # forward's bv to keep the f32 accumulator within VMEM at large D)
        kh_spec = pl.BlockSpec((bt, D), lambda j, i: (i, 0))
        kw_spec = pl.BlockSpec((D, bv_dw), lambda j, i: (0, j))
        kv_spec = pl.BlockSpec((1, bv_dw), lambda j, i: (0, j))
        kt_spec = pl.BlockSpec((1, bt), lambda j, i: (0, i))
        dw, db = pl.pallas_call(
            functools.partial(_dw_kernel, bt=bt, bv=bv_dw),
            name="fused_ce_dw",
            grid=(Vp // bv_dw, Np // bt),
            in_specs=[kh_spec, kw_spec, kv_spec, kt_spec, kt_spec, kt_spec],
            out_specs=[kw_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct((D, Vp), wp.dtype),
                       jax.ShapeDtypeStruct((1, Vp), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((D, bv_dw), jnp.float32),
                            pltpu.VMEM((8, bv_dw), jnp.float32)],
            interpret=interpret,
        )(hp, wp, bp, labp, lse, coef)

        return (dh, dw, db.astype(bp.dtype),
                np.zeros(labp.shape, jax.dtypes.float0))

    ce_nll.defvjp(ce_fwd, ce_bwd)
    return ce_nll


# --------------------------------------------------------------------- #
# public entry point


def fused_cross_entropy(h, w, labels, bias=None, valid=None,
                        block_t: Optional[int] = None,
                        block_v: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Mean token cross-entropy of the vocab head ``h @ w + bias`` vs
    ``labels``, logits never materialised.

    h: [..., D] features (any leading shape; bf16 or f32); w: [D, V];
    bias: optional [V]; labels: [...] int (must be in [0, V) — mask
    ignore-index positions via ``valid`` and clamp the labels, exactly like
    ``chunked_vocab_ce``'s safe_labels); valid: optional [...] bool/float
    keep-mask. Returns the scalar mean nll over valid tokens
    (``sum(nll·valid) / max(sum(valid), 1)`` — empty masks yield 0, matching
    the XLA reference path).

    Differentiable through ``jax.custom_vjp`` w.r.t. h, w, and bias, and
    composes with jit/remat/shard_map (fully-manual contexts). Runs compiled
    on TPU, interpreted elsewhere (``interpret=None`` auto-selects).
    """
    D = h.shape[-1]
    V = w.shape[-1]
    if w.shape[0] != D:
        raise ValueError(f"w {w.shape} does not match features D={D}")
    N = 1
    for d in labels.shape:
        N *= d
    if h.size != N * D:
        raise ValueError(f"h {h.shape} does not match labels {labels.shape}")
    interpret = resolve_interpret("fused_cross_entropy", interpret)

    bt, bt_fwd, bv, bv_dw = _tiles(N, D, V, h.dtype.itemsize, block_t, block_v)
    Np = -(-N // bt) * bt
    Vp = -(-V // bv) * bv
    record("fused_ce_fwd", "lane_state", f"bt_fwd={bt_fwd} bt={bt} bv={bv}")

    hp = h.reshape(N, D)
    if w.dtype != hp.dtype:
        # the in-kernel dots need matching operand dtypes; the cast sits
        # OUTSIDE the custom_vjp, so AD casts dw back to w's dtype itself
        w = w.astype(hp.dtype)
    labp = labels.reshape(N).astype(jnp.int32)
    vf = (jnp.ones((N,), jnp.float32) if valid is None
          else valid.reshape(N).astype(jnp.float32))
    b = (jnp.zeros((V,), jnp.float32) if bias is None
         else bias.astype(jnp.float32))

    if Np != N:
        hp = jnp.pad(hp, ((0, Np - N), (0, 0)))
        labp = jnp.pad(labp, (0, Np - N))
        vf = jnp.pad(vf, (0, Np - N))
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
        # pad columns get a -1e30 bias: zero probability in fwd AND bwd
        b = jnp.pad(b, (0, Vp - V), constant_values=_MASKED)

    ce_nll = _build(D, bt, bt_fwd, bv, bv_dw, bool(interpret))
    nll = ce_nll(hp, w, b[None, :], labp[None, :])  # [1, Np]
    # masked mean OUTSIDE the custom_vjp: AD turns it into the per-token
    # backward coefficient (0 on masked/padded tokens, 1/count elsewhere)
    return jnp.sum(nll[0] * vf) / jnp.maximum(jnp.sum(vf), 1.0)
