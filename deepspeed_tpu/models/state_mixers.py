"""The recurrent mixers: the layer kinds that keep a conv state, and all but
the short conv a float32 state, in a slot a request where an attention layer
keeps KV blocks. A kind is
ONE record of ``STATE_MIXERS`` (at the end) and the functions it names.
``models/transformer.py`` owns the kinds' NAMES, so that a config is built and
checked without this module, and reaches the records through its
``_mixer(kind)`` alone: the import runs one way, from here to there."""

from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (LINEAR_ATTENTION, MAMBA2,
                                              SHORT_CONV, TransformerConfig,
                                              _use_flash, _w)
from deepspeed_tpu.ops import dispatch


# --------------------------------------------------------------------- #
# Linear attention: the channel-wise gated delta rule (Kimi Delta Attention)
#
#   q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
#   qh = q / |q| * dk^-0.5,  kh = k / |k|                 (per head)
#   g  = -exp(A_log) * softplus(x Wf1 Wf2 + dt_bias) <= 0 (per channel)
#   beta = KDA_BETA_SCALE * sigmoid(x Wb)                 (per head)
#   S' = diag(exp(g)) S;  S = S' + beta kh (v - S'^T kh)^T;  o = S^T qh
#   y  = (RMSNorm_head(o) * sigmoid(x Wg1 Wg2 + bg)) Wo
#
# A request's state (S, float32 [H, dk, dv] a layer) and conv state (the
# last K-1 conv inputs) live in a SLOT of two pools beside the KV pools:
# ``state`` [periods, slots, H, dk, dv] and ``conv`` [periods, slots, K-1,
# 3*H*dk], one array of each a linear-attention position of the period
# (init_paged_kv_cache says why). Slot 0 is the dummy (inactive decode rows and nothing else).
# Decode updates the state where it lives and never gathers it: on TPU a
# Pallas kernel over the live rows' slots, elsewhere the one-token update
# over the WHOLE pool slice of a layer, slot-major (_kda_decode); prefill
# runs a chunked form.

_HI = jax.lax.Precision.HIGHEST
#: tokens a chunk of the chunked form; every exponent inside is a decay
#: between two positions of one chunk, so <= 0
KDA_CHUNK = 64
#: taps of the causal depthwise conv over q, k and v
KDA_CONV_KERNEL = 4
#: beta = 2 sigmoid(.): eigenvalues of the state's transition in (-1, 1)
KDA_BETA_SCALE = 2.0


def _init_linear_attention(cfg: TransformerConfig, n: int, key, dtype, out_std):
    D, H, dk, K = cfg.d_model, cfg.lin_heads, cfg.lin_head_dim, KDA_CONV_KERNEL
    r = dk                      # the rank of the two factored gates
    std = cfg.init_std
    ks = jax.random.split(key, 12)

    def dense(k, shape, scale=std):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    # the family's own draws (fla KimiDeltaAttention): a step's decay
    # exp(-A * dt) lies where a trained model's does
    a = jax.random.uniform(ks[9], (n, H), minval=1.0, maxval=16.0)
    dt = jax.random.uniform(ks[10], (n, H * dk), minval=1e-3, maxval=0.1)
    return {
        "wq": dense(ks[0], (n, D, H * dk)),
        "wk": dense(ks[1], (n, D, H * dk)),
        "wv": dense(ks[2], (n, D, H * dk)),
        "wo": dense(ks[3], (n, H * dk, D), out_std),
        # depthwise, causal: tap K-1 multiplies the current input; q | k | v
        "conv_w": dense(ks[4], (n, K, 3 * H * dk), K ** -0.5),
        "wf1": dense(ks[5], (n, D, r)), "wf2": dense(ks[6], (n, r, H * dk)),
        "A_log": jnp.log(a).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "wb": dense(ks[7], (n, D, H)),
        "wg1": dense(ks[8], (n, D, r)), "wg2": dense(ks[11], (n, r, H * dk)),
        "bg": jnp.zeros((n, H * dk), dtype),
        "o_norm": {"scale": jnp.ones((n, dk), dtype)},
    }


def _kda_project(cfg: TransformerConfig, x, lp, conv_ctx):
    """x [N, T, D], conv_ctx [N, K-1, 3*H*dk] the conv inputs before x ->
    (qh, kh, v [N, T, H, dk], g [N, T, H, dk] <= 0, beta [N, T, H], all
    float32, and the conv window [N, T+K-1, 3*H*dk] the next conv state is
    cut from)."""
    N, T, _ = x.shape
    H, dk, K = cfg.lin_heads, cfg.lin_head_dim, KDA_CONV_KERNEL
    f32 = jnp.float32
    u = jnp.concatenate([x @ _w(lp["wq"], x), x @ _w(lp["wk"], x),
                         x @ _w(lp["wv"], x)], axis=-1)
    win = jnp.concatenate([conv_ctx.astype(u.dtype), u], axis=1)
    with jax.named_scope("short_conv"):
        w = lp["conv_w"].astype(f32)
        y = sum(win[:, j:j + T].astype(f32) * w[j] for j in range(K))
        y = jax.nn.silu(y).reshape(N, T, 3, H, dk)
    q, k, v = y[:, :, 0], y[:, :, 1], y[:, :, 2]
    qh = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    kh = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = ((x @ _w(lp["wf1"], x)) @ _w(lp["wf2"], x)).astype(f32)
    g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        f.reshape(N, T, H, dk) + lp["dt_bias"].astype(f32).reshape(H, dk))
    beta = KDA_BETA_SCALE * jax.nn.sigmoid(
        (x @ _w(lp["wb"], x)).astype(f32))
    return qh, kh, v, g, beta, win


def _kda_output(cfg: TransformerConfig, o, x, lp):
    """o [N, T, H, dv] float32 -> (RMSNorm_head(o) * sigmoid(gate)) Wo."""
    N, T, H, dv = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
        * lp["o_norm"]["scale"].astype(jnp.float32)
    gate = ((x @ _w(lp["wg1"], x)) @ _w(lp["wg2"], x) + lp["bg"]).astype(jnp.float32)
    y = (o.reshape(N, T, H * dv) * jax.nn.sigmoid(gate)).astype(x.dtype)
    return y @ _w(lp["wo"], y)


def kda_recurrent_step(S, qh, kh, v, g, beta):
    """The one-token update, any leading dims: S [..., dk, dv] float32,
    qh, kh, g [..., dk], v [..., dv], beta [...] -> (o [..., dv], new S).
    S is read twice and written once: o = S'^T qh + (kh . qh) u comes out
    of the same pass as S'^T kh."""
    S1 = S * jnp.exp(g)[..., None]
    r = jnp.sum(S1 * kh[..., None], axis=-2)
    p = jnp.sum(S1 * qh[..., None], axis=-2)
    u = beta[..., None] * (v - r)
    o = p + jnp.sum(qh * kh, axis=-1, keepdims=True) * u
    return o, S1 + kh[..., None] * u[..., None, :]


def _inv_unit_lower(A):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular, row by row
    (forward substitution on the identity), all leading dims at once."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)

    def row(t, inv):
        a_t = jax.lax.dynamic_index_in_dim(A, t, axis=-2, keepdims=False)
        new = eye[t] - jnp.einsum("...s,...sj->...j", a_t, inv, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(inv, new, t, axis=-2)

    return jax.lax.fori_loop(0, C, row, jnp.zeros_like(A))


def kda_chunked(S0, qh, kh, v, g, beta, chunk: int = KDA_CHUNK):
    """The recurrence over T tokens of one sequence in chunks: S0 [H, dk,
    dv], qh, kh, g [T, H, dk], v [T, H, dv], beta [T, H] (T whole chunks)
    -> (o [T, H, dv], S_T). Inside a chunk, with G_t the cumulated log
    decay, u_t = beta_t (v_t - S'_t^T kh_t) solves (I + A) U = beta (V -
    (K e^G) S0), A_ts = beta_t sum_c kh_tc kh_sc e^(G_tc - G_sc) (s < t);
    o_t = S0^T (qh_t e^G_t) + sum_{s<=t} (qh_t . kh_s e^(G_t - G_s)) u_s;
    S_C = e^G_C S0 + sum_s (kh_s e^(G_C - G_s)) u_s^T. Decays are only ever
    taken between two positions (s <= t), never as e^-G: that overflows
    float32 within a chunk at this family's decay range."""
    T, H, dk = qh.shape
    C, n = chunk, T // chunk
    cut = lambda a: jnp.moveaxis(a.reshape(n, C, *a.shape[1:]), 2, 1)  # noqa: E731
    q, k, vv, gg = cut(qh), cut(kh), cut(v), cut(g)        # [n, H, C, d]
    b = cut(beta[..., None])                               # [n, H, C, 1]
    G = jnp.cumsum(gg, axis=2)
    t_idx = jnp.arange(C)
    incl = t_idx[:, None] >= t_idx[None, :]                # s <= t
    decay = jnp.exp(jnp.where(incl[:, :, None],
                              G[:, :, :, None, :] - G[:, :, None, :, :],
                              -jnp.inf))                   # [n, H, t, s, dk]
    a_qk = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :] * decay, -1)
    a_kk = jnp.sum(k[:, :, :, None, :] * k[:, :, None, :, :] * decay, -1)
    a_kk = jnp.where(t_idx[:, None] > t_idx[None, :], a_kk, 0.0) * b
    inv = _inv_unit_lower(a_kk)                            # [n, H, C, C]
    eG = jnp.exp(G)
    g_end = G[:, :, -1:, :]
    w = jnp.einsum("nhts,nhsd->nhtd", inv, b * k * eG, precision=_HI)
    vb = jnp.einsum("nhts,nhsd->nhtd", inv, b * vv, precision=_HI)
    qd, kr = q * eG, k * jnp.exp(g_end - G)
    d_end = jnp.exp(g_end[:, :, 0, :])                     # [n, H, dk]

    def step(S, xs):
        w_i, vb_i, qd_i, aqk_i, kr_i, d_i = xs
        u = vb_i - jnp.einsum("htk,hkv->htv", w_i, S, precision=_HI)
        o = jnp.einsum("htk,hkv->htv", qd_i, S, precision=_HI) \
            + jnp.einsum("hts,hsv->htv", aqk_i, u, precision=_HI)
        S = d_i[..., None] * S \
            + jnp.einsum("htk,htv->hkv", kr_i, u, precision=_HI)
        return S, o

    S, o = jax.lax.scan(step, S0, (w, vb, qd, a_qk, kr, d_end))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, -1), S


def _slot_start(pool, slot, fresh):
    """Row ``slot`` of a state or conv pool as a request's piece starts from
    it ([1, ...]): zero where the piece is the request's first (``fresh``, a
    bool, traced or not), whatever the slot's last holder left there."""
    row = jax.lax.dynamic_slice_in_dim(pool, slot, 1, axis=0)
    return jnp.where(jnp.logical_not(fresh), row, jnp.zeros_like(row))


def _kda_prefill(cfg: TransformerConfig, x, lp, state, conv, slot, n_valid,
                 fresh):
    """The mixer over a prompt, or a chunk of one, of ONE request: x [1, T,
    D] (T a compile bucket, the first ``n_valid`` positions real), ``slot``
    the request's row of the layer in ``state`` [rows, H, dk, dv] and
    ``conv`` [rows, K-1, 3*H*dk]. ``fresh`` (a bool, traced or not): the
    request's first piece starts from zero, whatever the slot's last holder
    left there. Padding has decay 1 and beta 0: the state after the bucket
    is the state after position ``n_valid``."""
    T = x.shape[1]
    K = KDA_CONV_KERNEL
    qh, kh, v, g, beta, win = _kda_project(cfg, x, lp,
                                           _slot_start(conv, slot, fresh))
    real = (jnp.arange(T) < n_valid)[None, :, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    S0 = _slot_start(state, slot, fresh)[0]
    with jax.named_scope("kda_state_update"):
        o, S = kda_chunked(S0, qh[0], kh[0], v[0], g[0], beta[0])
    state = jax.lax.dynamic_update_slice_in_dim(state, S[None], slot, axis=0)
    tail = jax.lax.dynamic_slice_in_dim(win, n_valid, K - 1, axis=1)
    conv = jax.lax.dynamic_update_slice_in_dim(
        conv, tail.astype(conv.dtype), slot, axis=0)
    return _kda_output(cfg, o[None], x, lp), state, conv


def _kda_slot_update(state, qh, kh, v, g, beta, slots, base, n_slots: int):
    """The plain-XLA form of a decode step's state update, and what the
    tests compare the kernel against: ``kda_recurrent_step`` over the
    layer's WHOLE slice of the pool (rows ``base .. base + n_slots``) in
    slot order, the rows' vectors ([B, H, d], by row) scattered to their
    slots; a slot no row holds keeps its state (decay 1, beta 0). Returns
    (o [B, H, dv] by row, the pool)."""
    def by_slot(a):
        return jnp.zeros((n_slots, *a.shape[1:]), a.dtype).at[slots].set(a)

    S = jax.lax.dynamic_slice_in_dim(state, base, n_slots, axis=0)
    o, S = kda_recurrent_step(S, by_slot(qh), by_slot(kh), by_slot(v),
                              by_slot(g), by_slot(beta))
    return o[slots], jax.lax.dynamic_update_slice_in_dim(state, S, base, axis=0)


def _kda_state_update(cfg: TransformerConfig, state, qh, kh, v, g, beta,
                      slots, base, n_slots: int):
    """A decode step's state update in one of two forms of the same float32
    arithmetic, chosen as the paged kernel is (``_use_flash``: the backend
    and the shape, never the model):

    * ``kda_kernel`` (TPU): ``ops/pallas/kda_decode_update.py`` reads each
      LIVE row's state once and writes it once, addressed row -> slot, the
      rows' vectors taken by row. A step's state traffic is the live rows';
      the dummy and every slot no live row holds are not touched.
    * ``slot_update`` (elsewhere, and shapes the kernel cannot tile):
      ``_kda_slot_update``. Its traffic is that of ALL the layer's slots,
      read twice and written once."""
    step = (qh, kh, v, g, beta, slots, base)
    out = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.kda_decode_update import \
            kda_decode_update
        out = kda_decode_update(state, *step)
    dispatch.record("kda_decode", "slot_update" if out is None else "kda_kernel",
                    f"B={qh.shape[0]} H={qh.shape[1]} dk={qh.shape[2]} "
                    f"dv={v.shape[2]} slots={n_slots}")
    return out or _kda_slot_update(state, *step, n_slots)


def _kda_decode(cfg: TransformerConfig, x, lp, state, conv, base, slots):
    """One token a row: x [B, 1, D], ``slots`` [B] each row's state slot
    (0, the dummy, for an inactive row), the layer's slots at rows ``base ..
    base + n_slots`` of ``state`` and ``conv`` (the pools of all the periods'
    layers at this position, ``n_slots`` rows each). The state is updated where
    it lives (``_kda_state_update``): on TPU by a kernel over the live rows'
    slots, so a step's state traffic goes with the live rows; elsewhere over
    the layer's whole slice, in slot order."""
    rows, n_slots = base + slots, state.shape[0] // cfg.n_periods
    ctx = conv[rows]
    qh, kh, v, g, beta, win = _kda_project(cfg, x, lp, ctx)
    conv = conv.at[rows].set(win[:, 1:].astype(conv.dtype))
    with jax.named_scope("kda_state_update"):
        o, state = _kda_state_update(cfg, state, qh[:, 0], kh[:, 0], v[:, 0],
                                     g[:, 0], beta[:, 0], slots, base, n_slots)
    return _kda_output(cfg, o[:, None], x, lp), state, conv


# --------------------------------------------------------------------- #
# Mamba-2: a state-space recurrence in its SSD form (Dao & Gu 2024), as the
# Granite 4.0-H family runs it (one group of B and C shared by the heads)
#
#   [z, xBC, dt] = x W_in ;  xBC = silu(conv(xBC) + b_conv)
#   x [H, P], B [N], C [N] = split(xBC) ;  dt = softplus(dt + dt_bias) [H]
#   S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (A = -exp(A_log); per head,
#   y_t = S_t C_t + D x_t                         S [P, N] float32)
#   out = (RMSNorm(y * silu(z)) * scale) W_out    (the norm over all H*P)
#
# A request's state (float32, kept [N, H*P] a layer: ``_ssd_to_pool``) and
# conv state (the last K-1 inputs of the conv, [K-1, H*P + 2N]) live in its
# SLOT of the same two pools a KDA layer's do (``init_paged_kv_cache``,
# ``cfg.state_shapes``). Decode updates each LIVE row's state where it lies
# (``_ssd_state_update``: on TPU a Pallas kernel, the live rows' states
# through VMEM in phases; elsewhere XLA's gather, update and scatter of the
# rows' states); a prompt runs the recurrence in chunks of
# ``cfg.ssm_chunk`` tokens (``ssd_chunked``): inside a chunk the quadratic
# form (C B^T * L)(dt x), L_ij = exp(sum_{j<k<=i} dt_k A), between chunks
# the state. No matrix inverse (KDA has one).


def _init_mamba2(cfg: TransformerConfig, n: int, key, dtype, out_std):
    D, H, Pd, N, K = (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim,
                      cfg.ssm_state, cfg.ssm_conv_kernel)
    inner, conv_dim = H * Pd, H * Pd + 2 * N
    ks = jax.random.split(key, 5)

    def dense(k, shape, scale=cfg.init_std):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    # the family's own draws (mamba_ssm Mamba2): A in U(1, 16), dt
    # log-uniform over (1e-3, 1e-1) through the inverse of softplus, D 1, the
    # conv torch's default U(-K^-1/2, K^-1/2): a step's decay exp(dt A) lies
    # where a trained model's does
    a = jax.random.uniform(ks[2], (n, H), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(ks[3], (n, H), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    return {
        # z | x B C | dt, one projection
        "w_in": dense(ks[0], (n, D, inner + conv_dim + H)),
        # depthwise, causal: tap K-1 multiplies the current input
        "conv_w": jax.random.uniform(ks[1], (n, K, conv_dim), minval=-K ** -0.5,
                                     maxval=K ** -0.5).astype(dtype),
        "b_conv": jnp.zeros((n, conv_dim), dtype),
        "A_log": jnp.log(a).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "D": jnp.ones((n, H), dtype),
        "norm": {"scale": jnp.ones((n, inner), dtype)},
        "w_out": dense(ks[4], (n, inner, D), out_std),
    }


def _mamba2_project(cfg: TransformerConfig, x, lp, conv_ctx):
    """x [R, T, D], conv_ctx [R, K-1, conv_dim] the conv inputs before x ->
    (z [R, T, H*P] the gate, xs [R, T, H, P], Bm and Cm [R, T, N] in x's
    type, dt [R, T, H] float32 > 0, and the conv window [R, T+K-1,
    conv_dim] the next conv state is cut from)."""
    R, T, _ = x.shape
    H, Pd, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                   cfg.ssm_conv_kernel)
    inner = H * Pd
    f32 = jnp.float32
    with jax.named_scope("in_proj"):
        u = x @ _w(lp["w_in"], x)
    z, xbc, dt = jnp.split(u, [inner, 2 * inner + 2 * N], axis=-1)
    win = jnp.concatenate([conv_ctx.astype(xbc.dtype), xbc], axis=1)
    with jax.named_scope("short_conv"):
        w = lp["conv_w"].astype(f32)
        y = sum(win[:, j:j + T].astype(f32) * w[j] for j in range(K))
        y = jax.nn.silu(y + lp["b_conv"].astype(f32)).astype(x.dtype)
    xs, Bm, Cm = jnp.split(y, [inner, inner + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    return z, xs.reshape(R, T, H, Pd), Bm, Cm, dt, win


def _mamba2_output(cfg: TransformerConfig, y, z, lp):
    """y [R, T, H, P] float32, z [R, T, H*P] -> (RMSNorm(y * silu(z)) *
    scale) W_out: the gate BEFORE the norm, in float32, the norm over all
    H*P channels."""
    R, T = y.shape[:2]
    with jax.named_scope("gated_norm"):
        g = y.reshape(R, T, -1) * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
        g = (g * lp["norm"]["scale"].astype(jnp.float32)).astype(z.dtype)
    return g @ _w(lp["w_out"], g)


def _ssd_to_pool(S):
    """A state as the recurrence writes it, ``[..., H, P, N]``, as the pool
    keeps it: ``[..., N, H * P]``, N on sublanes and the ``(h, p)`` channels
    on lanes, so that the decode kernel's ``y = S C`` sums whole vregs."""
    *lead, H, Pd, N = S.shape
    return jnp.moveaxis(S.reshape(*lead, H * Pd, N), -1, -2)


def _ssd_from_pool(S, H: int):
    """``_ssd_to_pool``'s inverse: ``[..., N, H * P]`` -> ``[..., H, P, N]``."""
    *lead, N, HP = S.shape
    return jnp.moveaxis(S, -2, -1).reshape(*lead, H, HP // H, N)


def ssd_recurrent_step(S, x, dt, A, Bm, Cm, D):
    """The one-token update of one row: S [H, P, N] float32, x [H, P], dt,
    A, D [H], Bm, Cm [N], all float32 -> (y [H, P], new S)."""
    S = jnp.exp(dt * A)[:, None, None] * S \
        + (dt[:, None] * x)[:, :, None] * Bm[None, None, :]
    return jnp.sum(S * Cm[None, None, :], axis=-1) + D[:, None] * x, S


def ssd_chunked(S0, x, dt, A, Bm, Cm, D, chunk: int):
    """The recurrence over T tokens of one sequence in chunks of ``chunk``
    (the last one what is left of T): S0 [N, H*P] float32, the state AS THE
    POOL KEEPS IT (``_ssd_to_pool``), x [T, H, P], Bm, Cm [T, N] (their type
    is the matmuls': bf16 served, float32 in the tests), dt [T, H] and A, D
    [H] float32 -> (y [T, H, P] float32, S_T [N, H*P]). The state's two
    products are plain matmuls that way round, ``C S`` [c, N] x [N, H*P] and
    ``B^T (dt x)`` [N, c] x [c, H*P], and nothing is turned between the
    pool and the scan (turned after the scan, the compiler kept every state
    pool of the prefill program N-minor and copied each in and out: 4.7 GB
    of temporaries at the published sizes, PERF.md section 6, PR 44). A
    position of dt 0 is no position: its decay is 1 and it adds nothing (a
    bucket's padding). Inside a chunk, with G_i the cumulated log decay, y_i
    = sum_{j<=i} (C_i . B_j) e^(G_i - G_j) dt_j x_j + e^G_i S0 C_i + D x_i;
    S_C = e^G_C S0 + sum_j e^(G_C - G_j) dt_j x_j B_j^T (S as [P, N] a
    head). Decays are float32 and only ever taken between two positions j
    <= i of one chunk, so every exponent is <= 0. The heads lead every operand inside a chunk, and the
    scan stacks a chunk's y as the matmul leaves it, [H, C, P]: stacked [C,
    H, P], each chunk's y was written by a ``dynamic-update-slice`` of 134
    us on the TPU, three times the chunk's arithmetic (PERF.md section 6,
    PR 43)."""
    T, H, Pd = x.shape
    C = min(chunk, T)
    f32, mm = jnp.float32, x.dtype
    xh, dth = jnp.swapaxes(x, 0, 1), dt.T                    # [H, T, P] [H, T]

    def one_chunk(S, xs):
        x_c, b_c, c_c, dt_c = xs              # [H, c, P] [c, N] [c, N] [H, c]
        G = jnp.cumsum(dt_c * A[:, None], axis=1)            # [H, c] <= 0
        incl = jnp.tril(jnp.ones((G.shape[1],) * 2, bool))   # j <= i
        L = jnp.exp(jnp.where(incl, G[:, :, None] - G[:, None, :], -jnp.inf))
        cb = jnp.einsum("in,jn->ij", c_c, b_c, preferred_element_type=f32)
        dtx = dt_c[:, :, None] * x_c.astype(f32)             # [H, c, P]
        y = jnp.einsum("hij,hjp->hip", (cb * L).astype(mm), dtx.astype(mm),
                       preferred_element_type=f32)
        c = dtx.shape[1]
        sc = jnp.einsum("in,nk->ik", c_c.astype(f32), S, precision=_HI)
        y = y + jnp.exp(G)[:, :, None] * jnp.swapaxes(
            sc.reshape(c, H, Pd), 0, 1)
        to_end = jnp.exp(G[:, -1:] - G)                      # [H, c]
        w = jnp.swapaxes((to_end[:, :, None] * dtx).astype(mm), 0, 1)
        S = jnp.repeat(jnp.exp(G[:, -1]), Pd)[None, :] * S + jnp.einsum(
            "jn,jk->nk", b_c, w.reshape(c, H * Pd), preferred_element_type=f32)
        return S, y

    n, whole = T // C, T // C * C

    def cut(a, ax):              # the whole chunks of axis ``ax``, stacked
        a = jax.lax.slice_in_dim(a, 0, whole, axis=ax)
        return jnp.moveaxis(
            a.reshape(*a.shape[:ax], n, C, *a.shape[ax + 1:]), ax, 0)

    S, y = jax.lax.scan(one_chunk, S0,
                        (cut(xh, 1), cut(Bm, 0), cut(Cm, 0), cut(dth, 1)))
    y = jnp.moveaxis(y, 0, 1).reshape(H, whole, Pd)
    if whole < T:
        S, rest = one_chunk(S, (xh[:, whole:], Bm[whole:], Cm[whole:],
                                dth[:, whole:]))
        y = jnp.concatenate([y, rest], axis=1)
    return jnp.swapaxes(y, 0, 1) + D[:, None] * x.astype(f32), S


def _mamba2_prefill(cfg: TransformerConfig, x, lp, state, conv, slot,
                    n_valid, fresh):
    """The mixer over a prompt, or a chunk of one, of ONE request: x [1, T,
    D] (T a compile bucket, the first ``n_valid`` positions real), ``slot``
    the request's row of the layer in ``state`` [rows, N, H*P] and ``conv``
    [rows, K-1, conv_dim]. ``fresh`` (a bool, traced or not): the request's
    first piece starts from zero, whatever the slot's last holder left
    there. Padding has dt 0: the state after the bucket is the state after
    position ``n_valid``."""
    T = x.shape[1]
    f32 = jnp.float32
    z, xs, Bm, Cm, dt, win = _mamba2_project(cfg, x, lp,
                                             _slot_start(conv, slot, fresh))
    dt = jnp.where((jnp.arange(T) < n_valid)[None, :, None], dt, 0.0)
    S0 = _slot_start(state, slot, fresh)[0]
    with jax.named_scope("ssd_chunk_scan"):
        y, S = ssd_chunked(S0, xs[0], dt[0], -jnp.exp(lp["A_log"].astype(f32)),
                           Bm[0], Cm[0], lp["D"].astype(f32), cfg.ssm_chunk)
    state = jax.lax.dynamic_update_slice_in_dim(state, S[None], slot, axis=0)
    # the K-1 rows as a gather of rows: a dynamic slice along the time axis
    # made the TPU compiler keep the whole conv pool time-minor in this
    # program (3 padded to 128 lanes: 289 MB a pool copied in and out a
    # prefill, 2.6 GB of temporaries at the published sizes)
    tail = win[:, n_valid + jnp.arange(cfg.ssm_conv_kernel - 1)]
    conv = jax.lax.dynamic_update_slice_in_dim(
        conv, tail.astype(conv.dtype), slot, axis=0)
    return _mamba2_output(cfg, y[None], z, lp), state, conv


def _ssd_decode_update(state, x, dt, A, Bm, Cm, D, slots, base):
    """The plain-XLA form of a decode step's state update, and what the
    tests compare the kernel against: ``state`` [pool rows, N, H*P]
    float32, the rows' x [B, H, P], dt [B, H], Bm, Cm [B, N] float32 by
    row, row ``b`` at ``state[base + slots[b]]``: the rows' states gathered,
    ``ssd_recurrent_step`` a row, and scattered back. An idle row (slot 0)
    writes the dummy back as it was and its ``y`` is zero. Returns (y [B,
    H, P], the pool)."""
    rows = base + slots
    live = (slots != 0)[:, None, None]
    old = state[rows]
    y, new = jax.vmap(
        lambda S, xb, dtb, bb, cb: ssd_recurrent_step(S, xb, dtb, A, bb, cb, D)
    )(_ssd_from_pool(old, x.shape[1]), x, dt, Bm, Cm)
    new = jnp.where(live, _ssd_to_pool(new), old)
    return jnp.where(live, y, 0.0), state.at[rows].set(new)


def _ssd_state_update(cfg: TransformerConfig, state, x, dt, A, Bm, Cm, D,
                      slots, base):
    """A decode step's state update in one of two forms of the same float32
    arithmetic, chosen as the paged kernel is (``_use_flash``: the backend
    and the shape, never the model), both over the LIVE rows' slots alone:

    * ``mamba2_kernel`` (TPU): ``ops/pallas/mamba2_decode_update.py``, the
      live rows' states read once and written once where they lie, in
      phases of one direction at a time (``state_phases.py``, the KDA
      kernel's schedule);
    * ``slot_gather`` (elsewhere, and shapes the kernel cannot tile):
      ``_ssd_decode_update``, the rows' states gathered, updated and
      scattered back by XLA."""
    out = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.mamba2_decode_update import \
            mamba2_decode_update
        out = mamba2_decode_update(state, x, dt, A, Bm, Cm, slots, base)
    dispatch.record("ssd_decode", "slot_gather" if out is None else "mamba2_kernel",
                    f"B={x.shape[0]} H={x.shape[1]} P={x.shape[2]} "
                    f"N={Bm.shape[1]}")
    if out is None:
        return _ssd_decode_update(state, x, dt, A, Bm, Cm, D, slots, base)
    y, state = out
    # D x over the H * P channels as the kernel leaves y, one lane row a
    # row (laid out [H, P], each operand would be turned and turned back)
    B, H, Pd = x.shape
    dx = jnp.repeat(D, Pd) * x.reshape(B, H * Pd)
    y = y.reshape(B, H * Pd) + jnp.where((slots != 0)[:, None], dx, 0.0)
    return y.reshape(B, H, Pd), state


def _mamba2_decode(cfg: TransformerConfig, x, lp, state, conv, base, slots):
    """One token a row: x [B, 1, D], ``slots`` [B] each row's state slot
    (0, the dummy, for an inactive row), the layer's slots from row ``base``
    of ``state`` [rows, N, H*P] and ``conv``."""
    f32 = jnp.float32
    rows = base + slots
    z, xs, Bm, Cm, dt, win = _mamba2_project(cfg, x, lp, conv[rows])
    conv = conv.at[rows].set(win[:, 1:].astype(conv.dtype))
    with jax.named_scope("ssd_state_update"):
        y, state = _ssd_state_update(
            cfg, state, xs[:, 0].astype(f32), dt[:, 0],
            -jnp.exp(lp["A_log"].astype(f32)), Bm[:, 0].astype(f32),
            Cm[:, 0].astype(f32), lp["D"].astype(f32), slots, base)
    return _mamba2_output(cfg, y[:, None], z, lp), state, conv



# --------------------------------------------------------------------- #
# The gated short convolution (LFM2's ``conv`` layers)
#
#   [B, C, x~] = split3(x W_in) ;  u = B * x~
#   c_t = sum_j w[j] u_(t-K+1+j)     (depthwise, causal, K = cfg.conv_kernel
#   y = (C * c) W_out                 taps, no bias and no activation)
#
# ALL a request keeps is its conv state, the last K - 1 values of u in
# the pool's type ([K-1, d_model] a layer, in its SLOT of ``conv``): no
# recurrent state (``_short_conv_shapes``'s None, for which
# ``init_paged_kv_cache`` allocates nothing; the functions below take and
# return None in the state's place). u is rounded to the activations' type
# before the conv in a prompt too, so that a token's conv reads the same
# values whether its neighbours came by the prompt or by the slot; the
# taps and the gate are float32. A decode step gathers the rows' K - 1
# values, computes the K taps and writes the newer K - 1 back: plain XLA at
# 8 KB a row and layer (scope ``state``, under the layer's ``short_conv``).


def _init_short_conv(cfg: TransformerConfig, n: int, key, dtype, out_std):
    D, K = cfg.d_model, cfg.conv_kernel
    ks = jax.random.split(key, 3)

    def dense(k, shape, scale=cfg.init_std):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    return {
        # B | C | x~, one projection
        "w_in": dense(ks[0], (n, D, 3 * D)),
        # depthwise, causal: tap K-1 multiplies the current input; torch's
        # default draw of a conv, U(-K^-1/2, K^-1/2)
        "conv_w": jax.random.uniform(ks[1], (n, K, D), minval=-K ** -0.5,
                                     maxval=K ** -0.5).astype(dtype),
        "w_out": dense(ks[2], (n, D, D), out_std),
    }


def _short_conv_project(x, lp):
    """x [R, T, D] -> (u = B * x~ in x's type, the gate C), [R, T, D]."""
    with jax.named_scope("in_proj"):
        b, c, xs = jnp.split(x @ _w(lp["w_in"], x), 3, axis=-1)
    return b * xs, c


def _short_conv_taps(win, lp, T: int):
    """win [R, T+K-1, D], the conv's inputs with the K - 1 before them ->
    the conv's T outputs, float32."""
    w = lp["conv_w"].astype(jnp.float32)
    return sum(win[:, j:j + T].astype(jnp.float32) * w[j]
               for j in range(w.shape[0]))


def _short_conv_output(c, y, lp):
    """The gate c [R, T, D] times the conv's float32 output y, through W_out."""
    g = (c.astype(jnp.float32) * y).astype(c.dtype)
    with jax.named_scope("out_proj"):
        return g @ _w(lp["w_out"], g)


def _short_conv_prefill(cfg: TransformerConfig, x, lp, state, conv, slot,
                        n_valid, fresh):
    """The mixer over a prompt, or a chunk of one, of ONE request: x [1, T,
    D] (T a compile bucket, the first ``n_valid`` positions real), ``slot``
    the request's row of the layer in ``conv`` [rows, K-1, D]; ``state`` is
    None. ``fresh`` (a bool, traced or not): the request's first piece
    starts from zeros (the conv's left padding), whatever the slot's last
    holder left there. The slot takes the last K - 1 inputs of the VALID
    positions: a prompt of one token leaves a zero beside it."""
    T, K = x.shape[1], cfg.conv_kernel
    dispatch.record("mixer", "short_conv", f"T={T} D={x.shape[2]} K={K}")
    u, c = _short_conv_project(x, lp)
    with jax.named_scope("state"):
        win = jnp.concatenate(
            [_slot_start(conv, slot, fresh).astype(u.dtype), u], axis=1)
        y = _short_conv_taps(win, lp, T)
        # rows by a gather, as the Mamba-2 prefill takes them (a dynamic
        # slice along time turns the pool time-minor on the TPU)
        tail = win[:, n_valid + jnp.arange(K - 1)]
        conv = jax.lax.dynamic_update_slice_in_dim(
            conv, tail.astype(conv.dtype), slot, axis=0)
    return _short_conv_output(c, y, lp), state, conv


def _short_conv_decode(cfg: TransformerConfig, x, lp, state, conv, base,
                       slots):
    """One token a row: x [B, 1, D], ``slots`` [B] each row's slot (0, the
    dummy, for an inactive row), the layer's slots from row ``base`` of
    ``conv`` [rows, K-1, D]; ``state`` is None."""
    dispatch.record("mixer", "short_conv",
                    f"B={x.shape[0]} D={x.shape[2]} K={cfg.conv_kernel}")
    rows = base + slots
    u, c = _short_conv_project(x, lp)
    with jax.named_scope("state"):
        win = jnp.concatenate([conv[rows].astype(u.dtype), u], axis=1)
        y = _short_conv_taps(win, lp, 1)
        conv = conv.at[rows].set(win[:, 1:].astype(conv.dtype))
    return _short_conv_output(c, y, lp), state, conv


# --------------------------------------------------------------------- #
# One record a kind: all that ``models/transformer.py`` knows of it

def _kda_check(cfg: TransformerConfig):
    if not (cfg.lin_heads and cfg.lin_head_dim):
        raise ValueError("a linear_attention layer needs lin_heads and "
                         "lin_head_dim")


def _kda_shapes(cfg: TransformerConfig):
    H, dk = cfg.lin_heads, cfg.lin_head_dim
    return (H, dk, dk), (KDA_CONV_KERNEL - 1, 3 * H * dk)


def _mamba2_check(cfg: TransformerConfig):
    if not (cfg.ssm_heads and cfg.ssm_head_dim and cfg.ssm_state):
        raise ValueError("a mamba2 layer needs ssm_heads, ssm_head_dim and "
                         "ssm_state")


def _mamba2_shapes(cfg: TransformerConfig):
    # the state N first, the H * P channels on the lanes: the decode
    # kernel's sums over N are sums of whole vregs
    H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return (N, H * Pd), (cfg.ssm_conv_kernel - 1, H * Pd + 2 * N)


def _short_conv_check(cfg: TransformerConfig):
    if cfg.conv_kernel < 2:
        raise ValueError("a short_conv layer needs conv_kernel >= 2 taps")


def _short_conv_shapes(cfg: TransformerConfig):
    # no recurrent state: the conv's last K - 1 inputs are all it keeps
    return None, (cfg.conv_kernel - 1, cfg.d_model)


class StateMixer(NamedTuple):
    key: str            # of the mixer's parameters in a layer group
    check: Callable     # (cfg): ValueError where cfg lacks the kind's sizes
    init: Callable      # (cfg, n, key, dtype, out_std) -> n stacked layers'
    shapes: Callable    # (cfg) -> a request's state's (None: the kind keeps
    #                     none, and its functions are handed None for
    #                     ``state``) and conv state's
    prefill: Callable   # (cfg, xn, lp, state, conv, row, n_valid, fresh)
    decode: Callable    # (cfg, xn, lp, state, conv, row0, state_slots);
    #                     both -> (the mixer's output, state, conv)


STATE_MIXERS = {
    LINEAR_ATTENTION: StateMixer("lin", _kda_check, _init_linear_attention,
                                 _kda_shapes, _kda_prefill, _kda_decode),
    MAMBA2: StateMixer("ssm", _mamba2_check, _init_mamba2, _mamba2_shapes,
                       _mamba2_prefill, _mamba2_decode),
    SHORT_CONV: StateMixer("conv", _short_conv_check, _init_short_conv,
                           _short_conv_shapes, _short_conv_prefill,
                           _short_conv_decode),
}
