"""Pallas fused Adam — the TPU-native named op for the reference's
multi-tensor fused Adam (``csrc/adam/multi_tensor_adam.cu:163``,
``csrc/adam/fused_adam_frontend.cpp``; Python wrapper
``deepspeed/ops/adam/fused_adam.py``).

The multi-tensor-apply trick on GPU exists to amortise kernel-launch
overhead and make the optimizer bandwidth-bound: one kernel walks chunk
lists covering every parameter tensor. The TPU-idiomatic equivalent is a
single Pallas kernel over ONE flat buffer per optimizer slot: the engine
already keeps flat param/moment pytrees, so we flatten leaves once
(``ravel``/concat happens inside the same jit and fuses to pure layout),
then stream p/g/m/v through VMEM in (8·SUBLANES, 128)-tiles — every
element is read once and written once, which is the whole point of the
fused op (4 reads + 3 writes per element, no intermediate HBM traffic).

Two call surfaces:

* :func:`fused_adam_step` — raw kernel on 1-D flat arrays; what the op
  registry's ``FusedAdamBuilder`` loads.
* :func:`fused_adam` — optax ``GradientTransformationExtraArgs`` drop-in
  (config name ``FusedAdam``) whose ``update`` runs the kernel per leaf
  in ``emit="update"`` mode (the kernel writes the update direction
  directly — no ``new_p - p`` reconstruction, no extra pass over p, no
  bf16 cancellation), so the engine/ZeRO sharding machinery treats it
  like any other optimizer.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import dispatch

# (rows, 128) f32 tile per grid step: 256*128*4B = 128 KiB per operand —
# 7 operands ≈ 0.9 MiB of VMEM, far under budget, big enough to saturate
# HBM bandwidth.
_BLOCK_ROWS = 256
_LANES = 128
_BLOCK = _BLOCK_ROWS * _LANES


def _adam_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                 *, b1, b2, eps, wd, adam_w, emit):
    """One (rows, 128) tile: full Adam step, everything in fp32 registers.

    sc_ref (SMEM, f32[3]): [lr, 1-b1^t, 1-b2^t] — the only per-step scalars.
    ``emit="param"`` writes ``p - lr*upd``; ``emit="update"`` writes the
    descent direction ``upd`` itself (fp32) for callers that apply it
    elsewhere (e.g. the engine's ``p - lr*u`` with a scheduled lr).
    """
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    if not adam_w and wd:
        # reference Adam mode: L2 folded into the gradient before moments
        g = g + wd * p
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * (g * g)
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w and wd:
        upd = upd + wd * p
    if emit == "param":
        po_ref[:] = (p - lr * upd).astype(po_ref.dtype)
    else:
        po_ref[:] = upd
    mo_ref[:] = m
    vo_ref[:] = v


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "adam_w",
                                             "emit", "interpret"))
def _fused_adam_flat(p, g, m, v, lr, bc1, bc2, *, b1, b2, eps, wd, adam_w,
                     emit, interpret):
    n = p.shape[0]
    pad = (-n) % _BLOCK
    padded = n + pad

    def prep(x):
        x = jnp.pad(x, (0, pad)) if pad else x
        return x.reshape(padded // _LANES, _LANES)

    rows = padded // _LANES
    grid = (rows // _BLOCK_ROWS,)
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i, sc: (i, 0))
    scalars = jnp.stack([lr, bc1, bc2]).astype(jnp.float32)
    kern = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                             adam_w=adam_w, emit=emit)
    out_dtype = p.dtype if emit == "param" else jnp.float32
    po, mo, vo = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec] * 4,
            out_specs=[spec] * 3,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), out_dtype),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(scalars, prep(p), prep(g), prep(m.astype(jnp.float32)),
      prep(v.astype(jnp.float32)))

    def unprep(x):
        flat = x.reshape(-1)
        return flat[:n] if pad else flat

    return unprep(po), unprep(mo), unprep(vo)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "adam_w",
                                             "emit"))
def _jnp_adam_flat(p, g, m, v, lr, bc1, bc2, *, b1, b2, eps, wd, adam_w, emit):
    """Same math as the kernel in plain jnp — the off-TPU fallback (XLA:CPU
    fuses this fine; Pallas interpret mode is only for kernel unit tests)."""
    g = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    if not adam_w and wd:
        g = g + wd * pf
    m = b1 * m.astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v.astype(jnp.float32) + (1.0 - b2) * (g * g)
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w and wd:
        upd = upd + wd * pf
    if emit == "param":
        return (pf - lr * upd).astype(p.dtype), m, v
    return upd, m, v


def _run_adam(p, g, m, v, *, step, lr, b1, b2, eps, weight_decay, adam_w_mode,
              bias_correction, interpret, emit):
    # interpret=None: compiled kernel on TPU, jnp math elsewhere.
    # interpret=True: kernel in interpret mode (off-TPU only).
    # interpret=False: compiled kernel (any backend — caller's risk off-TPU).
    use_kernel = interpret is not None or dispatch.on_tpu()
    step = jnp.asarray(step, jnp.float32)
    if bias_correction:
        bc1 = 1.0 - jnp.asarray(b1, jnp.float32) ** step
        bc2 = 1.0 - jnp.asarray(b2, jnp.float32) ** step
    else:
        bc1 = jnp.float32(1.0)
        bc2 = jnp.float32(1.0)
    kw = dict(b1=float(b1), b2=float(b2), eps=float(eps),
              wd=float(weight_decay), adam_w=bool(adam_w_mode), emit=emit)
    lr = jnp.asarray(lr, jnp.float32)
    if not use_kernel:
        dispatch.record("kernel/fused_adam", "jnp")
        return _jnp_adam_flat(p, g, m, v, lr, bc1, bc2, **kw)
    return _fused_adam_flat(
        p, g, m, v, lr, bc1, bc2,
        interpret=dispatch.resolve_interpret("fused_adam", interpret), **kw)


def fused_adam_step(p, g, m, v, *, step, lr, b1=0.9, b2=0.999, eps=1e-8,
                    weight_decay=0.0, adam_w_mode=True, bias_correction=True,
                    interpret: Optional[bool] = None):
    """Single fused Adam step on flat 1-D buffers.

    Returns ``(new_p, new_m, new_v)``. ``step`` is the 1-based step count
    (traced scalar is fine); ``lr`` may be a traced scalar so schedules stay
    inside jit. Moments are kept in fp32 regardless of param dtype.

    ``interpret``: None (default) = compiled Pallas kernel on TPU, identical
    jnp math elsewhere; True = kernel in interpret mode (kernel unit tests);
    False = force the compiled kernel on any backend.
    """
    return _run_adam(p, g, m, v, step=step, lr=lr, b1=b1, b2=b2, eps=eps,
                     weight_decay=weight_decay, adam_w_mode=adam_w_mode,
                     bias_correction=bias_correction, interpret=interpret,
                     emit="param")


class FusedAdamState(NamedTuple):
    count: jax.Array  # int32 step counter
    mu: optax.Updates
    nu: optax.Updates


def fused_adam(learning_rate=None, b1=0.9, b2=0.999, eps=1e-8,
               weight_decay=0.0, adam_w_mode=True, bias_correction=True,
               interpret: Optional[bool] = None) -> optax.GradientTransformationExtraArgs:
    """Optax-compatible wrapper: kernel per leaf in ``emit="update"`` mode.

    ``learning_rate=None`` means "LR injected by the engine": the transform
    returns the POSITIVE descent direction u (the engine applies
    ``p - lr*u``, keeping the schedule inside jit — see
    ``runtime/engine.py _apply_update``). With a concrete ``learning_rate``
    it returns standard optax deltas ``-lr*u`` (``apply_updates`` adds them).
    """

    def init(params):
        # moments keep the PARAM shapes (fp32) so ZeRO/TP sharding rules and
        # checkpoint layouts treat them like any optax state; the kernel's
        # ravel is a pure layout op inside jit
        zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        return FusedAdamState(count=jnp.zeros((), jnp.int32),
                              mu=zeros, nu=jax.tree.map(jnp.copy, zeros))

    def update(grads, state, params=None, **extra):
        if params is None:
            raise ValueError("fused_adam requires params (fused update kernel)")
        count = state.count + 1
        leaves_p, treedef = jax.tree.flatten(params)
        leaves_g = treedef.flatten_up_to(grads)
        leaves_m = treedef.flatten_up_to(state.mu)
        leaves_v = treedef.flatten_up_to(state.nu)
        out_u, out_m, out_v = [], [], []
        for p, g, m, v in zip(leaves_p, leaves_g, leaves_m, leaves_v):
            u, nm, nv = _run_adam(
                p.reshape(-1), g.reshape(-1), m.reshape(-1), v.reshape(-1),
                step=count, lr=0.0,  # lr unused in emit="update"
                b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
                adam_w_mode=adam_w_mode, bias_correction=bias_correction,
                interpret=interpret, emit="update")
            u = u.reshape(p.shape)
            if learning_rate is not None:
                # schedules (callables of the step count) resolve like optax
                # optax evaluates schedules at the 0-based pre-increment
                # count; our count is 1-based
                lr_t = (learning_rate(count - 1) if callable(learning_rate)
                        else learning_rate)
                u = (-lr_t * u).astype(p.dtype)
            out_u.append(u)
            out_m.append(nm.reshape(p.shape))
            out_v.append(nv.reshape(p.shape))
        updates = jax.tree.unflatten(treedef, out_u)
        new_state = FusedAdamState(count=count,
                                   mu=jax.tree.unflatten(treedef, out_m),
                                   nu=jax.tree.unflatten(treedef, out_v))
        return updates, new_state

    return optax.GradientTransformationExtraArgs(init, update)
