"""Quantization kernels: grouped fake-quant + TPU stochastic rounding.

TPU-native named op for the reference's quantizer family
(``csrc/quantization/fake_quantizer.cu`` — ``ds_quantize_*`` /
``ds_sr_quantize_*`` grouped sym/asym fake quantization with
stochastic-rounding variants; binding ``csrc/quantization/pt_binding.cpp``).

Deterministic rounding is pure elementwise math — XLA fuses it, no kernel
needed (:func:`ds_quantize` / :func:`ds_quantize_asym`). Stochastic
rounding is where the hardware matters: the Pallas kernel draws uniform
noise from the on-core PRNG (``pltpu.prng_seed`` / ``prng_random_bits``)
right in VMEM — no HBM round-trip for a noise tensor the size of the
input, which is what an XLA-level ``jax.random.uniform`` would cost.
Off-TPU the same math runs with ``jax.random`` (bit-exact distribution
up to the underlying generator).

Group semantics mirror the reference: the tensor is flattened to
``[groups, -1]`` and each group gets one scale (sym) or scale+offset
(asym).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import dispatch

_LANES = 128
_ROWS = 8


def _group_view(x, groups: int):
    flat = x.astype(jnp.float32).reshape(groups, -1)
    L = flat.shape[1]
    pad = (-L) % _LANES
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    rpad = (-groups) % _ROWS
    if rpad:
        flat = jnp.pad(flat, ((0, rpad), (0, 0)))
    return flat, L, pad, rpad


def _sym_scale(flat, bits: int):
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(flat), axis=1, keepdims=True) / qmax
    return jnp.where(scale == 0, 1.0, scale), qmax


# ------------------------------------------------------------------ #
# deterministic (round-to-nearest) — XLA fuses this; no kernel needed

def ds_quantize(x, groups: int, bits: int = 8):
    """Grouped symmetric fake quantization (reference ``ds_quantize_fp32``)."""
    flat, L, pad, rpad = _group_view(x, groups)
    scale, qmax = _sym_scale(flat, bits)
    q = jnp.clip(jnp.round(flat / scale), -qmax - 1, qmax)
    out = (q * scale)[:groups, :L] if (pad or rpad) else q * scale
    return out.reshape(x.shape).astype(x.dtype)


def ds_quantize_asym(x, groups: int, bits: int = 8):
    """Grouped asymmetric fake quantization (reference ``ds_quantize_asym``)."""
    flat = x.astype(jnp.float32).reshape(groups, -1)
    lo = jnp.min(flat, axis=1, keepdims=True)
    hi = jnp.max(flat, axis=1, keepdims=True)
    levels = 2.0**bits - 1
    scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
    q = jnp.clip(jnp.round((flat - lo) / scale), 0, levels)
    return (q * scale + lo).reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------------ #
# stochastic rounding — Pallas kernel drawing noise from the core PRNG

def _sr_kernel(seed_ref, x_ref, scale_ref, o_ref, *, qmax, n_cols):
    i, j = pl.program_id(0), pl.program_id(1)
    # mix the user seed (odd multiplicative hash, int32 wraparound is fine)
    # so seed=step streams don't collide with adjacent blocks' streams at
    # neighbouring steps
    pltpu.prng_seed(seed_ref[0] * 1000003 + i * n_cols + j)
    bits = pltpu.prng_random_bits(x_ref.shape)
    # prng_random_bits is int32: mask to the low 24 bits (non-negative
    # regardless of sign) → uniform [0, 1). An arithmetic >> of negative
    # draws would put u in [-0.5, 0) and bias every element low by half a
    # step.
    u = (bits & 0x00FFFFFF).astype(jnp.float32) * (1.0 / 16777216.0)
    scaled = x_ref[:] / scale_ref[:]
    q = jnp.clip(jnp.floor(scaled + u), -qmax - 1.0, qmax)
    o_ref[:] = q * scale_ref[:]


@functools.partial(jax.jit, static_argnames=("bits", "col_block", "interpret"))
def _sr_call(flat, scale, seed, *, bits, col_block, interpret):
    G, L = flat.shape
    qmax = 2.0 ** (bits - 1) - 1
    grid = (G // _ROWS, L // col_block)
    out = pl.pallas_call(
        functools.partial(_sr_kernel, qmax=qmax, n_cols=grid[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_ROWS, col_block), lambda i, j, sc: (i, j)),
                pl.BlockSpec((_ROWS, 1), lambda i, j, sc: (i, 0)),
            ],
            out_specs=pl.BlockSpec((_ROWS, col_block), lambda i, j, sc: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((G, L), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.int32).reshape(1), flat, scale)
    return out


def ds_sr_quantize(x, groups: int, bits: int = 8, seed=0,
                   interpret: Optional[bool] = None):
    """Grouped symmetric fake quantization with STOCHASTIC rounding
    (reference ``ds_sr_quantize_fp32``): values round up with probability
    equal to their fractional position, so quantization error is unbiased
    in expectation — the property 1-bit/low-precision training relies on.
    """
    # the core-PRNG primitives have no interpret-mode lowering, so the
    # kernel runs only where it compiles: on TPU (interpret=False forces
    # a compile attempt for AOT checks)
    use_kernel = dispatch.on_tpu() if interpret is None else not interpret
    dispatch.record("kernel/sr_quantize", "compiled" if use_kernel else "jnp")
    flat, L, pad, rpad = _group_view(x, groups)
    scale, qmax = _sym_scale(flat[:groups] if rpad else flat, bits)
    if rpad:
        scale = jnp.pad(scale, ((0, rpad), (0, 0)), constant_values=1.0)
    if use_kernel:
        col_block = next(b for b in (1024, 512, 256, _LANES)
                         if flat.shape[1] % b == 0)
        out = _sr_call(flat, scale, seed, bits=bits, col_block=col_block,
                       interpret=False)
    else:
        u = jax.random.uniform(jax.random.key(seed), flat.shape)
        q = jnp.clip(jnp.floor(flat / scale + u), -qmax - 1, qmax)
        out = q * scale
    out = out[:groups, :L] if (pad or rpad) else out
    return out.reshape(x.shape).astype(x.dtype)


def ds_sr_quantize_asym(x, groups: int, bits: int = 8, seed=0):
    """Asymmetric stochastic-rounding fake quantization (jnp form; the sym
    kernel above is the hot path the reference accelerates)."""
    flat = x.astype(jnp.float32).reshape(groups, -1)
    lo = jnp.min(flat, axis=1, keepdims=True)
    hi = jnp.max(flat, axis=1, keepdims=True)
    levels = 2.0**bits - 1
    scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
    u = jax.random.uniform(jax.random.key(seed), flat.shape)
    q = jnp.clip(jnp.floor((flat - lo) / scale + u), 0, levels)
    return (q * scale + lo).reshape(x.shape).astype(x.dtype)
