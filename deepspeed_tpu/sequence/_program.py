"""Shared shard_map program builder for the SP attention implementations.

ring.py and ulysses.py differ only in the per-shard body; the cached
(mesh, static-args) → jitted shard_map program machinery lives here once.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.utils.jax_compat import shard_map


@functools.lru_cache(maxsize=128)
def _cached_program(local_fn: Callable, mesh, axis: str, causal: bool, has_mask: bool,
                    has_alibi: bool, scale: Optional[float], knobs: tuple = ()):
    """Build + jit the shard_map program once per (body, mesh, static-arg)
    combo so eager callers hit the jit cache instead of recompiling.
    ``knobs`` carries the caller's module-level tuning globals (chunk sizes,
    kernel toggles) purely as cache-key salt: the body reads the globals at
    trace time, so keying on their current values makes mutating a knob
    after first compile take effect instead of silently hitting a stale
    program."""
    qkv_spec = P(None, axis, None, None)
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    if has_mask:
        in_specs.append(P(None, axis))
    if has_alibi:
        in_specs.append(P(None))  # replicated [H] slopes

    def body(*xs):
        qq, kk, vv = xs[:3]
        rest = list(xs[3:])
        mb = rest.pop(0) if has_mask else None
        slopes = rest.pop(0) if has_alibi else None
        return local_fn(qq, kk, vv, axis=axis, causal=causal, mask_bias=mb,
                        alibi_slopes=slopes, scale=scale)

    fn = shard_map(body, mesh=mesh, in_specs=tuple(in_specs), out_specs=qkv_spec,
                       axis_names={axis}, check_vma=False)
    # partial-auto shard_map must run under jit; nested jit inlines when traced
    return jax.jit(fn)


def run_sp_program(local_fn: Callable, q, k, v, *, mesh, axis: str, causal: bool,
                   mask_bias, alibi_slopes, scale: Optional[float], knobs: tuple = ()):
    """Dispatch q/k/v (+ optional mask/slopes) through the cached shard_map
    program built around ``local_fn``. ``knobs``: the caller's current
    tuning-global values (cache-key salt, see _cached_program)."""
    args = [q, k, v]
    if mask_bias is not None:
        args.append(mask_bias)
    if alibi_slopes is not None:
        args.append(jnp.asarray(alibi_slopes))
    fn = _cached_program(local_fn, mesh, axis, causal, mask_bias is not None,
                         alibi_slopes is not None, scale, knobs)
    return fn(*args)


def resolve_use_flash(override) -> bool:
    """Shared auto-detection for the SP bodies' Pallas-kernel toggles
    (ring.RING_USE_FLASH / ulysses.ULYSSES_USE_FLASH): explicit override
    wins, else kernel on TPU, XLA streaming core elsewhere."""
    if override is not None:
        return bool(override)
    return dispatch.on_tpu()
