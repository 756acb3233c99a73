"""Loss scaling for fp16 training.

Reference parity: ``deepspeed/runtime/fp16/loss_scaler.py`` —
``LossScaler`` (static) and ``DynamicLossScaler`` (grow/backoff with
hysteresis). Rebuilt as a pure state-transition so the overflow check and
scale update live *inside* the compiled train step (reference "hard part"
noted in SURVEY.md §7: skip-update semantics without a host round-trip).

State is a small pytree; ``update(state, overflow)`` returns the next state.
The train step uses ``jax.lax.cond`` on ``overflow`` to skip the optimizer
update for that step, exactly matching the reference's skip semantics.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LossScaleState:
    loss_scale: jnp.ndarray        # f32 scalar
    good_steps: jnp.ndarray        # i32 scalar: consecutive non-overflow steps
    hysteresis: jnp.ndarray        # i32 scalar: remaining tolerated overflows
    # static config: aux data of the pytree, not traced leaves
    init_scale: float = dataclasses.field(default=2.0**16, metadata={"static": True})
    scale_window: int = dataclasses.field(default=1000, metadata={"static": True})
    min_scale: float = dataclasses.field(default=1.0, metadata={"static": True})
    delayed_shift: int = dataclasses.field(default=2, metadata={"static": True})
    scale_factor: float = dataclasses.field(default=2.0, metadata={"static": True})
    dynamic: bool = dataclasses.field(default=True, metadata={"static": True})

    def _replace(self, **kwargs) -> "LossScaleState":
        return dataclasses.replace(self, **kwargs)


def make_loss_scale_state(init_scale: float = 2.0**16,
                          scale_window: int = 1000,
                          min_scale: float = 1.0,
                          delayed_shift: int = 2,
                          scale_factor: float = 2.0,
                          dynamic: bool = True) -> LossScaleState:
    return LossScaleState(
        loss_scale=jnp.asarray(init_scale, jnp.float32),
        good_steps=jnp.asarray(0, jnp.int32),
        hysteresis=jnp.asarray(delayed_shift, jnp.int32),
        init_scale=init_scale,
        scale_window=scale_window,
        min_scale=min_scale,
        delayed_shift=delayed_shift,
        scale_factor=scale_factor,
        dynamic=dynamic,
    )


def has_overflow(grads) -> jnp.ndarray:
    """True if any grad element is NaN/Inf (reference CheckOverflow,
    runtime/utils.py:171 — here a single fused reduction instead of a
    per-tensor loop + collective)."""
    import jax

    leaves = jax.tree.leaves(grads)
    if not leaves:
        return jnp.asarray(False)
    flat = [jnp.sum(jnp.abs(leaf.astype(jnp.float32))) for leaf in leaves]
    total = sum(flat)
    return ~jnp.isfinite(total)


def count_nonfinite(tree) -> jnp.ndarray:
    """Total non-finite elements across the pytree (fp32 scalar) — the
    counting twin of :func:`has_overflow`, feeding the health sentinels:
    where ``has_overflow`` answers "skip this step?", this answers "how
    bad is it?" for the anomaly report."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    return sum(jnp.sum(~jnp.isfinite(l.astype(jnp.float32))).astype(jnp.float32)
               for l in leaves)


def update(state: LossScaleState, overflow) -> LossScaleState:
    """Next scaler state after a step that did/didn't overflow."""
    if not state.dynamic:
        return state
    overflow = jnp.asarray(overflow)

    # overflow: consume hysteresis; only back off once hysteresis exhausted
    new_hyst = jnp.where(overflow, jnp.maximum(state.hysteresis - 1, 0), state.hysteresis)
    backoff = overflow & (state.hysteresis <= 1)
    scale_after_backoff = jnp.maximum(state.loss_scale / state.scale_factor, state.min_scale)

    # growth: scale_window consecutive good steps
    good = jnp.where(overflow, 0, state.good_steps + 1)
    grow = (~overflow) & (good >= state.scale_window)
    new_scale = jnp.where(backoff, scale_after_backoff,
                          jnp.where(grow, state.loss_scale * state.scale_factor, state.loss_scale))
    good = jnp.where(grow, 0, good)
    new_hyst = jnp.where(~overflow & (state.good_steps > 0), jnp.asarray(state.delayed_shift, jnp.int32), new_hyst)

    return state._replace(loss_scale=new_scale, good_steps=good.astype(jnp.int32),
                          hysteresis=new_hyst.astype(jnp.int32))


# Reference-shaped class wrappers --------------------------------------- #

class LossScalerBase:

    def __init__(self, cur_scale: float):
        self.cur_scale = cur_scale
        self.dynamic = False

    @property
    def loss_scale(self):
        return self.cur_scale

    def scale_gradient(self, module, grad_in, grad_out):
        return tuple(self.loss_scale * g for g in grad_in)

    def update_scale(self, overflow):
        pass

    def backward(self, loss, retain_graph=False):
        return loss * self.loss_scale


class LossScaler(LossScalerBase):
    """Static loss scale."""

    def __init__(self, scale: float = 1.0):
        super().__init__(scale)


class DynamicLossScaler(LossScalerBase):
    """Host-side mirror of the in-step dynamic scaler (for reference-shaped
    access patterns and tests)."""

    def __init__(self, init_scale: float = 2.0**32, scale_factor: float = 2.0, scale_window: int = 1000,
                 min_scale: float = 1.0, delayed_shift: int = 1, consecutive_hysteresis: bool = False,
                 raise_error_at_min_scale: bool = True):
        super().__init__(init_scale)
        self.cur_iter = 0
        self.last_overflow_iter = -1
        self.scale_factor = scale_factor
        self.scale_window = scale_window
        self.min_scale = min_scale
        self.delayed_shift = delayed_shift
        self.cur_hysteresis = delayed_shift
        self.consecutive_hysteresis = consecutive_hysteresis
        self.raise_error_at_min_scale = raise_error_at_min_scale
        self.dynamic = True

    def update_scale(self, overflow: bool):
        if overflow:
            if self.delayed_shift == 1 or self.cur_hysteresis == 1:
                if self.cur_scale == self.min_scale and self.raise_error_at_min_scale:
                    raise Exception("Current loss scale already at minimum - cannot decrease scale anymore. "
                                    "Exiting run.")
                self.cur_scale = max(self.cur_scale / self.scale_factor, self.min_scale)
            else:
                self.cur_hysteresis -= 1
            self.last_overflow_iter = self.cur_iter
        else:
            if self.consecutive_hysteresis:
                self.cur_hysteresis = self.delayed_shift
            if (self.cur_iter - self.last_overflow_iter) % self.scale_window == 0:
                if not self.consecutive_hysteresis:
                    self.cur_hysteresis = self.delayed_shift
                self.cur_scale *= self.scale_factor
        self.cur_iter += 1


def CreateLossScaler(dtype, static_loss_scale, dynamic_scaling, dynamic_loss_args):
    """Factory mirroring the reference's loss_scaler.CreateLossScaler."""
    import jax.numpy as jnp_
    if dtype == jnp_.float16 and dynamic_scaling:
        kwargs = dynamic_loss_args or {}
        return DynamicLossScaler(**kwargs)
    loss_scale_value = static_loss_scale if dtype == jnp_.float16 else 1.0
    return LossScaler(scale=loss_scale_value)
