"""The Mamba-2 decode-update kernel (interpret mode on the CPU tier) against
``ssd_recurrent_step`` in float32: the live rows' ``y`` and new state to 1e-6
of their largest value, every pool row no live row holds BIT-identical
afterwards (the dummy among them: the kernel issues no copy for an inactive
row), an inactive row's ``y`` zero. One parametrised test, a case each state
the serving loop puts it in and each place the live rows can end in the copy
schedule's phases (``state_phases.py``); and the model's dispatch between
the kernel and its plain-XLA twin. The pool is kept ``[rows, N, H * P]``
(``SM._ssd_to_pool``); the recurrence takes ``[H, P, N]``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import state_mixers as SM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.pallas import state_phases
from deepspeed_tpu.ops.pallas.mamba2_decode_update import mamba2_decode_update

REL = 1e-6
SLOTS = 7           # a layer's slots, the dummy (0) among them

#       name: rows' slots, first pool row of the layer, (H, P, N), rows a
#             phase (None: what the budget gives, every row at once)
CASES = {
    "all_rows_live": ([1, 2, 3, 4, 5, 6], 0, (4, 32, 128), 2),
    "unsorted_slots": ([5, 2, 6, 1, 4, 3], 0, (4, 32, 128), None),
    "some_rows_on_the_dummy": ([3, 0, 6, 0, 0, 1], 0, (4, 32, 128), 2),
    "one_live_row": ([0, 0, 4, 0], 0, (4, 32, 128), 2),
    "no_live_row": ([0, 0, 0], 0, (4, 32, 128), 2),
    "two_periods": ([3, 0, 6, 1], SLOTS, (4, 32, 128), 2),
    # a row a phase: the two buffers take turns five times
    "three_heads_of_256_lanes": ([2, 4, 0, 1, 6, 3], 0, (3, 128, 256), 1),
    # H * P one lane tile, N two sublane tiles
    "one_lane_tile": ([2, 4, 0, 1, 6, 3], 0, (2, 64, 16), 2),
    # the live rows end inside a phase, at its end, and one row past it
    "ends_inside_a_phase": ([6, 5, 4, 0, 0, 0, 0], 0, (4, 32, 128), 4),
    "ends_at_a_phase": ([6, 5, 4, 3, 0, 0, 0], 0, (4, 32, 128), 4),
    "ends_one_past_a_phase": ([6, 5, 4, 3, 2, 0, 0], 0, (4, 32, 128), 4),
    "two_full_phases_and_a_row": ([6, 5, 4, 3, 2], 0, (4, 32, 128), 2),
    "a_live_row_after_idle_rows": ([0, 0, 0, 5, 0, 2], 0, (4, 32, 128), 2),
    "a_shape_the_kernel_refuses": ([2, 0, 5], 0, (3, 16, 32), None),
}


@pytest.fixture(autouse=True)
def _no_mesh():
    """A mesh an earlier file of the worker left would send the model's
    step to its plain-XLA form (a bare ``pallas_call`` is legal on one
    device only)."""
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def draw_step(r, B, H, P, N):
    """(x, dt, A, B, C, D) of one decode step as ``_mamba2_project`` gives
    them: dt a softplus in the family's range, A in -(1, 16)."""
    f = lambda a: jnp.asarray(a, jnp.float32)                       # noqa: E731
    return (f(r.standard_normal((B, H, P))),
            f(np.exp(r.uniform(np.log(1e-3), np.log(0.3), (B, H)))),
            f(-r.uniform(1.0, 16.0, H)), f(r.standard_normal((B, N))),
            f(r.standard_normal((B, N))), f(1.0 + 0.3 * r.standard_normal(H)))


@pytest.mark.parametrize("case", CASES)
def test_kernel_against_the_recurrence(case, monkeypatch):
    slots, base, (H, P, N), phase_rows = CASES[case]
    B = len(slots)
    r = np.random.default_rng(sorted(CASES).index(case))
    x, dt, A, Bm, Cm, D = draw_step(r, B, H, P, N)
    pool = jnp.asarray(r.standard_normal((2 * SLOTS, N, H * P)), jnp.float32)
    slots = np.asarray(slots, np.int32)
    if phase_rows:
        monkeypatch.setattr(state_phases, "_PHASE_BYTES",
                            phase_rows * H * P * N * 4)
    cfg = T.TransformerConfig(vocab_size=8, n_layer=1, n_head=1, d_model=8,
                              attention_backend="flash")
    step = (x, dt, A, Bm, Cm, D, jnp.asarray(slots), base)

    dispatch.reset()
    out = mamba2_decode_update(pool + 0.0, x, dt, A, Bm, Cm, slots, base)
    if (H * P) % 128:
        # outside the envelope: None, nothing selected, and the model's step
        # takes (and records) its plain-XLA form
        assert out is None and not dispatch.selected()
        y, new = SM._ssd_state_update(cfg, pool, *step)
        assert dispatch.selected() == {"ssd_decode=slot_gather": 1}
        wy, wnew = SM._ssd_decode_update(pool, *step)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(wy))
        np.testing.assert_array_equal(np.asarray(new), np.asarray(wnew))
        return
    assert dispatch.selected() == {"kernel/mamba2_decode_update=interpret": 1}
    y, new = (np.asarray(a) for a in out)
    live = slots != 0
    rows = base + slots
    zero = jnp.zeros_like(D)                 # the kernel's y is S C alone
    want_y, want_S = jax.vmap(
        lambda S, xb, dtb, bb, cb: SM.ssd_recurrent_step(S, xb, dtb, A, bb, cb, zero)
    )(SM._ssd_from_pool(pool[rows], H), x, dt, Bm, Cm)
    want_y, want_S = np.asarray(want_y), np.asarray(SM._ssd_to_pool(want_S))
    assert np.isfinite(y).all() and np.isfinite(new).all()
    if live.any():
        assert np.abs(y[live] - want_y[live]).max() \
            <= REL * np.abs(want_y[live]).max()
        assert np.abs(new[rows[live]] - want_S[live]).max() \
            <= REL * np.abs(want_S[live]).max()
    assert not y[~live].any()
    untouched = np.ones(len(new), bool)
    untouched[rows[live]] = False
    np.testing.assert_array_equal(new[untouched], np.asarray(pool)[untouched])
    # the model's step takes the kernel, adds D x, and agrees with its twin
    dispatch.reset()
    my, mnew = SM._ssd_state_update(cfg, pool + 0.0, *step)
    assert dispatch.selected()["ssd_decode=mamba2_kernel"] == 1
    ty, tnew = SM._ssd_decode_update(pool, *step)
    scale = max(float(np.abs(ty).max()), 1.0)
    np.testing.assert_allclose(np.asarray(my), np.asarray(ty), rtol=0,
                               atol=REL * scale)
    np.testing.assert_allclose(np.asarray(mnew), np.asarray(tnew), rtol=0,
                               atol=REL * float(np.abs(tnew).max()))
    assert not np.asarray(my)[~live].any()
