"""The KDA decode-update kernel (interpret mode on the CPU tier) against
``kda_recurrent_step`` in float32: the live rows' ``o`` and new state to
1e-6 of their largest value, every pool row no live row holds BIT-identical
afterwards (the dummy among them: the kernel issues no copy for an inactive
row). One parametrised test, a case each state the serving loop puts it in.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import state_mixers as SM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.pallas import state_phases
from deepspeed_tpu.ops.pallas.kda_decode_update import kda_decode_update

REL = 1e-6
SLOTS = 7           # a layer's slots, the dummy (0) among them

#       name: rows' slots, first pool row of the layer, (H, dk, dv), decay,
#             rows a phase (None: what the budget gives, every row at once)
CASES = {
    "all_rows_live": ([1, 2, 3, 4, 5, 6], 0, (4, 16, 128), None, 2),
    "unsorted_slots": ([5, 2, 6, 1, 4, 3], 0, (4, 16, 128), None, None),
    "some_rows_on_the_dummy": ([3, 0, 6, 0, 0, 1], 0, (4, 16, 128), None, 2),
    "one_live_row": ([0, 0, 4, 0], 0, (4, 16, 128), None, 2),
    "no_live_row": ([0, 0, 0], 0, (4, 16, 128), None, 2),
    "decay_minus_10_a_step": ([2, 0, 5], 0, (4, 16, 128), -10.0, 2),
    "two_periods": ([3, 0, 6, 1], SLOTS, (4, 16, 128), None, 2),
    # a row a phase: the two buffers take turns five times
    "three_heads_of_256_lanes": ([2, 4, 0, 1, 6, 3], 0, (3, 8, 256), None, 1),
    # four rows a phase, the last phase short of one
    "a_short_last_phase": ([6, 5, 4, 3, 2, 1, 0], 0, (4, 16, 128), None, 4),
    "a_shape_the_kernel_refuses": ([2, 0, 5], 0, (3, 16, 32), None, None),
}


def draw_step(r, B, H, dk, dv, decay=None):
    """(qh, kh, v, g, beta) of one decode step as ``_kda_project`` gives
    them: unit keys, queries scaled by dk^-0.5, log decays in the family's
    range (or ``decay`` everywhere), beta in (0, 2)."""
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    f = lambda a: jnp.asarray(a, jnp.float32)                       # noqa: E731
    return (f(unit(r.standard_normal((B, H, dk))) * dk ** -0.5),
            f(unit(r.standard_normal((B, H, dk)))),
            f(r.standard_normal((B, H, dv))),
            f(np.full((B, H, dk), decay) if decay
              else -1.6 * r.random((B, H, dk))),
            f(2 * r.random((B, H))))


@pytest.mark.parametrize("case", CASES)
def test_kernel_against_the_recurrence(case, monkeypatch):
    slots, base, (H, dk, dv), decay, phase_rows = CASES[case]
    B = len(slots)
    r = np.random.default_rng(sorted(CASES).index(case))
    qh, kh, v, g, beta = draw_step(r, B, H, dk, dv, decay)
    pool = jnp.asarray(r.standard_normal((2 * SLOTS, H, dk, dv)), jnp.float32)
    slots = np.asarray(slots, np.int32)
    if phase_rows:
        monkeypatch.setattr(state_phases, "_PHASE_BYTES",
                            phase_rows * H * dk * dv * 4)

    dispatch.reset()
    out = kda_decode_update(pool + 0.0, qh, kh, v, g, beta, slots, base)
    if dv % 128:
        # outside the envelope: None, nothing selected, and the model's step
        # takes (and records) its plain-XLA form
        assert out is None and not dispatch.selected()
        cfg = T.TransformerConfig(vocab_size=8, n_layer=1, n_head=1, d_model=8,
                                  attention_backend="flash")
        step = (qh, kh, v, g, beta, jnp.asarray(slots), base)
        o, new = SM._kda_state_update(cfg, pool, *step, SLOTS)
        assert dispatch.selected() == {"kda_decode=slot_update": 1}
        wo, wnew = SM._kda_slot_update(pool, *step, SLOTS)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(wo))
        np.testing.assert_array_equal(np.asarray(new), np.asarray(wnew))
        return
    assert dispatch.selected() == {"kernel/kda_decode_update=interpret": 1}
    o, new = (np.asarray(a) for a in out)
    live = slots != 0
    rows = base + slots
    want_o, want_S = SM.kda_recurrent_step(pool[rows], qh, kh, v, g, beta)
    want_o, want_S = np.asarray(want_o), np.asarray(want_S)
    assert np.isfinite(o).all() and np.isfinite(new).all()
    if live.any():
        assert np.abs(o[live] - want_o[live]).max() \
            <= REL * np.abs(want_o[live]).max()
        assert np.abs(new[rows[live]] - want_S[live]).max() \
            <= REL * np.abs(want_S[live]).max()
    assert not o[~live].any()
    untouched = np.ones(len(new), bool)
    untouched[rows[live]] = False
    np.testing.assert_array_equal(new[untouched], np.asarray(pool)[untouched])
    # and the form the model takes off a TPU agrees on what both define
    to, tnew = SM._kda_slot_update(pool, qh, kh, v, g, beta, jnp.asarray(slots),
                                  base, SLOTS)
    if live.any():
        np.testing.assert_allclose(np.asarray(to)[live], o[live],
                                   atol=REL * np.abs(want_o[live]).max())
    keep = np.arange(len(new)) != base      # the twin also steps the dummy
    np.testing.assert_allclose(np.asarray(tnew)[keep], new[keep],
                               atol=REL * np.abs(want_S).max())
