"""The grouped expert kernel (``ops/pallas/grouped_expert_mlp.py``) in
interpret mode against its plain-XLA twin, ``dense_expert_mlp``, at toy
widths: the routing's corners (PR 40)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import dense_dispatch
from deepspeed_tpu.ops.pallas.grouped_expert_mlp import (MAX_ROWS,
                                                         dense_expert_mlp,
                                                         grouped_expert_mlp,
                                                         touched_visits)

E, D, F = 8, 64, 256


def _stacks(layers: int, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(7), 3)
    draw = lambda k, shape: (jax.random.normal(k, shape) * 0.1).astype(dtype)  # noqa: E731
    return (draw(ks[0], (layers * E, D, F)), draw(ks[1], (layers * E, D, F)),
            draw(ks[2], (layers * E, F, D)))


def _routing(T: int, k: int, lo: int, hi: int, skip=()):
    """Each row's k distinct experts among ``lo .. hi`` less ``skip`` (``E``
    is the sentinel of an expert held elsewhere) and its weights."""
    pool = np.array([e for e in range(lo, hi) if e not in skip])
    rng = np.random.default_rng(T * 31 + k)
    experts = np.stack([rng.permutation(pool)[:k] for _ in range(T)])
    weights = rng.uniform(0.1, 1.0, size=(T, k)).astype(np.float32)
    return jnp.asarray(weights), jnp.asarray(experts, jnp.int32)


# name: (rows, k, experts drawn from [lo, hi), skipped, relu, layer, layers,
#        some rows padding, dtype, tolerance)
CASES = {
    "silu": (16, 2, 0, E, (), False, 0, 1, False, jnp.float32, 2e-5),
    "reglu": (16, 2, 0, E, (), True, 0, 1, False, jnp.float32, 2e-5),
    # a share of the router's experts: index E is one held elsewhere
    "share_with_sentinel_rows": (16, 3, 2, E + 1, (), False, 0, 1, False,
                                 jnp.float32, 2e-5),
    "valid_padding_rows": (16, 2, 0, E, (), False, 0, 1, True, jnp.float32, 2e-5),
    "no_rows_on_the_first_expert": (16, 2, 1, E, (), False, 0, 1, False,
                                    jnp.float32, 2e-5),
    "no_rows_on_the_last_expert": (16, 2, 0, E - 1, (), False, 0, 1, False,
                                   jnp.float32, 2e-5),
    "no_rows_on_a_middle_expert": (16, 2, 0, E, (3, 4), False, 0, 1, False,
                                   jnp.float32, 2e-5),
    "every_row_on_one_expert": (16, 1, 5, 6, (), False, 0, 1, False,
                                jnp.float32, 2e-5),
    "rows_not_a_multiple_of_the_row_tile": (10, 2, 0, E, (), False, 0, 1, False,
                                            jnp.float32, 2e-5),
    "one_row": (1, 2, 0, E, (), True, 0, 1, False, jnp.float32, 2e-5),
    "layer_0_of_two": (16, 2, 0, E, (), False, 0, 2, False, jnp.float32, 2e-5),
    "layer_1_of_two": (16, 2, 0, E, (), False, 1, 2, False, jnp.float32, 2e-5),
    "bf16_operands": (16, 2, 0, E, (), False, 1, 2, True, jnp.bfloat16, 3e-2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_its_dense_twin(case):
    T, k, lo, hi, skip, relu, layer, layers, padded, dtype, tol = CASES[case]
    w_gate, w_up, w_down = _stacks(layers, dtype)
    x = jax.random.normal(jax.random.key(T), (T, D)).astype(dtype)
    weights, experts = _routing(T, k, lo, hi, skip)
    valid = jnp.asarray(np.arange(T) % 3 != 0, jnp.int32) if padded else None
    own = slice(layer * E, (layer + 1) * E)

    def kernel(xs, combine):
        # two F tiles: a visit's sum over them is exercised
        return grouped_expert_mlp(xs, combine, w_gate, w_up, w_down,
                                  jnp.int32(layer * E), relu=relu, f_tile=128,
                                  interpret=True)

    def twin(xs, combine):
        return dense_expert_mlp(xs, combine, w_gate[own], w_up[own],
                                w_down[own], relu=relu)

    got, n_got = dense_dispatch(x, weights, experts, E, kernel, valid)
    want, n_want = dense_dispatch(x, weights, experts, E, twin, valid)
    np.testing.assert_array_equal(np.asarray(n_got), np.asarray(n_want))
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    assert scale > 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol * scale)
    if valid is not None:       # a padding row reaches no expert
        assert not np.asarray(got, np.float32)[np.asarray(valid) == 0].any()
    if layers == 2:             # the other layer's experts give another answer
        other = slice((1 - layer) * E, (2 - layer) * E)
        wrong = dense_dispatch(
            x, weights, experts, E,
            lambda xs, c: dense_expert_mlp(xs, c, w_gate[other], w_up[other],
                                           w_down[other], relu=relu), valid)[0]
        assert float(jnp.abs(wrong.astype(jnp.float32)
                             - got.astype(jnp.float32)).max()) > 0.1 * scale


@pytest.mark.parametrize("touched,want", [
    ([0, 1, 1, 0, 1, 0, 0, 0], [1, 2, 4, 4, 4, 4, 4, 4]),
    ([1, 0, 0, 0, 0, 0, 0, 1], [0, 7, 7, 7, 7, 7, 7, 7]),
    ([1] * 8, list(range(8))),
    ([0] * 8, [0] * 8),
])
def test_the_visit_list_is_the_touched_experts_padded_by_the_last(touched, want):
    combine = jnp.asarray(touched, jnp.float32)[None, :] * jnp.ones((4, 1))
    visits, n = touched_visits(combine)
    assert int(n) == sum(touched)
    assert np.asarray(visits).tolist() == want


def test_more_rows_than_a_row_tile_are_left_to_the_caller():
    w_gate, w_up, w_down = _stacks(1)
    x = jnp.zeros((MAX_ROWS + 16, D))
    assert grouped_expert_mlp(x, jnp.zeros((MAX_ROWS + 16, E)), w_gate, w_up,
                              w_down, interpret=True) is None
