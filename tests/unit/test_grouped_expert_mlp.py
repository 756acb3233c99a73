"""The grouped expert kernel (``ops/pallas/grouped_expert_mlp.py``) in
interpret mode against its plain-XLA twin, ``dense_expert_mlp``, at toy
widths: the routing's corners (PR 40), and calls past one row tile, where a
visit computes its expert's own rows (PR 53)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import dense_dispatch
from deepspeed_tpu.ops.pallas.grouped_expert_mlp import (MAX_ROWS, RIDE_ROWS,
                                                         ROW_TILE,
                                                         dense_expert_mlp,
                                                         grouped_expert_mlp,
                                                         own_rows, row_tile,
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
    # past one row tile: a visit computes its expert's own rows, ROW_TILE at
    # a time (144 x 2 / 8 = 36 rows an expert: every expert a second tile)
    "rows_144": (144, 2, 0, E, (), False, 0, 1, False, jnp.float32, 2e-5),
    "rows_256": (256, 2, 0, E, (), False, 0, 1, False, jnp.float32, 2e-5),
    "rows_384_layer_1_of_two_valid_padding_rows": (
        384, 2, 0, E, (), False, 1, 2, True, jnp.float32, 2e-5),
    "rows_512_reglu": (512, 2, 0, E, (), True, 0, 1, False, jnp.float32, 2e-5),
    "rows_256_share_with_sentinel_rows_reglu": (
        256, 3, 2, E + 1, (), True, 0, 1, False, jnp.float32, 2e-5),
    "rows_256_no_rows_on_three_experts": (256, 2, 1, E, (3, 4), False, 0, 1,
                                          False, jnp.float32, 2e-5),
    "rows_512_every_row_on_one_expert": (512, 1, 5, 6, (), False, 0, 1, False,
                                         jnp.float32, 2e-5),
    "rows_200_not_a_multiple_of_the_sublane_tile": (
        200, 2, 0, E, (), False, 1, 2, True, jnp.bfloat16, 3e-2),
    "rows_256_bf16_operands_layer_0_of_two": (
        256, 2, 0, E, (), False, 0, 2, False, jnp.bfloat16, 3e-2),
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


def test_more_rows_than_the_envelope_are_left_to_the_caller():
    w_gate, w_up, w_down = _stacks(1)
    x = jnp.zeros((MAX_ROWS + 16, D))
    assert grouped_expert_mlp(x, jnp.zeros((MAX_ROWS + 16, E)), w_gate, w_up,
                              w_down, interpret=True) is None


def test_the_row_tile_is_a_rule_on_a_calls_rows():
    assert MAX_ROWS == 512 and ROW_TILE % 16 == 0
    assert [row_tile(t) for t in (1, 64, RIDE_ROWS)] == [0, 0, 0]
    assert [row_tile(t) for t in (RIDE_ROWS + 1, 256, MAX_ROWS)] \
        == [ROW_TILE] * 3


def test_a_rows_place_among_its_experts_rows():
    combine = np.zeros((40, E), np.float32)
    combine[::2, 1] = 0.5           # 20 rows: one tile of 16 and a second
    combine[5:8, 6] = 0.25
    combine[:, 7] = 1.0
    rank, rank_t, combine_t, tiles = own_rows(jnp.asarray(combine), 16)
    rank = np.asarray(rank)
    np.testing.assert_array_equal(np.asarray(rank_t), rank.T)
    np.testing.assert_array_equal(np.asarray(combine_t), combine.T)
    assert np.asarray(tiles).tolist() == [0, 2, 0, 0, 0, 0, 1, 3]
    assert rank[::2, 1].tolist() == list(range(20))
    assert (rank[1::2, 1] == -1).all() and (rank[:, 0] == -1).all()
    assert rank[5:8, 6].tolist() == [0, 1, 2]
    assert rank[:, 7].tolist() == list(range(40))


@pytest.mark.parametrize("rows", [64, 256])
def test_what_the_routing_did_not_choose_costs_and_adds_nothing(rows):
    """An untouched expert's weights are never read (NaN there reaches no
    row), a padding row (``combine`` all 0) gets exactly 0, and a row that
    overflowed upstream stays its own affair."""
    w_gate, w_up, w_down = _stacks(1)
    untouched = jnp.asarray([3, 4])
    w_gate, w_up, w_down = (w.at[untouched].set(jnp.nan)
                            for w in (w_gate, w_up, w_down))
    x = jax.random.normal(jax.random.key(rows), (rows, D))
    weights, experts = _routing(rows, 2, 0, E, (3, 4))
    valid = jnp.asarray(np.arange(rows) % 5 != 0, jnp.int32)
    x_bad = x.at[5].set(jnp.inf)    # row 5 is a padding row

    def kernel(xs, combine):
        return grouped_expert_mlp(xs, combine, w_gate, w_up, w_down,
                                  interpret=True)

    def clean(xs, combine):
        z = lambda w: jnp.nan_to_num(w)  # noqa: E731
        return dense_expert_mlp(xs, combine, z(w_gate), z(w_up), z(w_down))

    got = np.asarray(dense_dispatch(x_bad, weights, experts, E, kernel,
                                    valid)[0])
    want = np.asarray(dense_dispatch(x, weights, experts, E, clean, valid)[0])
    keep = np.arange(rows) != 5
    assert np.isfinite(got[keep]).all()
    np.testing.assert_allclose(got[keep], want[keep],
                               atol=2e-5 * np.abs(want).max())
    assert not got[(np.asarray(valid) == 0) & keep].any()


# --------------------------------------------------------------------- #
# model level: a paged program of 256 rows selects the kernel where a bare
# Pallas call is legal (``attention_backend="flash"``, no mesh) and agrees
# with the ``dense`` form the default backend keeps

def _toy_pair(name):
    """(the toy preset's model as the CPU builds it, the same with the
    kernels interpreted, float32 params)."""
    from deepspeed_tpu.models.presets import get_model
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", name + ".json")) as f:
        preset = json.load(f)["preset"]
    plain = get_model(**preset)
    return plain, get_model(**preset, attention_backend="flash"), \
        plain.init_params(jax.random.key(3))


def _selected_by(run, model):
    from deepspeed_tpu.ops import dispatch
    dispatch.reset()
    out = run(model)
    return out, dispatch.selected(), dispatch.details()


def _agree(got, want, counts_got, counts_want):
    np.testing.assert_array_equal(np.asarray(counts_got),
                                  np.asarray(counts_want))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_lfm2s_256_row_decode_step_takes_the_kernel_and_agrees_with_dense():
    plain, flash, params = _toy_pair("rehearsal-lfm2-moe-tiny")
    rows, bs = 256, 16
    tokens = np.random.default_rng(5).integers(0, 512, (rows, 1), np.int32)
    bt = (1 + np.arange(rows, dtype=np.int32))[:, None]
    pos = np.zeros((rows,), np.int32)
    pos[::7] = 3                    # some rows a few tokens in
    live = np.arange(rows) % 9 != 0  # and some rows idle (the dummy block)
    bt[~live] = 0

    def step(model):
        pools = model.init_paged_cache(rows + 1, bs, jnp.float32,
                                       state_slots=rows + 1)
        logits, _, counts = jax.jit(model.forward_paged_decode)(
            params, tokens, pools, bt, pos, None,
            np.where(live, 1 + np.arange(rows), 0).astype(np.int32))
        return logits[live], counts

    (want, n_want), chosen, _ = _selected_by(step, plain)
    assert "experts=grouped_kernel" not in chosen and chosen["experts=dense"]
    (got, n_got), chosen, details = _selected_by(step, flash)
    assert "experts=dense" not in chosen
    assert chosen["kernel/grouped_expert_mlp=interpret"] \
        == chosen["experts=grouped_kernel"] >= 1
    assert f"tm={ROW_TILE}" in details["experts=grouped_kernel"]
    _agree(got, want, n_got, n_want)
    # every live row's k assignments computed, owed and no more
    k, E = flash.moe.k, flash.moe.num_experts
    assert (np.asarray(n_got)[:, :E].sum(axis=1) == live.sum() * k).all()
    assert (np.asarray(n_got)[:, E] == live.sum() * k).all()


def test_sdars_256_position_pass_takes_the_kernel_and_agrees_with_dense():
    plain, flash, params = _toy_pair("rehearsal-sdar-tiny")
    W, bs = 64, 16
    Bg = plain.config.generation.block
    assert W * Bg == 256
    tokens = np.random.default_rng(6).integers(0, 512, (W, Bg), np.int32)
    bt = (1 + np.arange(W, dtype=np.int32))[:, None]
    pos = np.zeros((W,), np.int32)

    def block_pass(model):
        pools = model.init_paged_cache(W + 1, bs, jnp.float32)
        logits, _, counts = jax.jit(model.forward_paged_block)(
            params, tokens, pools, bt, pos)
        return logits, counts

    (want, n_want), chosen, _ = _selected_by(block_pass, plain)
    assert "experts=grouped_kernel" not in chosen
    (got, n_got), chosen, details = _selected_by(block_pass, flash)
    assert "experts=dense" not in chosen and chosen["experts=grouped_kernel"]
    assert "rows=256" in details["experts=grouped_kernel"]
    assert f"tm={ROW_TILE}" in details["experts=grouped_kernel"]
    _agree(got, want, n_got, n_want)


def test_rows_past_the_envelope_keep_dense():
    """One number on a call's static rows: 512 take the kernel, 528 do not
    (``moe_lm._GROUPED_KERNEL_MAX_ROWS``)."""
    from deepspeed_tpu.models import moe_lm
    _, flash, params = _toy_pair("rehearsal-olmoe-tiny")
    assert moe_lm._GROUPED_KERNEL_MAX_ROWS == MAX_ROWS
    assert flash._grouped_kernel(params, MAX_ROWS)
    assert not flash._grouped_kernel(params, MAX_ROWS + 16)


def test_the_models_row_tile_is_what_the_engine_counts_by():
    plain, flash, params = _toy_pair("rehearsal-lfm2-moe-tiny")
    assert [flash.expert_row_tile(params, t) for t in (64, 128, 256, 512, 528)] \
        == [0, 0, ROW_TILE, ROW_TILE, 0]
    assert plain.expert_row_tile(params, 256) == 0      # its call is dense


@pytest.mark.parametrize("tile,want", [(0, None), (16, 1 + 2 + 1 + 3)])
def test_the_row_tile_counter(tile, want):
    """``serving/moe_expert_row_tiles``: the tiles each touched expert's
    rows fill, summed over the layers of a step past one row tile; absent
    where no step was."""
    from deepspeed_tpu.inference.scheduler import ServingTelemetry
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    tel = ServingTelemetry(MetricsRegistry())
    # two layers of four experts, the owed column last
    counts = np.asarray([[16, 17, 0, 0, 33], [0, 0, 1, 33, 34]])
    tel.count_moe(counts, False, tile)
    got = tel.registry.snapshot()["counters"]
    assert got["serving/moe_experts_touched"] == 4
    assert got.get("serving/moe_expert_row_tiles") == want
