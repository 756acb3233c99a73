"""granite-4.0-h-micro's block on the paged programs against its plain
reference (``perfbench/reference/granite_hybrid_decoder.py``: float32, the
state-space recurrence token by token), on the ``granite_hybrid`` ``tiny``
preset (ONE WHOLE PUBLISHED PERIOD: five Mamba-2 layers, a NoPE GQA layer,
four more Mamba-2 layers; the four multipliers at their published values; a
tied head) with seeded weights perturbed as ``perfbench/weights.py`` perturbs
them. The reference itself is held to ``transformers``'
``GraniteMoeHybridForCausalLM`` in ``test_module_inject.py`` (that file's
worker has paid for torch); the engine's part (batching with more callers
than rows, a recompute-preemption, the refusals, the counters) is
``test_serving_state.py``'s, which runs on this toy too.

Tolerances. Program and reference both compute in float32 here (the CPU's
default matmul precision is full float32), so they differ by the order of
sums and by the chunked form of the recurrence: logits of magnitude ~0.03
(deviation 0.006: the toy's init, ``models/presets.py``, puts its
projections where the published widths put theirs and its embedding low
enough that the layers, not the x12 embedding, decide the logits) agree to
3e-8 (prefill whole or in chunks, then 24 decode steps). ``LOGIT_TOL`` 5e-7
is seventeen times that and a thirteenth of what the smallest control moves
them by: a state rounded to bf16 moves the logits by 6.7e-6
(``test_a_bf16_state_fails_the_tolerance``), a lost state by 2e-3, a dropped
conv state by 3e-2, a multiplier left at 1.0 by more.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import state_mixers as SM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.presets import get_model

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench")
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from reference import granite_hybrid_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-granite-hybrid-tiny"
LOGIT_TOL = 5e-7
BS = 128


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def load_toy(backend="auto", **over):
    """(model, float32 params, the reference's cfg, the name map) of the toy
    configuration, ``over`` laid over its preset (the program's names)."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"], **over, attention_backend=backend)
    params = make_params(model, 3100000043, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


@pytest.fixture(scope="module")
def toy():
    return load_toy()


def tokens_of(seed, n, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def reference_logits(toy, tokens, **cfg_over):
    _, params, cfg, name_map = toy
    w = ref.Weights(params, name_map)
    cfg = {**cfg, **cfg_over}
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens)[None])
    return np.asarray(ref.logits_rows(cfg, w, h[0]))


def fresh_pools(model, n_blocks, slots):
    """Pools whose slots' last holders left something there: it must not be
    inherited."""
    pools = model.init_paged_cache(n_blocks, BS, jnp.float32, state_slots=slots)
    pools["state"] = tuple(a + 3.0 for a in pools["state"])
    pools["conv"] = tuple(a - 2.0 for a in pools["conv"])
    return pools


_JITTED = {}


def jitted(model, name):
    """One jit a model and program, so that a second call compiles nothing."""
    key = (id(model), name)
    if key not in _JITTED:
        _JITTED[key] = (model, jax.jit(getattr(model, name)))
    return _JITTED[key][1]


def sharpened(toy):
    """The toy with its attention layer's q and k projections 20 times and
    its v and o projections 10 times as large: under the seeded 0.02 a
    head's scores are ~1e-3, its softmax is flat whatever multiplies them,
    and its output is a hundredth of a Mamba-2 layer's, so the attention's
    scale shows in nothing (it moves the logits by 2e-6)."""
    model, params, cfg, name_map = toy
    layers = list(params["layers"])
    attn = {k: w * {"wq": 20.0, "wk": 20.0}.get(k, 10.0)
            for k, w in layers[5]["attn"].items()}
    layers[5] = {**layers[5], "attn": attn}
    return model, {**params, "layers": tuple(layers)}, dict(cfg), name_map


def prefill(model, params, pools, tokens, table, slot, chunk=0, bucket=128):
    """``tokens`` prefilled into the blocks of ``table`` and state slot
    ``slot``: whole, or ``chunk`` tokens a piece. Returns (the last
    position's logits, pools)."""
    start, n_prompt = 0, len(tokens)
    while start < n_prompt:
        n = min(chunk or n_prompt, n_prompt - start)
        Tb = -(-n // bucket) * bucket
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :n] = tokens[start:start + n]
        p_t = start + np.arange(Tb)
        slots = np.where(np.arange(Tb) < n,
                         table[np.minimum(p_t // BS, len(table) - 1)] * BS
                         + p_t % BS, p_t % BS).astype(np.int32)
        if chunk:
            lg, pools = jitted(model, "forward_paged_prefill_chunk")(
                params, toks, pools, table[None], slots, np.int32(start),
                np.int32(n - 1), np.int32(slot))
        else:
            lg, pools = jitted(model, "forward_paged_prefill")(
                params, toks, pools, slots, np.int32(n - 1), np.int32(slot))
        start += n
    return np.asarray(lg)[0], pools


# --------------------------------------------------------------------- #
# what the preset builds


def test_the_published_model_is_built_whole():
    """Every size as ``config.json`` gives it: 3,191,396,096 parameters (36
    Mamba-2 layers of 76,182,976, 4 attention layers of 60,821,504, the tied
    embedding and the final norm), nothing cut; and the pools the cell holds."""
    model = get_model("granite_hybrid", "4.0-h-micro")
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 3_191_396_096 == model.num_parameters
    cfg = model.config
    assert cfg.period == ("mamba2",) * 5 + ("attention",) + ("mamba2",) * 4
    assert (cfg.n_layer, cfg.n_periods, cfg.d_model, cfg.ff_dim) == (40, 4, 2048, 8192)
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_conv_kernel, cfg.ssm_chunk) == (64, 64, 128, 4, 256)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attn_scale) == (12.0, 0.22, 8.0, 0.015625)
    assert cfg.pos_embedding == "none" and cfg.tie_embeddings \
        and cfg.vocab_size == 100352 and cfg.norm_eps == 1e-5
    assert cfg.cache_spec == {"kv": 4, "state": 36, "window": 0, "latent": 0}
    ssm = shapes["layers"][0]["ssm"]
    assert ssm["w_in"].shape == (4, 2048, 8512) \
        and ssm["conv_w"].shape == (4, 4, 4352) \
        and ssm["b_conv"].shape == (4, 4352) and ssm["w_out"].shape == (4, 4096, 2048)
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        769, 128, jnp.bfloat16, state_slots=65))
    assert [a.shape for a in pools["state"]] == [(4, 65, 128, 64 * 64)] * 9
    assert {a.dtype for a in pools["state"]} == {jnp.dtype(jnp.float32)}
    assert [a.shape for a in pools["conv"]] == [(4, 65, 3, 4352)] * 9
    assert pools["conv"][0].dtype == jnp.bfloat16
    assert pools["k"].shape == (4, 769, 128, 512)


def test_the_toy_is_one_published_period(toy):
    model, params = toy[:2]
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == 542_540
    assert model.config.period == get_model("granite_hybrid", "4.0-h-micro").config.period
    assert model.config.n_periods == 1
    # H P = 2 d, as published
    assert model.config.ssm_heads * model.config.ssm_head_dim == 2 * model.config.d_model


def test_what_the_serving_path_alone_runs(toy):
    model, params = toy[:2]
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="paged serving path only"):
        model.forward(params, toks)
    with pytest.raises(NotImplementedError, match="paged serving path only"):
        model.forward_cached(params, toks, model.init_cache(1, 16), jnp.int32(0))
    with pytest.raises(ValueError, match="ssm_heads"):
        T.init_params(dataclasses.replace(model.config, ssm_state=0),
                      jax.random.key(0))


def test_a_multiplier_without_a_pattern_is_refused_off_the_serving_path():
    from deepspeed_tpu.models import CausalLM
    model = CausalLM(T.TransformerConfig(
        vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64, max_seq=64,
        residual_multiplier=0.22))
    params = model.init_params(jax.random.key(0))
    with pytest.raises(NotImplementedError, match="residual_multiplier"):
        model.forward(params, jnp.zeros((1, 8), jnp.int32))


# --------------------------------------------------------------------- #
# the recurrence: chunked form against the one-token update


def _ssd_inputs(seed, n, H=4, Pd=32, N=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(n, H) - 2.0)
    A = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.7, size=H), jnp.float32))
    return f(n, H, Pd), dt, A, f(n, N), f(n, N), 1.0 + 0.3 * f(H)


def _sequential(S, x, dt, A, Bm, Cm, D):
    ys = []
    for t in range(x.shape[0]):
        y, S = SM.ssd_recurrent_step(S, x[t], dt[t], A, Bm[t], Cm[t], D)
        ys.append(y)
    return jnp.stack(ys), S


@pytest.mark.parametrize("from_zero", [True, False], ids=["zero", "carried"])
@pytest.mark.parametrize("n", [1, 7, 8, 9, 29])
def test_the_chunked_form_is_the_sequential_recurrence(n, from_zero):
    """Lengths of one token, short of a chunk, a chunk, a chunk and one, three
    chunks and five (the chunk is 8), from zero and from a state carried
    in."""
    x, dt, A, Bm, Cm, D = _ssd_inputs(n, n)
    S0 = jnp.zeros((4, 32, 16), jnp.float32) if from_zero else \
        jnp.asarray(np.random.default_rng(99).standard_normal((4, 32, 16)),
                    jnp.float32)
    # the chunked form takes and leaves the state as the pool keeps it
    y, S = SM.ssd_chunked(SM._ssd_to_pool(S0), x, dt, A, Bm, Cm, D, chunk=8)
    want_y, want_S = _sequential(S0, x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(y, want_y, rtol=0, atol=2e-5)
    np.testing.assert_allclose(SM._ssd_from_pool(S, 4), want_S, rtol=0, atol=2e-5)


def test_a_buckets_padding_touches_neither_state_nor_conv(toy):
    """13 real positions in a bucket of 32 leave the request's state and conv
    state where the same 13 in a bucket of 16 leave them, and the real
    positions' outputs where they were."""
    model, params = toy[:2]
    cfg = model.config
    lp = jax.tree.map(lambda a: a[0], params["layers"][0]["ssm"])
    (sh, ch) = cfg.state_shapes("mamba2")
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.standard_normal((3,) + sh), jnp.float32)
    conv = jnp.asarray(rng.standard_normal((3,) + ch), jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 32, cfg.d_model)), jnp.float32)
    run = jax.jit(lambda xb: SM._mamba2_prefill(
        cfg, xb, lp, state, conv, jnp.int32(1), jnp.int32(13), False))
    outs = [run(x[:, :Tb]) for Tb in (16, 32)]
    for a, b in zip(outs[0][1:], outs[1][1:]):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    np.testing.assert_allclose(outs[0][0][:, :13], outs[1][0][:, :13],
                               rtol=0, atol=1e-6)
    # and the other slots are bit for bit what they were
    for got, was in ((outs[1][1], state), (outs[1][2], conv)):
        np.testing.assert_array_equal(got[np.array([0, 2])], was[np.array([0, 2])])
    assert float(jnp.abs(outs[1][1][1] - state[1]).max()) > 1e-3


def test_the_decode_update_moves_the_live_rows_alone():
    """Five rows, two idle: the live rows' slots hold what the one-token
    update gives, every other pool row is bit for bit what it was, an idle
    row's output is zero."""
    x, dt, A, Bm, Cm, D = _ssd_inputs(3, 5)
    pool = jnp.asarray(np.random.default_rng(4).standard_normal((12, 16, 4 * 32)),
                       jnp.float32)     # kept [N, H * P]: SM._ssd_to_pool
    slots = jnp.asarray([3, 0, 1, 0, 4], jnp.int32)
    y, new = jax.jit(SM._ssd_decode_update)(pool, x, dt, A, Bm, Cm, D, slots,
                                           jnp.int32(6))
    for b, s in enumerate(np.asarray(slots)):
        if s == 0:
            assert float(jnp.abs(y[b]).max()) == 0.0
            continue
        want_y, want_S = SM.ssd_recurrent_step(
            SM._ssd_from_pool(pool[6 + s], 4), x[b], dt[b], A, Bm[b], Cm[b], D)
        np.testing.assert_allclose(y[b], want_y, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(new[6 + s], SM._ssd_to_pool(want_S),
                                   rtol=1e-6, atol=1e-6)
    untouched = np.array([r for r in range(12) if r not in (7, 9, 10)])
    np.testing.assert_array_equal(new[untouched], pool[untouched])


# --------------------------------------------------------------------- #
# logits through the paged programs


def _prefill_then_decode(toy, steps):
    """Two requests of 37 and 150 tokens in rows 0 and 2 of three (row 1
    idle), slots 2 and 1: the prefill's last position and then each of
    ``steps`` decode steps, teacher-forced, against the reference's full
    forward. Returns the pools."""
    model, params = toy[:2]
    seqs = [tokens_of(1, 37 + steps), tokens_of(2, 150 + steps)]
    n_prompt, tables, slot_of = [37, 150], [np.array([1]), np.array([2, 3])], [2, 1]
    want = [reference_logits(toy, s) for s in seqs]
    pools = fresh_pools(model, 5, 4)
    for s, n, tb, sl, w in zip(seqs, n_prompt, tables, slot_of, want):
        lg, pools = prefill(model, params, pools, s[:n], tb, sl)
        np.testing.assert_allclose(lg, w[n - 1], rtol=0, atol=LOGIT_TOL)
    decode = jax.jit(model.forward_paged_decode)
    for step in range(steps):
        bt = np.zeros((3, 2), np.int32)
        bt[0, :1], bt[2] = tables
        t = np.zeros((3, 1), np.int32)
        pos = np.zeros((3,), np.int32)
        for row, (s, n) in zip((0, 2), zip(seqs, n_prompt)):
            t[row, 0], pos[row] = s[n + step], n + step
        lg, pools = decode(params, t, pools, bt, pos, None,
                           np.array([2, 0, 1], np.int32))
        for row, (w, n) in zip((0, 2), zip(want, n_prompt)):
            np.testing.assert_allclose(np.asarray(lg)[row], w[n + step],
                                       rtol=0, atol=LOGIT_TOL)
    return pools


def test_logits_of_prefill_and_24_decode_steps(toy):
    _prefill_then_decode(toy, 24)


def test_logits_through_the_decode_kernel():
    """The same run at a width the Mamba-2 decode kernel tiles (2 heads of
    a 64 x 128 state) with the paged programs on their Pallas forms, which
    the CPU interprets: the form the chip times
    (``ops/pallas/mamba2_decode_update.py`` through ``_mamba2_decode``),
    within the same LOGIT_TOL; the dummy slot, which the idle row names,
    and the slot of no row are as they were."""
    from deepspeed_tpu.ops import dispatch
    wide = dict(ssm_heads=2, ssm_head_dim=64, ssm_state=128)
    model, params, cfg, name_map = load_toy("flash", **wide)
    dispatch.reset()
    pools = _prefill_then_decode((model, params, {**cfg, **wide}, name_map), 8)
    assert dispatch.selected().get("ssd_decode=mamba2_kernel") == 9
    assert "kernel/mamba2_decode_update=interpret" in dispatch.selected()
    assert "ssd_decode=slot_gather" not in dispatch.selected()
    for st in pools["state"]:
        for slot in (0, 3):
            assert float(jnp.abs(st[0, slot] - 3.0).max()) == 0


def test_a_bf16_state_fails_the_tolerance(toy, monkeypatch):
    """The control that holds ``LOGIT_TOL`` to its purpose: the state rounded
    to bf16 wherever a step writes it (``reduce_precision``: a pair of casts
    XLA may elide) moves a decode step's logits past the tolerance."""
    model, params = toy[:2]
    step = SM.ssd_recurrent_step

    def rounding(S, *a):
        y, S = step(S, *a)
        return y, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)

    monkeypatch.setattr(SM, "ssd_recurrent_step", rounding)
    faulted = type(model)(model.config)          # a jit cache of its own
    toks = tokens_of(1, 37 + 24)
    want = reference_logits(toy, toks)
    table = np.array([1])
    _, pools = prefill(faulted, params, fresh_pools(faulted, 3, 3), toks[:37],
                       table, 1)
    worst = 0.0
    for pos in range(37, 61):
        lg, pools = jitted(faulted, "forward_paged_decode")(
            params, toks[None, pos:pos + 1], pools, table[None],
            np.array([pos], np.int32), None, np.array([1], np.int32))
        worst = max(worst, float(np.abs(np.asarray(lg)[0] - want[pos]).max()))
    assert worst > 5 * LOGIT_TOL, worst


@pytest.mark.parametrize("chunk", [5, 13])
def test_a_chunked_prefill_is_the_whole_prefill(toy, chunk):
    """Pieces that do not divide by the SSD chunk (8), each in a bucket of
    16: the last position's logits, the state and the conv state are the
    whole prefill's."""
    model, params = toy[:2]
    toks = tokens_of(7, 41)
    table = np.array([1])
    whole, pw = prefill(model, params, fresh_pools(model, 3, 3), toks, table, 1)
    pieces, pc = prefill(model, params, fresh_pools(model, 3, 3), toks, table, 1,
                         chunk=chunk, bucket=16)
    np.testing.assert_allclose(pieces, whole, rtol=0, atol=LOGIT_TOL)
    np.testing.assert_allclose(whole, reference_logits(toy, toks)[-1], rtol=0,
                               atol=LOGIT_TOL)
    for a, b in zip(pw["state"] + pw["conv"], pc["state"] + pc["conv"]):
        np.testing.assert_allclose(a[0, 1], b[0, 1], rtol=0, atol=1e-5)


def test_slots_a_fresh_start_and_a_neighbour_left_alone(toy):
    """A slot's next holder starts from zero whatever was left there (bit
    for bit the prefill into a zeroed pool), and a step that a slot's
    request takes no part in leaves that slot bit for bit."""
    model, params = toy[:2]
    toks = tokens_of(8, 20)
    table = np.array([1])
    zeroed = model.init_paged_cache(3, BS, jnp.float32, state_slots=4)
    lg0, p0 = prefill(model, params, zeroed, toks, table, 2)
    lg1, p1 = prefill(model, params, fresh_pools(model, 3, 4), toks, table, 2)
    np.testing.assert_array_equal(lg0, lg1)
    for a, b in zip(p0["state"] + p0["conv"], p1["state"] + p1["conv"]):
        np.testing.assert_array_equal(a[:, 2], b[:, 2])
    bt = np.zeros((2, 1), np.int32)
    bt[0] = table
    _, p2 = jax.jit(model.forward_paged_decode)(
        params, np.array([[3], [0]], np.int32), p1, bt,
        np.array([20, 0], np.int32), None, np.array([2, 0], np.int32))
    for a, b in zip(p1["state"], p2["state"]):
        np.testing.assert_array_equal(a[:, np.array([0, 1, 3])],
                                      b[:, np.array([0, 1, 3])])
        assert float(jnp.abs(a[:, 2] - b[:, 2]).max()) > 0
    for a, b in zip(p1["conv"], p2["conv"]):
        np.testing.assert_array_equal(a[:, np.array([1, 3])], b[:, np.array([1, 3])])


# --------------------------------------------------------------------- #
# the four multipliers


@pytest.mark.parametrize("ours,theirs,plain", [
    ("embedding_multiplier", "embedding_multiplier", 1.0),
    ("residual_multiplier", "residual_multiplier", 1.0),
    ("logits_scaling", "logits_scaling", 1.0),
    ("attn_scale", "attention_multiplier", 0.25),      # 1 / sqrt(16)
])
def test_each_multiplier_reaches_the_logits(toy, ours, theirs, plain):
    """A model with that one multiplier at the value a model without it has:
    its prefill's logits are the reference's under the same change, and not
    the published model's (the decode step's are the next test's and
    ``test_logits_of_prefill_and_24_decode_steps``')."""
    if ours == "attn_scale":
        toy = sharpened(toy)
    model, params = toy[:2]
    toks = tokens_of(11, 30)
    other = type(model)(dataclasses.replace(model.config, **{ours: plain}))
    got, _ = prefill(other, params, fresh_pools(other, 3, 3), toks,
                     np.array([1]), 1)
    want = reference_logits(toy, toks, **{theirs: plain})[-1]
    np.testing.assert_allclose(got, want, rtol=0, atol=20 * LOGIT_TOL)
    assert np.abs(want - reference_logits(toy, toks)[-1]).max() > 50 * LOGIT_TOL


def test_the_attention_scale_reaches_the_kernels():
    """At a head size the paged kernel tiles (64), with the kernels
    interpreted: the flash prefill and the paged decode take 1/64, not
    1/sqrt(64)."""
    from deepspeed_tpu.ops import dispatch
    dispatch.reset()            # what THIS test's programs select
    toy = sharpened(load_toy(backend="flash", head_size=64))
    model, params = toy[:2]
    toy[2]["head_dim"] = 64
    toks = tokens_of(12, 140)
    want = reference_logits(toy, toks)
    table = np.array([1, 2])
    lg, pools = prefill(model, params, fresh_pools(model, 4, 3), toks[:136],
                        table, 1)
    np.testing.assert_allclose(lg, want[135], rtol=0, atol=20 * LOGIT_TOL)
    decode = jax.jit(model.forward_paged_decode)
    for pos in range(136, 140):
        out, pools = decode(params, toks[None, pos:pos + 1], pools, table[None],
                            np.array([pos], np.int32), None, np.array([1], np.int32))
        np.testing.assert_allclose(np.asarray(out)[0], want[pos], rtol=0,
                                   atol=20 * LOGIT_TOL)
    assert {"paged_decode=paged_kernel", "paged_prefill=flash"} \
        <= set(dispatch.selected())
    wrong = reference_logits(toy, toks, attention_multiplier=0.125)
    assert np.abs(wrong[-1] - want[-1]).max() > 100 * LOGIT_TOL


# --------------------------------------------------------------------- #
# the spans


@pytest.mark.parametrize("program,scopes", [
    ("decode", ("in_proj", "short_conv", "ssd_state_update", "gated_norm")),
    ("prefill", ("in_proj", "short_conv", "ssd_chunk_scan", "gated_norm")),
    ("chunk", ("in_proj", "short_conv", "ssd_chunk_scan", "gated_norm")),
])
def test_the_mixers_scopes_are_in_the_lowered_programs(toy, program, scopes):
    model, params = toy[:2]
    pools = model.init_paged_cache(3, BS, jnp.float32, state_slots=3)
    i32 = np.int32
    if program == "decode":
        lowered = jax.jit(model.forward_paged_decode).lower(
            params, np.zeros((2, 1), i32), pools, np.zeros((2, 1), i32),
            np.zeros((2,), i32), None, np.zeros((2,), i32))
    elif program == "prefill":
        lowered = jax.jit(model.forward_paged_prefill).lower(
            params, np.zeros((1, 16), i32), pools, np.zeros((16,), i32),
            i32(3), i32(1))
    else:
        lowered = jax.jit(model.forward_paged_prefill_chunk).lower(
            params, np.zeros((1, 16), i32), pools, np.zeros((1, 1), i32),
            np.zeros((16,), i32), i32(16), i32(3), i32(1))
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"mamba2/{scope}" in text, scope
    absent = "ssd_chunk_scan" if program == "decode" else "ssd_state_update"
    assert absent not in text
