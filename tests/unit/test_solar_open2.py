"""Solar-Open2 on the paged programs against its plain reference
(``perfbench/reference/solar_open2_decoder.py``: float32, the recurrence
token by token, every held expert the slow way), on the ``solar_open2``
``tiny`` preset (a period of one gated NoPE GQA layer and three KDA layers,
head size 32 under d_model 64, a sigmoid router over 16 experts of which 2
are held and a token takes 4, a shared expert, untied head) with seeded
weights perturbed as ``perfbench/weights.py`` perturbs them.

Tolerances. Program and reference both compute in float32 here (the CPU's
default matmul precision is full float32), so they differ by the order of
sums and by the chunked form of the recurrence (a 64 x 64 triangular solve a
chunk instead of 64 rank-one updates): logits of magnitude ~0.6 agree to
9e-7 (prefill whole or in chunks, then 12 decode steps). ``LOGIT_TOL`` 2e-5
is twenty times that and a hundredth of what the smallest control moves
them by: a KDA state kept in bf16 moves the logits by 3e-3 (the state is
summed into for every token of a request), the selection bias added to the
weights by more. The controls below hold the tolerance to that.
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
from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.moe import sharded_moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench")
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from reference import solar_open2_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-solar-open2-tiny"
LOGIT_TOL = 2e-5
BS = 128


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def load_toy(backend="auto", **over):
    """(model, float32 params, the reference's cfg, the name map) of the toy
    configuration, ``over`` laid over its preset and the reference's cfg
    alike (``head_size`` is the reference's ``head_dim``)."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"], **over, attention_backend=backend)
    params = make_params(model, 3100000031, jnp.float32, jax.devices()[:1])
    cfg = correctness.reference_config(config, name_map)
    cfg.update({"head_dim" if k == "head_size" else k: v for k, v in over.items()})
    return model, params, cfg, name_map


#: a width the KDA decode kernel tiles: two heads of a 128 x 128 state
WIDE = dict(lin_heads=2, lin_head_dim=128)


@pytest.fixture(scope="module")
def toy():
    return load_toy()


def tokens_of(seed, n, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def reference_logits(toy, tokens):
    _, params, cfg, name_map = toy
    w = ref.Weights(params, name_map)
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens)[None])
    return np.asarray(ref.logits_rows(cfg, w, h[0]))


def paged_logits(model, params, tokens, n_prompt, chunk=0, slot=2, rows=3,
                 decode_slot=None, decode_model=None):
    """The logits of positions ``n_prompt - 1 ..`` through the paged
    programs: the prompt prefilled (whole, or ``chunk`` tokens a piece into
    the request's blocks and state slot), then the rest decoded one token a
    step, teacher-forced, in row 1 of ``rows`` (the others idle), from the
    request's state slot (``decode_slot``: a planted fault's) by
    ``decode_model`` (the same weights under another backend), if given."""
    n_blocks = -(-len(tokens) // BS)
    pools = model.init_paged_cache(n_blocks + 2, BS, jnp.float32,
                                   state_slots=rows + 1)
    # a slot's last holder left something there: it must not be inherited
    pools["state"] = tuple(a + 3.0 for a in pools["state"])
    pools["conv"] = tuple(a - 2.0 for a in pools["conv"])
    table = np.arange(1, n_blocks + 1, dtype=np.int32)
    out = []
    start = 0
    while start < n_prompt:
        n = min(chunk or n_prompt, n_prompt - start)
        Tb = -(-n // 128) * 128
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :n] = tokens[start:start + n]
        p_t = start + np.arange(Tb)
        slots = np.where(np.arange(Tb) < n,
                         table[np.minimum(p_t // BS, n_blocks - 1)] * BS + p_t % BS,
                         p_t % BS).astype(np.int32)
        if chunk:
            lg, pools = jax.jit(model.forward_paged_prefill_chunk)(
                params, toks, pools, table[None], slots, np.int32(start),
                np.int32(n - 1), np.int32(slot))
        else:
            lg, pools = jax.jit(model.forward_paged_prefill)(
                params, toks, pools, slots, np.int32(n - 1), np.int32(slot))
        start += n
    out.append(np.asarray(lg)[0])
    decode = jax.jit((decode_model or model).forward_paged_decode)
    for pos in range(n_prompt, len(tokens)):
        bt = np.zeros((rows, n_blocks), np.int32)
        bt[1] = table
        t = np.zeros((rows, 1), np.int32)
        t[1, 0] = tokens[pos]
        positions = np.zeros((rows,), np.int32)
        positions[1] = pos
        ss = np.zeros((rows,), np.int32)
        ss[1] = slot if decode_slot is None else decode_slot
        lg, pools, _ = decode(params, t, pools, bt, positions, None, ss)
        out.append(np.asarray(lg)[1])
    return np.stack(out), pools


# --------------------------------------------------------------------- #
# the recurrence: chunked form against the one-token update


@pytest.mark.parametrize("decay", ["family", "minus_10_a_step"])
@pytest.mark.parametrize("length", [64, 192])
def test_chunked_form_against_the_recurrence(length, decay):
    """``kda_chunked`` against ``kda_recurrent_step`` token by token, from a
    state that is not zero. At a decay of -10 a step the cumulated log decay
    reaches -640 within a chunk: exp(+640) overflows float32, so a form
    that divides by a cumulated decay gives inf or nan; decays taken
    between positions stay finite and agree."""
    rng = np.random.default_rng(length)
    H, dk = 3, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((length, H, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((length, H, dk)))
    v = rng.standard_normal((length, H, dk))
    beta = 2 * rng.random((length, H))
    g = -1.6 * rng.random((length, H, dk)) if decay == "family" \
        else np.full((length, H, dk), -10.0)
    S0 = rng.standard_normal((H, dk, dk))
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    o, S = jax.jit(SM.kda_chunked)(f(S0), f(q), f(k), f(v), f(g), f(beta))
    want_S, want_o = f(S0), []
    for t in range(length):
        ot, want_S = SM.kda_recurrent_step(want_S, f(q[t]), f(k[t]), f(v[t]),
                                          f(g[t]), f(beta[t]))
        want_o.append(ot)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    np.testing.assert_allclose(np.asarray(o), np.stack(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), atol=2e-5)


# --------------------------------------------------------------------- #
# each kind of layer alone


def _layer_inputs(toy, position, n=100, bucket=128):
    model, params, cfg, name_map = toy
    lp = jax.tree.map(lambda a: a[0], params["layers"][position])
    w = ref.Weights(params, name_map).layer(position)
    x = np.random.default_rng(position).standard_normal(
        (1, bucket, model.config.d_model)).astype(np.float32)
    return model.config, lp, cfg, w, jnp.asarray(x), n


def test_the_gated_gqa_layer_alone(toy):
    """Head size 32 under d_model 64 (4 query heads over 2 key/value heads),
    no positions, the sigmoid output gate: x + Attn(RMS(x)) of the paged
    prefill against the reference's, a prompt of 100 in a bucket of 128."""
    mcfg, lp, cfg, w, x, n = _layer_inputs(toy, 0)
    assert mcfg.head_dim == 32 != mcfg.d_model // mcfg.n_head
    pool = jnp.zeros((3, BS, mcfg.kv_heads * mcfg.head_dim), jnp.float32)
    slots = jnp.arange(BS, dtype=jnp.int32) + BS
    a, kp, _ = T._paged_prefill_attention(
        mcfg, T._norm(mcfg, x, lp["ln_attn"]), lp["attn"], None, pool, pool, slots)
    want = ref.gqa(ref._Cfg(cfg), w, x[:, :n])
    np.testing.assert_allclose(np.asarray(x + a)[:, :n], np.asarray(want),
                               atol=LOGIT_TOL)
    assert float(jnp.abs(kp[1]).max()) > 0 and float(jnp.abs(kp[0]).max()) == 0


@pytest.mark.parametrize("n", [100, 128, 37])
def test_the_kda_layer_alone(toy, n):
    """The chunked prefill of one KDA layer, from a slot that holds junk,
    against the reference's token-by-token recurrence at lengths that are
    not whole chunks; the state and the conv state it leaves are those after
    the last REAL position."""
    mcfg, lp, cfg, w, x, _ = _layer_inputs(toy, 1)
    H, dk, K = mcfg.lin_heads, mcfg.lin_head_dim, SM.KDA_CONV_KERNEL
    state = jnp.full((4, H, dk, dk), 7.0, jnp.float32)
    conv = jnp.full((4, K - 1, 3 * H * dk), -5.0, jnp.float32)
    xn = T._norm(mcfg, x, lp["ln_attn"])
    a, state, conv = jax.jit(
        lambda *args: SM._kda_prefill(mcfg, *args, True))(
        xn, lp["lin"], state, conv, jnp.int32(2), jnp.int32(n))
    want = ref.kda(ref._Cfg(cfg), w, x[:, :n])
    np.testing.assert_allclose(np.asarray(x + a)[:, :n], np.asarray(want),
                               atol=LOGIT_TOL)
    # only the request's slot was written
    assert float(jnp.abs(state[1] - 7.0).max()) == 0
    assert float(jnp.abs(conv[3] + 5.0).max()) == 0
    # the conv state: the last K-1 conv inputs before position n
    u = np.concatenate([np.asarray(xn[0] @ lp["lin"][k]) for k in ("wq", "wk", "wv")], -1)
    np.testing.assert_allclose(np.asarray(conv[2]), u[n - (K - 1):n], atol=1e-5)
    # one more token from that state is the reference's next position
    a1, _, _ = SM._kda_decode(mcfg, xn[:, n:n + 1] if n < x.shape[1] else xn[:, :1],
                              lp["lin"], state, conv, 0, jnp.asarray([2]))
    if n < x.shape[1]:
        want1 = ref.kda(ref._Cfg(cfg), w, x[:, :n + 1])[:, n]
        np.testing.assert_allclose(np.asarray(x[:, n] + a1[:, 0]),
                                   np.asarray(want1), atol=LOGIT_TOL)


# --------------------------------------------------------------------- #
# the whole stack through the paged programs


@pytest.mark.parametrize("n_prompt,chunk", [(70, 0), (200, 0), (200, 128),
                                            (128, 128)])
def test_prefill_then_decode_against_the_full_forward(toy, n_prompt, chunk):
    """The prompt prefilled (whole, or in chunks that carry the state from
    one to the next) and 12 tokens decoded through the pools, logits against
    the reference's full forward of the same tokens."""
    model, params, _, _ = toy
    tokens = tokens_of(n_prompt, n_prompt + 12)
    got, pools = paged_logits(model, params, tokens, n_prompt, chunk)
    want = reference_logits(toy, tokens)[n_prompt - 1:]
    assert np.abs(got - want).max() <= LOGIT_TOL, np.abs(got - want).max()
    # idle rows went to the dummy slot and no live slot but the request's
    for st in pools["state"]:
        assert float(jnp.abs(st[0, 1] - 3.0).max()) == 0
        assert float(jnp.abs(st[0, 3] - 3.0).max()) == 0


def test_the_decode_kernel_against_the_full_forward():
    """The same run at a width the KDA decode kernel tiles (``WIDE``) with
    the paged programs on their Pallas forms, which the CPU interprets: the
    prompt prefilled, 12 tokens decoded through
    ``ops/pallas/kda_decode_update.py``, logits against the reference's full
    forward within the same LOGIT_TOL; the idle rows' dummy slot and the
    slots of no row are as they were."""
    from deepspeed_tpu.ops import dispatch
    wide = load_toy("flash", **WIDE)
    dispatch.reset()
    tokens = tokens_of(32, 70 + 12)
    got, pools = paged_logits(*wide[:2], tokens, 70)
    assert dispatch.selected().get("kda_decode=kda_kernel") == 3
    assert "kernel/kda_decode_update=interpret" in dispatch.selected()
    want = reference_logits(wide, tokens)[69:]
    assert np.abs(got - want).max() <= LOGIT_TOL, np.abs(got - want).max()
    for st in pools["state"]:
        for slot in (0, 1, 3):
            assert float(jnp.abs(st[0, slot] - 3.0).max()) == 0


def test_the_grouped_expert_kernel_against_the_full_forward():
    """The paged programs' experts through
    ``ops/pallas/grouped_expert_mlp.py`` (interpreted): a SHARE of the
    router's experts (choices held elsewhere reach no expert here), sigmoid
    scores with the selection bias, the shared expert beside them, one
    stack of experts a position of the period read in place. The prefill's
    128-row bucket and the decode step's 3 rows both take the kernel."""
    from deepspeed_tpu.ops import dispatch
    flash = load_toy("flash")
    dispatch.reset()
    tokens = tokens_of(33, 70 + 12)
    got, _ = paged_logits(*flash[:2], tokens, 70)
    chosen = dispatch.selected()
    assert chosen["experts=grouped_kernel"] == 2 * len(flash[0].config.period)
    assert "experts=dense" not in chosen
    want = reference_logits(flash, tokens)[69:]
    assert np.abs(got - want).max() <= LOGIT_TOL, np.abs(got - want).max()


def test_a_bf16_state_fails_the_tolerance(toy, monkeypatch):
    """The control of LOGIT_TOL: the same run with the recurrent state
    rounded to bf16 after every update, as a state pool kept in bf16 would
    hold it, is not within it."""
    model, params, _, _ = toy
    step, chunked = SM.kda_recurrent_step, SM.kda_chunked
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def rounded_step(*a):
        o, S = step(*a)
        return o, bf16(S)

    def rounded_chunked(*a, **k):
        o, S = chunked(*a, **k)
        return o, bf16(S)

    monkeypatch.setattr(SM, "kda_recurrent_step", rounded_step)
    monkeypatch.setattr(SM, "kda_chunked", rounded_chunked)
    tokens = tokens_of(5, 70 + 12)
    got, _ = paged_logits(model, params, tokens, 70)
    want = reference_logits(toy, tokens)[69:]
    assert np.abs(got - want).max() > 5 * LOGIT_TOL


def _beta_1(monkeypatch, model):
    monkeypatch.setattr(SM, "KDA_BETA_SCALE", 1.0)
    return model


def _planted(monkeypatch, **fields):
    """The KDA kind's record with ``fields`` in place of its own."""
    monkeypatch.setitem(SM.STATE_MIXERS, T.LINEAR_ATTENTION,
                        SM.STATE_MIXERS[T.LINEAR_ATTENTION]._replace(**fields))


def _lost_conv_state(monkeypatch, model):
    real = SM._kda_decode
    _planted(monkeypatch, decode=lambda cfg, x, lp, state, conv, *a:
             real(cfg, x, lp, state, jnp.zeros_like(conv), *a))
    return model


def _inherited_slot(monkeypatch, model):
    real = SM._kda_prefill
    _planted(monkeypatch, prefill=lambda *a: real(*a[:-1], False))
    return model


def _another_share(monkeypatch, model):
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        return get_model(**json.load(f)["preset"], share=1)


@pytest.mark.parametrize("plant", [_beta_1, _lost_conv_state, _inherited_slot,
                                   _another_share, "neighbours_slot"],
                         ids=lambda p: getattr(p, "__name__", p).lstrip("_"))
def test_a_planted_fault_fails_the_tolerance(toy, monkeypatch, plant):
    """Further controls, in the regime the cell's check runs in (the preset
    puts the toy's residual stream and branches at the cell's scales): beta
    without its factor 2, the conv state lost at every decode step, a first
    piece that inherits its slot's last holder, the held experts taken for
    another share's, and a decode step handed the slot after the request's.
    The cell's own check, an argmax within 4 bf16 steps on 16 tokens, sees
    only the gross ones of these on the chip (PERF.md section 6, PR 31);
    these logits are what holds the rest."""
    model, params, _, _ = toy
    jax.clear_caches()          # the programs are traced with the plant in
    tokens = tokens_of(5, 70 + 12)
    if plant == "neighbours_slot":
        got, _ = paged_logits(model, params, tokens, 70, decode_slot=3)
    else:
        got, _ = paged_logits(plant(monkeypatch, model), params, tokens, 70)
    jax.clear_caches()
    want = reference_logits(toy, tokens)[69:]
    assert np.abs(got - want).max() > 5 * LOGIT_TOL


# --------------------------------------------------------------------- #
# the router and the chip's share


def test_sigmoid_routing_with_a_selection_bias():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((33, 16)).astype(np.float32) * 2
    bias = rng.standard_normal(16).astype(np.float32)
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    order = np.argsort(-(s + bias), axis=-1)[:, :5]
    w, e, scores = sharded_moe.topk_routing(
        jnp.asarray(logits, jnp.bfloat16).astype(jnp.float32), 5)
    assert w.dtype == scores.dtype == jnp.float32
    w, e, scores = sharded_moe.topk_routing(
        jnp.asarray(logits), 5, True, scoring="sigmoid",
        select_bias=jnp.asarray(bias))
    np.testing.assert_array_equal(np.sort(np.asarray(e)), np.sort(order))
    picked = np.take_along_axis(s, np.asarray(e), -1)
    # the bias chooses and is not in the weights
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-5)
    with pytest.raises(ValueError, match="softmax|sigmoid"):
        sharded_moe.topk_routing(jnp.asarray(logits), 5, scoring="tanh")


def _moe_layer(share, n_shares, E=16, k=4, D=32, F=16, shared=16):
    held = E // n_shares
    cfg = T.TransformerConfig(vocab_size=64, n_layer=1, n_head=2, d_model=D,
                              d_ff=F, norm="rmsnorm", activation="swiglu")
    return MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", expert_activation="swiglu", scoring="sigmoid",
        norm_topk_prob=True, num_experts=held, k=k, expert_d_ff=F,
        router_experts=None if n_shares == 1 else E,
        expert_offset=share * held, shared_expert_d_ff=shared))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share is tied to the model: at a toy size the routed parts of all
    8 shares (each holding 2 of 16 experts, routing over all 16) plus the
    shared expert ONCE equal the uncut MoE layer, and the uncut reference's.
    A share's counts are its own experts' loads and nothing is dropped."""
    E, k, D, n_shares = 16, 4, 32, 8
    whole = _moe_layer(0, 1)
    lp = jax.tree.map(lambda a: a[0],
                      whole.init_params(jax.random.key(4))["layers"]["mlp"])
    lp["b_select"] = 0.3 * jax.random.normal(jax.random.key(5), (E,))
    x = jax.random.normal(jax.random.key(6), (2, 19, D))
    valid = jnp.asarray(np.random.default_rng(1).random(38) > 0.2)
    full, _, n_full, owed_full = whole._nodrop_mlp(lp, x, valid)
    assert int(owed_full) == int(valid.sum()) * k == int(n_full.sum())
    shared = {k_: jnp.zeros_like(v) for k_, v in lp["shared"].items()}
    routed_sum, counts = 0.0, []
    for share in range(n_shares):
        m = _moe_layer(share, n_shares)
        held = slice(share * 2, share * 2 + 2)
        lps = {**lp, **{k_: lp[k_][held] for k_ in ("w_gate", "w_up", "w_down")},
               "shared": shared}
        out, _, n, owed = m._nodrop_mlp(lps, x, valid)
        assert int(owed) == int(n.sum())          # nothing dropped
        routed_sum = routed_sum + out
        counts.append(np.asarray(n))
    only_shared, _, _, _ = _moe_layer(0, n_shares)._nodrop_mlp(
        {**lp, **{k_: jnp.zeros_like(lp[k_][:2]) for k_ in ("w_gate", "w_up", "w_down")}},
        x, valid)
    np.testing.assert_allclose(np.asarray(routed_sum + only_shared),
                               np.asarray(full), atol=2e-6)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(n_full))
    # and the uncut reference's layer (its MoE half): h + MoE(RMS(h))
    w = {"ln2_g": jnp.ones((D,)), "router": lp["gate_w"], "b_select": lp["b_select"],
         "w_gate": lp["w_gate"], "w_up": lp["w_up"], "w_down": lp["w_down"],
         "shared_gate": lp["shared"]["w_gate"], "shared_up": lp["shared"]["w_up"],
         "shared_down": lp["shared"]["w_down"]}
    rcfg = ref._Cfg(eps=1e-5, n_experts=E, experts_per_token=k, experts_held=E,
                    expert_offset=0, norm_topk_prob=True)
    normed = ref._rms(x, w["ln2_g"], 1e-5)
    m, c = ref.route(rcfg, w, x)
    want = ref.expert(m, jnp.ones(m.shape[:-1]), w["shared_gate"], w["shared_up"],
                      w["shared_down"])
    for e in range(E):
        want = want + ref.expert(m, c[..., e], w["w_gate"][e], w["w_up"][e],
                                 w["w_down"][e])
    got, _, _, _ = whole._nodrop_mlp(lp, normed, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_selection_bias_in_the_weights_fails_the_tolerance(toy, monkeypatch):
    """A second control: weights taken from score + bias move the logits far
    beyond LOGIT_TOL."""
    model, params, _, _ = toy
    real = sharded_moe.topk_routing

    def biased(logits, k, norm, scoring="softmax", select_bias=None):
        w, e, p = real(logits, k, False, scoring=scoring, select_bias=select_bias)
        w = w + jnp.take(select_bias.astype(jnp.float32), e)
        return w / jnp.sum(w, -1, keepdims=True), e, p

    from deepspeed_tpu.models import moe_lm
    monkeypatch.setattr(moe_lm, "topk_routing", biased)
    tokens = tokens_of(9, 60)
    got, _ = paged_logits(model, params, tokens, 50)
    assert np.abs(got - reference_logits(toy, tokens)[49:]).max() > 5 * LOGIT_TOL


# --------------------------------------------------------------------- #
# the configuration


def test_the_published_model_counts_its_parameters():
    """The reading of config.json behind the preset, at the published sizes:
    250.29 B parameters (48 layers, 320 experts, 196,608 rows), and the cut
    the cell serves 3,308 M."""
    cut = get_model("solar_open2", "250b-4l-ep8")
    count = lambda m: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(  # noqa: E731
        jax.eval_shape(m.init_params, jax.random.key(0))))
    assert count(cut) == 3_308_377_920
    full = get_model("solar_open2", "250b-4l-ep8", n_layer=48, vocab_size=196608,
                     moe=dict(num_experts=320, router_experts=None))
    assert abs(count(full) / 1e9 - 250.29) < 0.005
    cfg = cut.config
    assert cfg.cache_spec == {"kv": 1, "state": 3, "window": 0, "latent": 0} \
        and cfg.n_periods == 1
    assert cfg.period == ("attention",) + ("linear_attention",) * 3
    pools = jax.eval_shape(lambda: cut.init_paged_cache(2064, 128, jnp.bfloat16,
                                                        state_slots=129))
    assert [a.shape for a in pools["state"]] == [(1, 129, 64, 128, 128)] * 3
    assert pools["state"][0].dtype == jnp.float32
    assert [a.shape for a in pools["conv"]] == [(1, 129, 3, 24576)] * 3
    assert pools["k"].shape == (1, 2064, 128, 1024)


def test_what_a_layer_pattern_does_not_run():
    model = get_model("solar_open2", "tiny")
    params = model.init_params(jax.random.key(0))
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="layer pattern"):
        model.forward(params, toks)
    with pytest.raises(NotImplementedError, match="layer pattern"):
        model.forward_cached(params, toks, model.init_cache(1, 16), jnp.int32(0))
    pools = model.init_paged_cache(4, BS, jnp.float32, state_slots=3)
    with pytest.raises(NotImplementedError, match="rewound"):
        model.forward_paged_verify(params, toks, pools, jnp.zeros((1, 2), jnp.int32),
                                   jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="state slot"):
        model.forward_paged_prefill(params, jnp.zeros((1, 128), jnp.int32), pools,
                                    jnp.arange(128), jnp.int32(3))
    with pytest.raises(ValueError, match="state_slots"):
        model.init_paged_cache(4, BS, jnp.float32)
    with pytest.raises(ValueError, match="whole periods"):
        T.init_params(dataclasses.replace(model.config, n_layer=6), jax.random.key(0))
