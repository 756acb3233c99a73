"""LongCat-Flash's layer on the paged programs against its plain reference
(``perfbench/reference/longcat_flash_decoder.py``: float32, the latent
EXPANDED to heads at every position, no cache), on the ``longcat_flash``
``tiny`` preset (2 published layers: four latent-attention sub-blocks with
keys 32 + 64 and values 16 over a latent of 128, four dense MLPs, two
shortcut MoEs whose router scores 8 experts, 4 of them held, and 4
zero-compute experts, 3 a token, weights x 6 not normalised; an untied
head) with seeded weights perturbed as ``perfbench/weights.py`` perturbs them
(the selection bias among them). The reference itself is held to
``transformers``' ``LongcatFlashForCausalLM`` in ``test_module_inject.py``
(that file's worker has paid for torch).

What is held here: prefill through the paged latent cache and decode by the
ABSORBED path against the reference's logits; the absorbed and the expanded
attention on one row; the shares of an expert-parallel layer adding up to
the uncut layer, the zero-compute experts counted once; the parameter counts
of the cut and of the published model; continuous batching with more
callers than rows, a recompute-preemption and the refusals, through
``init_inference``; ``routed_scaling_factor`` 1 tracing to the program it
traced to before the field came back. The kernel is
``ops/test_latent_decode_attention.py``'s.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of sums and by the absorbed form's reassociation
(``(q Wk) . c`` for ``q . (c Wk)``): logits of magnitude ~2 agree to 3e-6
after a prefill and 12 decode steps. ``LOGIT_TOL`` 2e-5 is six times that
and a hundredth of what the smallest control moves them by (a dropped
shortcut, a zero expert that returns nothing, a missing lora scale, the
half-split rope: ``test_each_control_fails_the_tolerance``).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import latent_attention as LA
from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.monitor.metrics import get_registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench")
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from reference import longcat_flash_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-longcat-flash-tiny"
LOGIT_TOL = 2e-5
BS = 128


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def load_toy(**over):
    """(model, float32 params, the reference's cfg, the name map) of the toy
    configuration, ``over`` laid over its preset."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"], **over)
    params = make_params(model, 3100000048, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


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


_JITTED = {}


def jitted(model, name):
    key = (id(model), name)
    if key not in _JITTED:
        _JITTED[key] = (model, jax.jit(getattr(model, name)))
    return _JITTED[key][1]


def prefill(model, params, pools, tokens, table, bucket=128):
    n = len(tokens)
    Tb = -(-n // bucket) * bucket
    toks = np.zeros((1, Tb), np.int32)
    toks[0, :n] = tokens
    p_t = np.arange(Tb)
    slots = np.where(p_t < n, table[np.minimum(p_t // BS, len(table) - 1)] * BS
                     + p_t % BS, p_t % BS).astype(np.int32)
    lg, pools = jitted(model, "forward_paged_prefill")(
        params, toks, pools, slots, np.int32(n - 1))
    return np.asarray(lg)[0], pools


def served_logits(model, params, tokens, n_prompt):
    """The logits after each of ``tokens[n_prompt - 1:]``: the prompt's last
    from a prefill, the others from decode steps (teacher-forced) in row 1
    of three, beside an idle row and a row that decodes something else."""
    pools = model.init_paged_cache(12, BS, jnp.float32)
    pools = {k: a + 3.0 for k, a in pools.items()}    # what the last holder left
    table = np.array([3, 7, 5, 0], np.int32)
    other = np.array([9, 2, 0, 0], np.int32)
    lg, pools = prefill(model, params, pools, tokens[:n_prompt], table)
    _, pools = prefill(model, params, pools, tokens_of(99, 140), other)
    out = [lg]
    tables = np.stack([np.zeros(4, np.int32), table, other])
    for i in range(n_prompt, len(tokens)):
        toks = np.array([[0], [tokens[i]], [7]], np.int32)
        pos = np.array([0, i, 140 + i - n_prompt], np.int32)
        lg, pools, counts = jitted(model, "forward_paged_decode")(
            params, toks, pools, tables, pos)
        out.append(np.asarray(lg)[1])
    return np.stack(out), np.asarray(counts)


# --------------------------------------------------------------------- #
# what the preset builds


def test_the_cut_and_the_published_model_count_their_parameters():
    """The cell's cut as ``perfbench/configs/longcat-flash-omni.json``
    states it, part by part, and the published whole (the card's 560B)."""
    model = get_model("longcat_flash", "omni-4l-ep32")
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    first, second = shapes["layers"]
    assert count(first["attn"]) == 4 * 90_572_800
    assert count(first["mlp"]) == 4 * 226_492_416
    assert count(first["moe"]) == 4 * (4_719_360 + 16 * 37_748_736)
    assert count(second) == 4 * (90_572_800 + 226_492_416 + 2 * 6144)
    assert count(shapes) == 5_172_749_312 == model.num_parameters
    cfg = model.config
    assert cfg.cache_spec == {"kv": 0, "state": 0, "window": 0, "latent": 8}
    assert cfg.latent_row == 576 and cfg.n_periods == 4
    # a row's 576 values lie in 640 lanes: whole tiles of the chip's memory
    assert cfg.latent_pool_row == 640
    pools = jax.eval_shape(lambda: model.init_paged_cache(2305, 128))
    assert {k: a.shape for k, a in pools.items()} == \
        {"c": (8, 2305, 128, 640)}
    whole = get_model("longcat_flash", "omni-4l-ep32", n_layer=56,
                      vocab_size=131072,
                      moe={"num_experts": 512, "router_experts": None})
    published = 28 * (638_874_368 + 512 * 37_748_736) \
        + 2 * 131072 * 6144 + 6144
    assert count(jax.eval_shape(whole.init_params, jax.random.key(0))) \
        == published == whole.num_parameters == 560_664_980_480


def test_the_toy_is_the_cells_configuration_in_small(toy):
    model, params, cfg, _ = toy
    c, m = model.config, model.moe
    assert c.period == ("latent_attention",) * 2 and c.n_periods == 2
    assert (c.qk_nope_head_dim + c.qk_rope_head_dim) != c.v_head_dim
    assert (m.num_experts, m.router_experts, m.zero_experts, m.k) == (4, 8, 4, 3)
    assert m.shortcut and m.select_bias and not m.norm_topk_prob
    assert m.routed_scaling_factor == 6.0 and c.rope_interleaved
    assert "moe" in params["layers"][0] and "moe" not in params["layers"][1]
    # the seeded draw is the preset's; the library's bias starts at zero
    bias = params["layers"][0]["moe"]["select_bias"]
    assert 0.3 * m.select_bias_init_std < float(jnp.std(bias)) \
        < 3 * m.select_bias_init_std == 1.5 / model.router_width
    plain = get_model("longcat_flash", "tiny", moe={"select_bias_init_std": 0.0})
    zero = plain.init_params(jax.random.key(0))["layers"][0]["moe"]["select_bias"]
    assert zero.shape == bias.shape and not float(jnp.abs(zero).max())
    assert MoEConfig(select_bias=True).select_bias_init_std == 0.0


# --------------------------------------------------------------------- #
# the program against the reference


@pytest.mark.parametrize("n_prompt", [1, 37, 128, 200])
def test_prefill_then_absorbed_decode_gives_the_references_logits(toy, n_prompt):
    model, params, _, _ = toy
    tokens = tokens_of(n_prompt, n_prompt + 12)
    got, counts = served_logits(model, params, tokens, n_prompt)
    want = reference_logits(toy, tokens)[n_prompt - 1:]
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOL)
    # a decode step's counts, a published layer a row: the held experts',
    # what they owed, the zero experts' and all the router made (two real
    # rows x top-3)
    assert counts.shape == (2, 4 + 3)
    assert (counts[:, -1] == 6).all() and (counts[:, :4].sum(1) == counts[:, 4]).all()
    assert (counts[:, 4] + counts[:, 5] <= 6).all()


def _without_shortcut(model, params):
    layers = list(params["layers"])
    moe = {**layers[0]["moe"],
           "w_down": jnp.zeros_like(layers[0]["moe"]["w_down"])}
    return model, {**params, "layers": (
        {**layers[0], "moe": moe}, layers[1])}


CONTROLS = {
    "no_lora_scale": lambda m, p: (
        MoECausalLM(dataclasses.replace(m.config, mla_lora_scale=False),
                    m.moe), p),
    "half_split_rope": lambda m, p: (
        MoECausalLM(dataclasses.replace(m.config, rope_interleaved=False),
                    m.moe), p),
    "zero_experts_return_nothing": lambda m, p: (
        MoECausalLM(m.config, dataclasses.replace(
            m.moe, zero_experts=0, router_experts=12)), p),
    "another_shares_experts": lambda m, p: (
        MoECausalLM(m.config, dataclasses.replace(m.moe, expert_offset=4)), p),
    "scaling_factor_one": lambda m, p: (
        MoECausalLM(m.config, dataclasses.replace(
            m.moe, routed_scaling_factor=1.0)), p),
    "held_experts_return_nothing": _without_shortcut,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_fails_the_tolerance(toy, control):
    """One equation wrong at a time moves the logits by a hundred
    tolerances or more: the tolerance holds each of them."""
    model, params, _, _ = toy
    faulty, fparams = CONTROLS[control](model, params)
    tokens = tokens_of(5, 60)
    got, _ = served_logits(faulty, fparams, tokens, 50)
    want = reference_logits(toy, tokens)[49:]
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_the_absorbed_and_the_expanded_attention_agree_on_a_row(toy):
    """The last position of a sequence, once through the expanded form over
    the sequence's own rows and once through the absorbed form over the
    same rows in pool blocks."""
    model, params, _, _ = toy
    cfg = model.config
    lp = jax.tree.map(lambda a: a[1], params["layers"][1]["attn"])
    S = 150
    x = jax.random.normal(jax.random.key(4), (1, S, cfg.d_model))
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    q_nope, q_rope, rows = LA.project(cfg, x, lp, positions)
    want = LA.expanded_attention(cfg, q_nope, q_rope, rows, lp)[0, -1]
    table = np.array([[4, 1, 0]], np.int32)
    cp = jnp.full((6, BS, cfg.latent_pool_row), 7.0)
    flat = np.asarray(table[0])[np.arange(S) // BS] * BS + np.arange(S) % BS
    cp = LA._scatter(cp, rows, flat)
    got = LA.absorbed_attention(cfg, q_nope[:, -1], q_rope[:, -1], lp, cp,
                                table, jnp.array([S - 1], jnp.int32))[0]
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """Every share of the expert-parallel layer computes the MoE for the
    same rows with its own experts and every zero-compute expert: the
    shares' outputs, the zero experts' part counted once, are the uncut
    reference's MoE, and with the rest of the layer its output."""
    model, params, cfg, name_map = toy
    full = get_model("longcat_flash", "tiny",
                     moe={"num_experts": 8, "router_experts": None})
    fparams = make_params(full, 11, jnp.float32, jax.devices()[:1])
    lp = jax.tree.map(lambda a: a[0], fparams["layers"][0]["moe"])
    m = jax.random.normal(jax.random.key(2), (1, 40, 64))
    outs = []
    for share in (0, 1):
        part = get_model("longcat_flash", "tiny", share=share)
        held = {k: (w[4 * share:4 * share + 4]
                    if k in ("w_gate", "w_up", "w_down") else w)
                for k, w in lp.items()}
        out, _, counts, owed, routed = part._nodrop_mlp(held, m)
        assert int(counts.sum()) == int(owed) and int(routed[1]) == 40 * 3
        outs.append(np.asarray(out))
    w = ref.Weights(fparams, name_map).layer(0)
    whole = {**cfg, "experts_held": 8}
    none_held = {**cfg, "experts_held": 0}
    with jax.default_matmul_precision("highest"):
        zero_part = np.asarray(ref.moe(none_held, w["moe"], m))
        want = np.asarray(ref.moe(whole, w["moe"], m))
        np.testing.assert_allclose(outs[0] + outs[1] - zero_part, want,
                                   rtol=0, atol=5e-6)
        # and the reference's own shares, each with the stacks it holds
        held = lambda s: {k: a[4 * s:4 * s + 4] if k.startswith("e_") else a  # noqa: E731
                          for k, a in w["moe"].items()}
        for s in (0, 1):
            np.testing.assert_allclose(
                np.asarray(ref.moe({**cfg, "expert_offset": 4 * s}, held(s), m)),
                outs[s], rtol=0, atol=5e-6)
        parts = [ref.moe({**cfg, "expert_offset": 4 * s}, held(s), m,
                         zero=False) for s in (0, 1)]
        np.testing.assert_allclose(np.asarray(parts[0] + parts[1]) + zero_part,
                                   want, rtol=0, atol=5e-6)
    assert np.abs(zero_part).max() > 0.1 and np.abs(want - zero_part).max() > 0.01


# --------------------------------------------------------------------- #
# routed_scaling_factor came back


@pytest.mark.parametrize("family", ["olmoe", "solar_open2"])
def test_a_scaling_factor_of_one_traces_to_the_program_without(family):
    """``MoEConfig.routed_scaling_factor`` 1.0 puts no multiply into a
    model's programs (what ``test_sdar.py`` pins for these toys by digest),
    and another value computes: the MoE's part of the logits scales."""
    base = get_model(family, "tiny", max_seq=256)
    assert base.moe.routed_scaling_factor == 1.0
    scaled = MoECausalLM(base.config, dataclasses.replace(
        base.moe, routed_scaling_factor=2.5))
    params = base.init_params(jax.random.key(0))
    lp = jax.tree.map(lambda a: a[0], (
        params["layers"][0] if base.config.layer_kinds else params["layers"])["mlp"])
    x = jax.random.normal(jax.random.key(1), (1, 9, base.config.d_model))
    one = jax.make_jaxpr(lambda l, a: base._nodrop_mlp(l, a)[0])(lp, x)
    other = jax.make_jaxpr(lambda l, a: scaled._nodrop_mlp(l, a)[0])(lp, x)
    assert len(other.eqns) > len(one.eqns)
    shared = base.moe.shared_expert_d_ff
    a = base._nodrop_mlp(lp, x)[0]
    b = scaled._nodrop_mlp(lp, x)[0]
    if not shared:
        np.testing.assert_allclose(np.asarray(b), 2.5 * np.asarray(a),
                                   rtol=1e-5, atol=1e-7)
    else:
        assert float(jnp.abs(b - a).max()) > 1e-4


def test_what_needs_the_nodrop_dispatch_says_so():
    cfg = get_model("olmoe", "tiny").config
    with pytest.raises(ValueError, match="zero-compute"):
        MoECausalLM(cfg, MoEConfig(zero_experts=2))
    with pytest.raises(ValueError, match="shortcut MoE spans a period"):
        MoECausalLM(cfg, MoEConfig(dispatch="nodrop", shortcut=True))


# --------------------------------------------------------------------- #
# through the engine


def engine_of(toy, telemetry=None, **serving):
    cfg = {"block_size": 16, "max_running": 3}
    cfg.update(serving)
    kw = {"dtype": "fp32", "serving": cfg}
    if telemetry is not None:
        kw["telemetry"] = telemetry
    return deepspeed_tpu.init_inference(toy[0], params=toy[1], **kw)


def prompts_of(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def alone(toy, prompts, max_new):
    engine = engine_of(toy)
    return [np.asarray(engine.generate_batch([p], max_new_tokens=max_new)[0])
            for p in prompts]


def test_more_requests_than_rows(toy):
    """Eight requests over three rows: every request's tokens are those it
    gets alone, each the reference's pick at its position, and the counters
    say what the routers did."""
    get_registry().reset()
    lens = (5, 130, 70, 300, 17, 200, 64, 129)
    prompts = prompts_of(lens)
    engine = engine_of(toy, telemetry={"enabled": True})
    outs = engine.generate_batch(prompts, max_new_tokens=10)
    for o, w in zip(outs, alone(toy, prompts, 10)):
        np.testing.assert_array_equal(np.asarray(o), w)
    weights = correctness.Weights(toy[1], toy[3])
    for p, o in zip(prompts, outs):
        verdict = correctness.check_served(toy[2], weights, p,
                                           list(np.asarray(o)[len(p):]))
        assert verdict["worst_gap_bf16_steps"] <= 0.01, verdict
    counters = engine.telemetry_snapshot()["counters"]
    assert set(engine._paged_workspace[2]) == {"c"}
    routed = counters["serving/moe_router_assignments"]
    assert routed > 0 and routed % 3 == 0
    assert 0 < counters["serving/moe_zero_expert_assignments"] < routed
    assert 0 < counters["serving/moe_assignments"] < routed
    assert counters["serving/moe_dropped_assignments"] == 0
    assert counters["serving/decode_live_kv_tokens"] > 0
    assert not [k for k in counters if "state" in k]


def test_recompute_preemption_gives_the_undisturbed_tokens(toy):
    prompts = prompts_of((30, 25, 28, 20), seed=2)
    engine = engine_of(toy, max_num_blocks=9)
    outs = engine.generate_batch(prompts, max_new_tokens=40)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, alone(toy, prompts, 40)):
        np.testing.assert_array_equal(np.asarray(o), w)


def test_what_cannot_hold_beside_a_latent_row_is_refused(toy):
    """Each from ``cache_spec``, with its reason; ``auto`` resolves to no
    prefix caching."""
    one = prompts_of((5,))
    for serving in ({"prefix_caching": "on"}, {"prefill_chunk_tokens": 128},
                    {"speculative": {"mode": "ngram", "k": 2}},
                    {"kv_host": {"enabled": True}}):
        with pytest.raises(ValueError, match=r"cache_spec\['latent'\]"):
            engine_of(toy, **serving).generate_batch(one, max_new_tokens=2)
    with pytest.raises(ValueError, match=r"cache_spec\['latent'\]"):
        engine_of(toy, kv_host={"enabled": True}).ensure_host_kv_pool()
    with pytest.raises(ValueError, match=r"cache_spec\['latent'\]"):
        engine_of(toy).adopt_host_kv_pool(object())
    engine = engine_of(toy)
    session = engine.open_serve_session(max_new=2)
    try:
        assert not session.sched.prefix_caching
        assert session.sched.allocator.state_slots == 0
    finally:
        session.close()
    model, params = toy[:2]
    pools = model.init_paged_cache(8, 16, jnp.float32)
    i32 = jnp.int32
    with pytest.raises(NotImplementedError, match="latent rows"):
        model.forward_paged_prefill_chunk(
            params, jnp.zeros((1, 16), i32), pools, jnp.zeros((1, 4), i32),
            jnp.zeros((16,), i32), i32(0), i32(3))
    with pytest.raises(NotImplementedError, match="latent rows"):
        model.forward_paged_verify(
            params, jnp.zeros((2, 2), i32), pools, jnp.zeros((2, 4), i32),
            jnp.zeros((2, 2), i32), jnp.zeros((2,), i32))


def test_a_tp_mesh_refuses_a_latent_pool(toy):
    """``_kv_head_sharding``: a latent row has no head axis."""
    engine = engine_of(toy)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    engine.mesh = mesh
    with pytest.raises(ValueError, match=r"cache_spec\['latent'\]"):
        engine._kv_head_sharding()


@pytest.mark.parametrize("held_share,overflows", [(1 / 16, False), (0.6, True)])
def test_a_shares_sorted_dispatch_takes_the_head_of_the_order(held_share,
                                                              overflows):
    """``sorted_dispatch(cap=...)``: of a share's rows only the first
    ``cap`` of the sorted order are gathered and multiplied, and a call
    with more assignments to held experts than that takes the whole order:
    both are the uncapped result."""
    from deepspeed_tpu.moe.sharded_moe import sorted_dispatch
    rng = np.random.default_rng(3)
    T, k, E, D = 200, 3, 4, 16
    here = rng.random((T, k)) < held_share
    experts = jnp.asarray(np.where(here, rng.integers(0, E, (T, k)), E), jnp.int32)
    weights = jnp.asarray(rng.random((T, k)), jnp.float32)
    tokens = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    mats = jnp.asarray(rng.standard_normal((E, D, D)), jnp.float32)
    valid = jnp.asarray(rng.random(T) < 0.9)
    seen = []

    def grouped(xs, sizes):
        seen.append(xs.shape[0])
        return jax.lax.ragged_dot(xs, mats, sizes)

    want, n_want = sorted_dispatch(tokens, weights, experts, E, grouped, valid)
    got, n_got = sorted_dispatch(tokens, weights, experts, E, grouped, valid,
                                 cap=128)
    assert (int(n_want.sum()) > 128) == overflows and seen == [600, 128, 600]
    np.testing.assert_array_equal(np.asarray(n_got), np.asarray(n_want))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5)

    # what the grouped matmul leaves in the rows past its last group is the
    # chip's business (uninitialised memory: PR 56 met NaN there in a
    # 6,144-row prefill): neither body lets it reach a token
    def dirty(xs, sizes):
        past = jnp.arange(xs.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, grouped(xs, sizes))

    for cap in (0, 128):
        dirtied, _ = sorted_dispatch(tokens, weights, experts, E, dirty, valid,
                                     cap=cap)
        np.testing.assert_allclose(np.asarray(dirtied), np.asarray(want),
                                   rtol=0, atol=2e-5)


def test_a_long_prefill_takes_the_ragged_form_and_gives_the_references_logits(
        toy, monkeypatch):
    """From ``_SORTED_DISPATCH_MIN_ROWS`` rows on (1,536; lowered here to the
    toy's bucket) a prefill's experts are the ragged form over the head of
    the sorted order (the share's cap)."""
    from deepspeed_tpu.models import moe_lm
    from deepspeed_tpu.ops import dispatch
    monkeypatch.setattr(moe_lm, "_SORTED_DISPATCH_MIN_ROWS", 256)
    model, params, _, _ = toy
    tokens = tokens_of(8, 200)
    pools = model.init_paged_cache(6, BS, jnp.float32)
    before = dispatch.selected().get("experts=ragged", 0)
    lg, _ = jax.jit(model.forward_paged_prefill)(
        params, np.pad(tokens, (0, 56))[None], pools,
        np.where(np.arange(256) < 200, BS + np.arange(256), np.arange(256) % BS)
        .astype(np.int32), np.int32(199))
    assert dispatch.selected()["experts=ragged"] > before
    np.testing.assert_allclose(np.asarray(lg)[0], reference_logits(toy, tokens)[-1],
                               rtol=0, atol=LOGIT_TOL)
