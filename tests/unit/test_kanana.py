"""Kanana-2-30B-A3B's stage (``deepseek_v3``) on the paged programs against
its plain reference (``perfbench/reference/deepseek_v3_decoder.py``: float32,
the latent EXPANDED to heads at every position, the rope written the
published way, no cache), on the ``deepseek_v3`` ``tiny`` preset (a LATENT
leading dense layer and two MoE layers: 8 heads of keys 32 + 64 and values 32
over a latent of 128 with a DIRECT query, 8 gated-SiLU experts top-3 behind a
sigmoid router with a selection bias, weights normalised with 1e-20 and times
2.448, two shared experts as one MLP; an untied head) with seeded weights
perturbed as ``perfbench/weights.py`` perturbs them (the selection bias
among them).

What is held here: prefill through the paged latent cache, the lead's rows
first in pool ``c``, and decode by the ABSORBED path against the reference's
logits; the direct-query latent kind against the reference's ``mla``; a
stack with and without the latent lead; the router on hand-made scores; the
published de-interleave-then-rotate-halves rope against
``cfg.rope_interleaved``; the parameter counts of the cut and of the
published 48 layers; continuous batching through ``init_inference``, the
refusals, ``serving/prefill_tokens_squared``, and the witnesses the chip
tool (``benchmarks/kanana_check_controls.py --logits``) reads a precision by.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of sums and by the absorbed form's reassociation
(``(q Wk) . c`` for ``q . (c Wk)``): logits of magnitude ~3 agree to a few
1e-6 after a prefill and 12 decode steps. ``LOGIT_TOL`` 2e-5 is several
times that and under a hundredth of what the smallest control moves them by
(``test_each_control_fails_the_tolerance``).
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
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.moe_lm import MoECausalLM
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.monitor.metrics import get_registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench")
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "benchmarks")]
import correctness  # noqa: E402
import kanana_check_controls as tool  # noqa: E402
from reference import deepseek_v3_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-deepseek-v3-tiny"
CELL = "kanana-2-30b-a3b-instruct-2601"
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
    params = make_params(model, 3100000061, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


@pytest.fixture(scope="module")
def toy():
    return load_toy()


def tokens_of(seed, n, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def reference_logits(toy, tokens, cfg=None):
    _, params, toy_cfg, name_map = toy
    w = ref.Weights(params, name_map)
    h = ref.final_hidden(cfg or toy_cfg, w, jnp.asarray(tokens)[None])
    return np.asarray(ref.logits_rows(cfg or toy_cfg, w, h[0]))


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
    """The cell's cut as ``perfbench/configs/kanana-2-30b-a3b-instruct-
    2601.json`` states it, part by part, and the published 48 layers (the
    card's 30B)."""
    model = get_model("deepseek_v3", "kanana-2-30b-8l")
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    attn = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    assert attn == 26_345_984
    (lead,), (layers,) = shapes["lead"], shapes["layers"]
    assert set(lead["attn"]) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert count(lead["attn"]) == attn and count(layers["attn"]) == 7 * attn
    assert count(lead["mlp"]) == 3 * 2048 * 6144
    expert, shared = 3 * 2048 * 768, 3 * 2048 * 1536
    router = 2048 * 128 + 128
    assert count(layers["mlp"]) == 7 * (128 * expert + shared + router)
    moe_layer = attn + 128 * expert + shared + router + 2 * 2048
    lead_layer = attn + 3 * 2048 * 6144 + 2 * 2048
    top = 2 * 128256 * 2048 + 2048
    assert count(shapes) == lead_layer + 7 * moe_layer + top \
        == 5_069_642_624 == model.num_parameters
    cfg = model.config
    assert cfg.cache_spec == {"kv": 0, "state": 0, "window": 0, "latent": 8}
    assert cfg.latent_row == 576 and cfg.latent_pool_row == 640
    assert cfg.n_periods == 7 and cfg.lead_kinds == ("latent_attention",)
    pools = jax.eval_shape(lambda: model.init_paged_cache(1993, 128))
    assert {k: a.shape for k, a in pools.items()} == \
        {"c": (8, 1993, 128, 640)}
    whole = get_model("deepseek_v3", "kanana-2-30b-8l", n_layer=48)
    published = lead_layer + 47 * moe_layer + top
    assert published == whole.num_parameters == 30_670_815_104
    # of which a token's matmuls touch the card's "A3B"
    active = 48 * attn + 3 * 2048 * 6144 \
        + 47 * (6 * expert + shared + 2048 * 128) + 128256 * 2048
    assert active == 3_351_535_616          # the embedding is a lookup
    with open(os.path.join(BENCH, "configs", CELL + ".json")) as f:
        stated = json.load(f)["assumed"]["parameter_count"]
    assert "5,069,642,624" in stated and "30,670,815,104" in stated


def test_the_toy_is_the_cells_configuration_in_small(toy):
    model, params, cfg, _ = toy
    c, m = model.config, model.moe
    big = get_model("deepseek_v3", "kanana-2-30b-8l")
    same = lambda a, b, keys: all(  # noqa: E731
        getattr(a, k) == getattr(b, k) for k in keys)
    assert same(c, big.config, ("period", "lead_kinds", "q_lora_rank",
                                "rope_interleaved", "rope_theta", "norm_eps",
                                "tie_embeddings", "mla_lora_scale"))
    assert same(m, big.moe, ("scoring", "norm_topk_prob", "norm_topk_eps",
                             "routed_scaling_factor", "dispatch",
                             "router_experts", "expert_activation"))
    assert c.q_lora_rank == 0 and c.n_periods == 2 and c.n_layer == 3
    assert c.qk_nope_head_dim == c.v_head_dim       # as 128 = 128 published
    assert m.router_experts is None and m.shared_expert_d_ff == 2 * 32
    assert set(params["lead"][0]["attn"]) == set(params["layers"][0]["attn"]) \
        == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert cfg["n_dense_layer"] == 1 and cfg["n_group"] == cfg["topk_group"] == 1
    # the harness draws the selection bias; the library's starts at zero
    assert float(jnp.std(params["layers"][0]["mlp"]["b_select"])) > 0.005
    zero = model.init_params(jax.random.key(0))["layers"][0]["mlp"]["b_select"]
    assert not float(jnp.abs(zero).max())


@pytest.mark.parametrize("q_lora_rank,leaves", [
    (0, {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}),
    (48, {"wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"})])
def test_the_latent_kind_takes_a_direct_query_or_a_bottleneck(q_lora_rank,
                                                              leaves):
    cfg = dataclasses.replace(get_model("deepseek_v3", "tiny").config,
                              q_lora_rank=q_lora_rank)
    LA.check(cfg)
    p = LA.init(cfg, 2, jax.random.key(0), jnp.float32, 0.1)
    assert set(p) == leaves
    lp = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.key(1), (1, 5, cfg.d_model))
    q_nope, q_rope, rows = LA.project(cfg, x, lp, jnp.arange(5)[None])
    assert q_nope.shape == (1, 5, 8, 32) and q_rope.shape == (1, 5, 8, 64)
    assert rows.shape == (1, 5, cfg.latent_row)
    with pytest.raises(ValueError, match="q_lora_rank of 0"):
        LA.check(dataclasses.replace(cfg, q_lora_rank=-1))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        LA.check(dataclasses.replace(cfg, kv_lora_rank=0))


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
    # a decode step's counts, an MoE layer a row (the lead has none): the
    # experts' and what they owed (two real rows x top-3)
    assert counts.shape == (2, 8 + 1)
    assert (counts[:, -1] == 6).all() and (counts[:, :8].sum(1) == 6).all()


def _zeroed(params, *path):
    """``params`` with the leaf at ``path`` of the MoE layers' group zeroed."""
    (group,) = params["layers"]

    def put(node, keys):
        if not keys:
            return jnp.zeros_like(node)
        return {**node, keys[0]: put(node[keys[0]], keys[1:])}
    return {**params, "layers": (put(group, path),)}


def _bias_weighs(model, params):
    """A router whose bias is part of the score it weighs by: sigmoid(z) + b
    chooses AND weighs where the bias is folded into the scores."""
    class Weighed(MoECausalLM):
        def _route(self, lp, tokens):
            w, e, probs, zero = super()._route(lp, tokens)
            b = lp["b_select"].astype(jnp.float32)[e]
            s = jnp.take_along_axis(probs, e, axis=1)
            w = (s + b) / (jnp.sum(s + b, -1, keepdims=True) + 1e-20) * 2.448
            return w, e, probs, zero
    return Weighed(model.config, model.moe), params


def _without_kv_norm(model, params):
    ones = lambda g: {**g, "attn": {**g["attn"], "kv_norm": {  # noqa: E731
        "scale": jnp.ones_like(g["attn"]["kv_norm"]["scale"])}}}
    return model, {**params, "lead": tuple(map(ones, params["lead"])),
                   "layers": tuple(map(ones, params["layers"]))}


def _lead_skipped(model, params):
    cfg = dataclasses.replace(model.config, lead_kinds=(), n_layer=2)
    return MoECausalLM(cfg, model.moe), \
        {k: v for k, v in params.items() if k != "lead"}


_with = lambda **kw: lambda m, p: (  # noqa: E731
    MoECausalLM(dataclasses.replace(m.config, **kw), m.moe), p)
_moe = lambda **kw: lambda m, p: (  # noqa: E731
    MoECausalLM(m.config, dataclasses.replace(m.moe, **kw)), p)

CONTROLS = {
    "no_routed_scaling_factor": _moe(routed_scaling_factor=1.0),
    "no_norm_topk_prob": _moe(norm_topk_prob=False),
    "bias_weighs_as_well_as_chooses": _bias_weighs,
    "bias_left_out_of_the_choice": lambda m, p: (m, _zeroed(p, "mlp", "b_select")),
    "shared_expert_missing": lambda m, p: (
        m, _zeroed(p, "mlp", "shared", "w_down")),
    "lead_skipped": _lead_skipped,
    "kv_norm_scale_missing": _without_kv_norm,
    "half_split_rope": _with(rope_interleaved=False),
    "scale_of_the_nope_width": _with(attn_scale=32 ** -0.5),
    "lora_scale_of_another_family": _with(mla_lora_scale=True),
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


def test_the_reference_computed_in_float8_is_another_function(toy):
    """``round_to`` (the chip tool's ``float8_reference``): the matrices but
    the router's and the activations through e4m3 move the logits by far
    more than a planted equation's hundred tolerances, and a configuration
    that names no such type computes what it computed."""
    cfg, tokens = toy[2], tokens_of(5, 60)
    want = reference_logits(toy, tokens)
    low = reference_logits(toy, tokens, tool.float8("float8_reference", cfg))
    assert 0.02 * np.abs(want).max() < np.abs(low - want).max() < np.abs(want).max()
    assert tool.float8("sound", cfg) is cfg
    np.testing.assert_array_equal(reference_logits(toy, tokens, dict(cfg)), want)


@pytest.mark.parametrize("where", ["lead", "layers"])
def test_the_direct_query_kind_is_the_references_mla(toy, where):
    """One mixer alone: the program's prefill form (projection, the row's
    write, the expansion, the output projection) against ``ref.mla``, which
    ropes the published way."""
    model, params, cfg, name_map = toy
    l = 0 if where == "lead" else 2
    lp = jax.tree.map(lambda a: a[-1], params[where][0]["attn"])
    w = ref.Weights(params, name_map).layer(l)
    S = 150
    x = jax.random.normal(jax.random.key(4), (1, S, 64))
    cp = jnp.zeros((3, BS, model.config.latent_pool_row))
    got, cp = LA.prefill(model.config, x, lp, jnp.arange(S)[None], cp,
                         BS + jnp.arange(S))
    with jax.default_matmul_precision("highest"):
        want = ref.mla(cfg, w, x[0])
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=0,
                               atol=3e-6)
    # what the cache keeps of a token: the normed latent, then the shared
    # roped key part, zeros to the pool's lanes
    rows = np.asarray(cp[1, :5])
    assert np.abs(rows[:, :192]).min() > 0 and not np.abs(rows[:, 192:]).max()


def test_the_absorbed_and_the_expanded_attention_agree_on_a_row(toy):
    model, params, _, _ = toy
    cfg = model.config
    lp = jax.tree.map(lambda a: a[1], params["layers"][0]["attn"])
    S = 150
    x = jax.random.normal(jax.random.key(4), (1, S, cfg.d_model))
    q_nope, q_rope, rows = LA.project(cfg, x, lp, jnp.arange(S)[None])
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


# --------------------------------------------------------------------- #
# the latent lead


def test_the_leads_rows_are_the_first_entry_of_pool_c(toy):
    """A prefill writes the lead's rows into layer 0 of ``c`` and the
    periods' into layers 1 and 2, each what that layer's own projection
    makes of the stream it read; a decode step appends to all three."""
    model, params, _, _ = toy
    cfg = model.config
    tokens = tokens_of(3, 40)
    pools = model.init_paged_cache(4, BS, jnp.float32)
    table = np.array([2, 0], np.int32)
    _, pools = prefill(model, params, pools, tokens, table)
    c = np.asarray(pools["c"])
    assert c.shape == (3, 4, BS, cfg.latent_pool_row)
    x = params["embed"]["tokens"][tokens][None]
    lead = jax.tree.map(lambda a: a[0], params["lead"][0])
    _, _, rows = LA.project(cfg, T._norm(cfg, x, lead["ln_attn"]),
                            lead["attn"], jnp.arange(40)[None])
    np.testing.assert_allclose(c[0, 2, :40, :cfg.latent_row],
                               np.asarray(rows[0]), rtol=0, atol=1e-6)
    # the periods' layers keep other rows, and nothing wrote elsewhere
    for l in (1, 2):
        assert np.abs(c[l, 2, :40, :192] - c[0, 2, :40, :192]).max() > 0.1
    assert not np.abs(c[:, 1]).max() and not np.abs(c[:, 3]).max()
    assert not np.abs(c[:, 2, 40:]).max()
    _, pools, _ = jitted(model, "forward_paged_decode")(
        params, np.array([[5]], np.int32), pools, table[None],
        np.array([40], np.int32))
    c = np.asarray(pools["c"])
    assert np.abs(c[:, 2, 40, :192]).min(axis=-1).all()
    assert not np.abs(c[:, 2, 41:]).max()


def test_a_stack_with_and_without_the_lead(toy):
    """The same MoE layers behind a lead and alone: the lead is one more
    layer of the stream (the logits differ), one more entry of the pool,
    and its parameters are the tree's ``lead`` and nothing else."""
    model, params, cfg, name_map = toy
    bare, bparams = _lead_skipped(model, params)
    assert bare.config.cache_spec["latent"] == 2
    assert model.config.cache_spec["latent"] == 3
    assert model.num_parameters - bare.num_parameters == sum(
        a.size for a in jax.tree.leaves(params["lead"]))
    assert bare.n_moe_layers == model.n_moe_layers == 2
    tokens = tokens_of(6, 30)
    got, _ = served_logits(bare, bparams, tokens, 20)
    # the reference without its first layer, the same weights
    w = ref.Weights(bparams, name_map)
    assert w.lead == ()
    h = ref.final_hidden({**cfg, "n_layer": 2, "n_dense_layer": 0}, w,
                         jnp.asarray(tokens)[None])
    want = np.asarray(ref.logits_rows(cfg, w, h[0]))[19:]
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOL)
    assert np.abs(want - reference_logits(toy, tokens)[19:]).max() > 0.05


# --------------------------------------------------------------------- #
# the router, on hand-made scores


def _hand_router(model, logits, bias):
    """(lp, tokens) whose router logits are ``logits`` [T, E] exactly."""
    E = logits.shape[1]
    D = model.config.d_model
    tokens = np.zeros((logits.shape[0], D), np.float32)
    tokens[:, :E] = logits
    return {"gate_w": jnp.eye(D, E), "b_select": jnp.asarray(bias)}, \
        jnp.asarray(tokens)


def test_the_bias_chooses_and_never_weighs(toy):
    model, _, cfg, _ = toy
    sig = lambda z: 1 / (1 + np.exp(-z))  # noqa: E731
    z = np.array([[2.0, 1.0, 0.5, 0.0, -0.5, -1.0, -2.0, -3.0],
                  [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]], np.float32)
    # the bias lifts expert 7 over everything in row 0 and sinks 7 in row 1
    bias = np.array([0, 0, 0, 0, 0, 0, 0, 0.9], np.float32)
    lp, tokens = _hand_router(model, z, bias)
    w, e, _, _ = model._route(lp, tokens)
    w, e = np.asarray(w), np.asarray(e)
    assert sorted(e[0]) == [0, 1, 7] and sorted(e[1]) == [5, 6, 7]
    for t in range(2):
        s = sig(z[t, e[t]])
        np.testing.assert_allclose(w[t], s / (s.sum() + 1e-20) * 2.448,
                                   rtol=1e-6)
        assert abs(w[t].sum() - 2.448) < 1e-5
    # the chosen expert 7 of row 0 weighs by its own small score
    assert w[0][list(e[0]).index(7)] < 0.1
    # and the reference, the published way, says the same
    with jax.default_matmul_precision("highest"):
        c = np.asarray(ref.route(cfg, {"router": lp["gate_w"],
                                       "expert_bias": lp["b_select"]}, tokens))
    for t in range(2):
        assert sorted(np.nonzero(c[t])[0]) == sorted(e[t])
        np.testing.assert_allclose(c[t, e[t]], w[t], rtol=1e-6)


def test_the_normalisation_bears_scores_that_vanish(toy):
    """Three scores that underflow: the 1e-20 keeps the weights finite (0),
    where a bare division would be 0 / 0."""
    model, _, _, _ = toy
    lp, tokens = _hand_router(model, np.full((1, 8), -200.0, np.float32),
                              np.zeros(8, np.float32))
    w, _, _, _ = model._route(lp, tokens)
    assert np.isfinite(np.asarray(w)).all() and not np.asarray(w).any()


@pytest.mark.parametrize("groups,kept,want", [
    (1, 1, [1, 3, 4]),      # one group: the plain three largest
    (2, 1, [1, 2, 3]),      # the first group's two best sum higher: it alone
    (2, 2, [1, 3, 4])])     # both groups kept: the plain three largest again
def test_the_references_group_limited_choice(groups, kept, want):
    c = jnp.asarray([[0.1, 0.9, 0.3, 0.8, 0.85, 0.05, 0.2, 0.0]])
    cfg = {"n_experts": 8, "experts_per_token": 3, "n_group": groups,
           "topk_group": kept}
    assert sorted(np.asarray(ref.choose(cfg, c))[0]) == want


# --------------------------------------------------------------------- #
# the rope


def test_the_published_rope_gives_the_interleaved_pairs_scores():
    """``rope_interleave``: de-interleave, then rotate halves, on q and on
    the shared key part alike. A value lands elsewhere than under
    ``cfg.rope_interleaved`` (pairs turned in place), every score of a
    query against a key is the same, and the half-split pairing of the
    raw order gives other scores."""
    S, H, d, theta = 9, 3, 64, 1e6
    q = jax.random.normal(jax.random.key(0), (S, H, d))
    k = jax.random.normal(jax.random.key(1), (S, 1, d))
    pos = jnp.arange(S)[None]
    ours = lambda a, inter: T._rope(a[None], pos, theta, 0, inter)[0]  # noqa: E731
    scores = lambda a, b: np.asarray(jnp.einsum("ihd,jd->hij", a, b[:, 0]))  # noqa: E731
    pub = scores(ref.rope(q, theta), ref.rope(k, theta))
    np.testing.assert_allclose(pub, scores(ours(q, True), ours(k, True)),
                               rtol=0, atol=2e-5)
    assert np.abs(np.asarray(ref.rope(q, theta)) - np.asarray(ours(q, True))
                  ).max() > 0.1
    assert np.abs(pub - scores(ours(q, False), ours(k, False))).max() > 0.1
    # the de-interleaved order: evens first, turned against the odds
    one = jnp.zeros((2, 1, d)).at[:, 0, 2].set(1.0)        # pair 1's first
    out = np.asarray(ref.rope(one, theta))[1, 0]
    ang = theta ** (-2 / d)
    np.testing.assert_allclose(out[[1, 33]], [np.cos(ang), np.sin(ang)],
                               atol=1e-6)
    assert np.count_nonzero(np.abs(out) > 1e-9) == 2


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
    say what the prefills and the routers did."""
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
    assert counters["serving/prefill_steps"] == len(lens)
    assert counters["serving/prefill_tokens"] == sum(lens)
    assert counters["serving/prefill_tokens_squared"] == sum(n * n for n in lens)
    # two MoE layers a step, the lead none
    assert counters["serving/moe_layer_steps"] \
        == 2 * counters["serving/decode_steps"]
    assert counters["serving/moe_assignments"] > 0
    assert counters["serving/moe_dropped_assignments"] == 0
    assert counters["serving/decode_live_kv_tokens"] > 0


def test_recompute_preemption_gives_the_undisturbed_tokens(toy):
    prompts = prompts_of((30, 25, 28, 20), seed=2)
    engine = engine_of(toy, max_num_blocks=9)
    outs = engine.generate_batch(prompts, max_new_tokens=40)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, alone(toy, prompts, 40)):
        np.testing.assert_array_equal(np.asarray(o), w)


@pytest.mark.parametrize("serving", [
    {"prefix_caching": "on"}, {"prefill_chunk_tokens": 128},
    {"speculative": {"mode": "ngram", "k": 2}}, {"kv_host": {"enabled": True}}],
    ids=["prefix_caching", "chunks", "speculation", "host_tier"])
def test_what_cannot_hold_beside_a_latent_row_stays_refused(toy, serving):
    with pytest.raises(ValueError, match=r"cache_spec\['latent'\]"):
        engine_of(toy, **serving).generate_batch(prompts_of((5,)),
                                                 max_new_tokens=2)


def test_a_prompts_chunks_add_up_to_its_square():
    """``serving/prefill_tokens_squared`` under chunked prefill (a dense
    stack: no chunk form reads a latent row): a chunk of n tokens behind
    ``start`` cached ones adds (start + n)^2 - start^2."""
    get_registry().reset()
    engine = deepspeed_tpu.init_inference(
        CausalLM(T.TransformerConfig(vocab_size=64, n_layer=2, n_head=4,
                                     d_model=32, d_ff=64, max_seq=128,
                                     remat=False)),
        dtype="fp32", telemetry=True,
        serving={"block_size": 8, "max_running": 2, "prefix_caching": "off",
                 "prefill_chunk_tokens": 32})
    lens = (5, 70, 100)
    engine.generate_batch([np.arange(n, dtype=np.int32) % 64 for n in lens],
                          max_new_tokens=3)
    c = engine.telemetry_snapshot()["counters"]
    assert c["serving/prefill_chunks"] > len(lens)
    assert c["serving/prefill_tokens"] == sum(lens)
    assert c["serving/prefill_tokens_squared"] == sum(n * n for n in lens)


# --------------------------------------------------------------------- #
# the chip tool's witnesses

@pytest.mark.parametrize("control,router_sound,norms_sound", [
    ("sound", True, True), ("bf16_router", False, True),
    ("bf16_norms", True, False)])
def test_the_tools_witnesses_tell_a_planted_precision(toy, control,
                                                      router_sound,
                                                      norms_sound):
    """``kanana_check_controls.py --logits`` on the toy in bf16, as served:
    the router's scores against ``sigmoid(m Wr)`` in float64 of the input it
    read and every RMSNorm's output against its float64 value in bf16 steps
    fall on the sound side of the tool's two limits for the sound program
    (float32 arithmetic: ~1e-7 and half a step) and on the other for the
    precision planted (bf16 arithmetic: ~2e-3, two steps), each by more than
    a factor of two; and the reference made to take the program's own
    experts at the kept positions holds the program's logits there."""
    model, _, cfg, name_map = toy
    whole = (ref._route, T._norm, LA._rms)
    params = make_params(model, 3100000061, jnp.bfloat16, jax.devices()[:1])
    weights = ref.Weights(params, name_map)
    gates = [np.asarray(weights.layer(l)["router"], np.float64)
             for l in range(cfg["n_dense_layer"], cfg["n_layer"])]
    prompts, steps, keep = [tokens_of(5, 130), tokens_of(6, 200)], 3, [0, 2]
    with tool.planted(control):
        kept, toks, took, scores, score_err, norm_steps = tool._paged_logits(
            model, params, {"block_size": BS, "max_running": 4,
                            "max_num_blocks": 25}, prompts, steps, keep, gates)
    K, E = cfg["experts_per_token"], cfg["n_experts"]
    assert np.isfinite(kept).all() and kept.shape == (2, 3, 512)
    assert took.shape == (2, len(gates), 3, K) and took.max() < E
    assert scores.shape == (2, len(gates), 3, E)
    assert (score_err < tool.ROUTER_TOL / 2) if router_sound \
        else (score_err > 2 * tool.ROUTER_TOL)
    assert (norm_steps < 0.51) if norms_sound \
        else (norm_steps > 2 * tool.NORM_TOL_STEPS)
    for r, (p, t) in enumerate(zip(prompts, toks)):
        seq = np.concatenate([p, t[:-1]])
        at = np.array([len(p) - 1] + [len(p) + s for s in keep])
        seen = []
        with tool.watched_router(ref, at, seen, took[r]):
            h = ref.final_hidden(cfg, weights, jnp.asarray(seq[None]))
        want = np.asarray(ref.logits_rows(cfg, weights, h[0, at]))
        assert len(seen) == len(gates) and seen[0][1].shape == (3, K)
        assert (np.abs(kept[r] - want).max(-1)
                <= tool.trinity.LOGIT_TOL * np.abs(want).max(-1)).all()
    # and the modules are whole again
    assert (ref._route, T._norm, LA._rms) == whole \
        and "_route" not in vars(model)
