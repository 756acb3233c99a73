"""LFM2-MoE's stage on the paged programs against its plain reference
(``perfbench/reference/lfm2_moe_decoder.py``: float32, the conv over the whole
sequence with zeros on its left, every expert over every token), on the
``lfm2_moe`` ``tiny`` preset (ONE LEADING conv layer with a dense MLP, then one
period of a GQA layer with per-head q/k RMSNorm and rope and three gated
short-conv layers, each with 8 experts behind a sigmoid router with a
selection bias, 2 a token; a tied head) with seeded weights perturbed as
``perfbench/weights.py`` perturbs them (the selection bias among them). The
engine's part (more callers than rows, a recompute-preemption, the refusals,
the counters) is ``test_serving_state.py``'s, which runs on this toy too.

Tolerances. Program and reference both compute in float32 here (the CPU's
default matmul precision is full float32), so they differ by the order of
sums: logits of magnitude up to ~80 (deviation 8.5: the embedding at 1.0
under a tied head, PR 48's init) agree to 6e-6, under one float32 step at
that magnitude (7.6e-6). ``LOGIT_TOL`` 3e-5 is four such steps and a
ninetieth of what the smallest control moves the logits by: the router's
matrix rounded to bf16 moves them by 2.8e-3, the conv's taps rounded to bf16
by 9.6e-3 (``test_a_lower_precision_fails_the_tolerance``), a missing
``expert_bias`` by 2.7, one dropped tap of one layer by 3.3.
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
from deepspeed_tpu.models import moe_lm
from deepspeed_tpu.models import state_mixers as SM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.ops import dispatch

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from reference import lfm2_moe_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-lfm2-moe-tiny"
CELL = "lfm2-24b-a2b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LOGIT_TOL = 3e-5
BS = 128


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def load_config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def load_toy(**over):
    """(model, float32 params, the reference's cfg, the name map) of the toy
    configuration, ``over`` laid over its preset (the program's names)."""
    config = load_config(TOY)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"], **over)
    params = make_params(model, 3100000043, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


@pytest.fixture(scope="module")
def toy():
    return load_toy()


def tokens_of(seed, n, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def reference_logits(toy, tokens, params=None, **cfg_over):
    _, own, cfg, name_map = toy
    w = ref.Weights(own if params is None else params, name_map)
    cfg = {**cfg, **cfg_over}
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens)[None])
    return np.asarray(ref.logits_rows(cfg, w, h[0]))


def fresh_pools(model, n_blocks, slots):
    """Pools whose slots' last holders left something there: it must not be
    inherited."""
    pools = model.init_paged_cache(n_blocks, BS, jnp.float32, state_slots=slots)
    pools["conv"] = tuple(a - 2.0 for a in pools["conv"])
    return pools


_JITTED = {}


def jitted(model, name):
    """One jit a model and program, so that a second call compiles nothing."""
    key = (id(model), name)
    if key not in _JITTED:
        _JITTED[key] = (model, jax.jit(getattr(model, name)))
    return _JITTED[key][1]


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


def decode(model, params, pools, rows, width=3, n_max=2):
    """One decode step of ``width`` rows: ``rows`` maps a row to (token,
    position, table, slot); the others are idle. Returns (logits, pools)."""
    bt = np.zeros((width, n_max), np.int32)
    t = np.zeros((width, 1), np.int32)
    pos = np.zeros((width,), np.int32)
    slots = np.zeros((width,), np.int32)
    for row, (tok, p, table, slot) in rows.items():
        bt[row, :len(table)] = table
        t[row, 0], pos[row], slots[row] = tok, p, slot
    lg, pools, _ = jitted(model, "forward_paged_decode")(
        params, t, pools, bt, pos, None, slots)
    return np.asarray(lg), pools


# --------------------------------------------------------------------- #
# what the preset and the configuration's file build


def test_the_stage_is_built_as_the_configuration_says():
    """Every width as ``config.json`` gives it, the cut as the file states
    it: 5,177,950,976 parameters, the pools the cell holds, and NO state
    array for a kind that keeps a conv state alone."""
    config = load_config(CELL)
    model = get_model(**config["preset"])
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 5_177_950_976 == model.num_parameters
    cfg, moe = model.config, model.moe
    assert tuple(cfg.lead_kinds) == ("short_conv",)
    assert cfg.period == ("attention",) + ("short_conv",) * 3
    assert (cfg.n_layer, cfg.n_periods) == (config["num_hidden_layers"], 2)
    kinds = tuple(cfg.lead_kinds) + cfg.period * cfg.n_periods
    assert [{"short_conv": "conv", "attention": "full_attention"}[k]
            for k in kinds] == config["layer_types"]
    assert len(cfg.lead_kinds) == config["num_dense_layers"]
    assert (cfg.d_model, cfg.lead_d_ff, cfg.conv_kernel, cfg.vocab_size) == (
        config["hidden_size"], config["intermediate_size"],
        config["conv_L_cache"], config["vocab_size"])
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (
        config["num_attention_heads"], config["num_key_value_heads"], 64)
    assert (cfg.norm_eps, cfg.rope_theta, cfg.qk_norm, cfg.tie_embeddings) == (
        config["norm_eps"], config["rope_parameters"]["rope_theta"], "head", True)
    assert (moe.num_experts, moe.k, model.expert_ff, moe.scoring,
            moe.norm_topk_prob, moe.norm_topk_eps, moe.routed_scaling_factor) == (
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], "sigmoid", config["norm_topk_prob"],
        1e-6, config["routed_scaling_factor"])
    assert model.n_moe_layers == 8
    assert cfg.max_seq == config["serve_max_seq"]
    assert cfg.cache_spec == {"kv": 2, "state": 7, "window": 0, "latent": 0}
    lead = shapes["lead"][0]
    assert lead["mlp"]["w_gate"].shape == (1, 2048, 11776) \
        and "gate_w" not in lead["mlp"]
    assert lead["conv"]["w_in"].shape == (1, 2048, 6144) \
        and lead["conv"]["conv_w"].shape == (1, 3, 2048)
    assert shapes["layers"][1]["mlp"]["w_up"].shape == (2, 64, 2048, 1536)
    assert shapes["layers"][0]["mlp"]["b_select"].shape == (2, 64)
    serve = config["assumed"]["serve"]
    pools = jax.eval_shape(lambda: model.init_paged_cache(
        serve["max_num_blocks"], serve["block_size"], jnp.bfloat16,
        state_slots=serve["max_running"] + 1))
    assert pools["state"] == (None,) * 4
    rows = serve["max_running"] + 1
    assert [a.shape for a in pools["conv"]] == \
        [(1, rows, 2, 2048)] + [(2, rows, 2, 2048)] * 3
    assert pools["conv"][0].dtype == jnp.bfloat16
    assert pools["k"].shape == (2, serve["max_num_blocks"], 128, 512)
    # the published model whole, by the same counts
    conv, attn, dense, expert = 16_783_360, 10_485_888, 72_351_744, 9_437_184
    router, norms, embed = 2048 * 64 + 64, 2 * 2048, 65536 * 2048
    whole = 2 * (conv + dense + norms) + 10 * (attn + 64 * expert + router + norms) \
        + 28 * (conv + 64 * expert + router + norms) + embed + 2048
    assert whole == 23_843_661_440
    stage = (conv + dense + norms) + 2 * (attn + 64 * expert + router + norms) \
        + 6 * (conv + 64 * expert + router + norms) + embed + 2048
    assert stage == model.num_parameters


def test_the_cut_is_the_sources_layers_one_to_nine():
    """The configuration's file against the source: its ``layer_types`` are
    entries 1-9 of the published forty, its lead the published kind of layer
    1 (a dense layer there: 1 < published ``num_dense_layers``), and every
    key not named in ``reduced`` is the catalog row's, where the catalog is
    on this machine."""
    config = load_config(CELL)
    published = config["layer_types_published"]
    first = config["first_published_layer"]
    n = config["num_hidden_layers"]
    assert len(published) == config["num_hidden_layers_published"] == 40
    assert config["layer_types"] == published[first:first + n] and n == 9
    assert first < config["num_dense_layers_published"] == 2
    assert config["num_dense_layers"] == config["num_dense_layers_published"] - first
    lead_kind = {"conv": "short_conv", "full_attention": "attention"}[published[first]]
    model = get_model(**config["preset"])
    assert tuple(model.config.lead_kinds) == (lead_kind,)
    # what follows the dense layers is whole periods of the published pattern
    period = published[2:6]
    assert config["layer_types"][1:] == period * 2
    reduced = {line.split(":")[0] for line in config["reduced"]}
    assert reduced == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[CELL]
    assert set(entry["reduced"]) == reduced and entry["source"] == config["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = {r["name"]: r for r in map(json.loads, f)}["LFM2-24B-A2B"]
        assert row["source_url"] == config["source"]
        assert row["config"]["layer_types"] == published
        for key, value in row["config"].items():
            if key not in reduced:
                assert config[key] == value, key
        assert row["config"]["num_hidden_layers"] == 40 \
            and row["config"]["num_dense_layers"] == 2


def test_the_toy_is_a_lead_and_one_period(toy):
    model, params = toy[:2]
    cfg = model.config
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == model.num_parameters == 329_216
    big = get_model("lfm2_moe", "24b-a2b-9l").config
    assert (tuple(cfg.lead_kinds), cfg.period) == (tuple(big.lead_kinds), big.period)
    assert (cfg.n_layer, cfg.n_periods, cfg.conv_kernel) == (5, 1, 3)
    assert len(params["lead"]) == 1 and len(params["layers"]) == 4
    assert [k for k in toy[2]["layer_types"]] == \
        ["conv", "full_attention", "conv", "conv", "conv"]


def test_what_the_serving_path_alone_runs(toy):
    model, params = toy[:2]
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="paged serving path only"):
        model.forward(params, toks)
    with pytest.raises(NotImplementedError, match="paged serving path only"):
        model.forward_cached(params, toks, model.init_cache(1, 16), jnp.int32(0))
    with pytest.raises(ValueError, match="conv_kernel"):
        T.init_params(dataclasses.replace(model.config, conv_kernel=1),
                      jax.random.key(0))
    with pytest.raises(ValueError, match="leading layers and whole periods"):
        T.init_params(dataclasses.replace(model.config, n_layer=6),
                      jax.random.key(0))
    with pytest.raises(NotImplementedError, match="ahead of the periods"):
        T.init_params(dataclasses.replace(
            model.config, lead_kinds=("linear_attention",), lin_heads=2,
            lin_head_dim=16), jax.random.key(0))


def test_a_dense_model_takes_a_lead_too():
    """The lead is the stack's, not the MoE model's: a dense model with a
    leading attention layer of another MLP width holds its KV in layer 0 of
    the pools, and decoding through the cache is its prefill."""
    from deepspeed_tpu.models import CausalLM
    cfg = T.TransformerConfig(
        vocab_size=128, n_layer=3, n_head=4, d_model=32, d_ff=48, max_seq=256,
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        lead_kinds=("attention",), lead_d_ff=80)
    model = CausalLM(cfg, param_dtype=jnp.float32)
    params = model.init_params(jax.random.key(1))
    assert params["lead"][0]["mlp"]["w_up"].shape == (1, 32, 80)
    assert params["layers"]["mlp"]["w_up"].shape == (2, 32, 48)
    assert cfg.cache_spec["kv"] == 3 and model.num_parameters == sum(
        a.size for a in jax.tree.leaves(params))
    toks = tokens_of(3, 21, vocab=128)
    table = np.array([1])
    want, _ = prefill(model, params, model.init_paged_cache(3, BS, jnp.float32),
                      toks, table, 0)
    _, pools = prefill(model, params, model.init_paged_cache(3, BS, jnp.float32),
                       toks[:20], table, 0)
    assert pools["k"].shape[0] == 3
    assert float(jnp.abs(pools["k"][0, 1, :20]).max()) > 0
    lg, _ = jax.jit(model.forward_paged_decode)(
        params, toks[None, 20:], pools, table[None], np.array([20], np.int32))
    np.testing.assert_allclose(np.asarray(lg)[0], want, rtol=0, atol=1e-5)


# --------------------------------------------------------------------- #
# the conv mixer alone


def _conv_layer(toy):
    model, params = toy[:2]
    return model.config, jax.tree.map(lambda a: a[0],
                                      params["layers"][1]["conv"])


def test_a_buckets_padding_touches_nothing_of_the_conv_state(toy):
    """13 real positions in a bucket of 32 leave the request's conv state
    where the same 13 in a bucket of 16 leave it: the last two VALID inputs;
    the other slots are bit for bit what they were."""
    cfg, lp = _conv_layer(toy)
    (none, ch) = cfg.state_shapes("short_conv")
    assert none is None and ch == (2, cfg.d_model)
    rng = np.random.default_rng(5)
    conv = jnp.asarray(rng.standard_normal((3,) + ch), jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 32, cfg.d_model)), jnp.float32)
    run = jax.jit(lambda xb: SM._short_conv_prefill(
        cfg, xb, lp, None, conv, jnp.int32(1), jnp.int32(13), True))
    outs = [run(x[:, :Tb]) for Tb in (16, 32)]
    assert outs[0][1] is None and outs[1][1] is None
    np.testing.assert_array_equal(outs[0][2], outs[1][2])
    np.testing.assert_allclose(outs[0][0][:, :13], outs[1][0][:, :13],
                               rtol=0, atol=1e-6)
    u, _ = SM._short_conv_project(x, lp)
    np.testing.assert_allclose(outs[1][2][1], u[0, 11:13], rtol=0, atol=1e-6)
    np.testing.assert_array_equal(outs[1][2][np.array([0, 2])],
                                  conv[np.array([0, 2])])


@pytest.mark.parametrize("n", [1, 2])
def test_a_short_prompt_leaves_zeros_beside_it(toy, n):
    """A prompt of one token leaves a zero beside its input in the slot, one
    of two tokens none: the conv's left padding, whatever the slot held."""
    cfg, lp = _conv_layer(toy)
    conv = jnp.full((3, 2, cfg.d_model), 7.0, jnp.float32)
    x = jnp.asarray(np.random.default_rng(n).standard_normal(
        (1, 16, cfg.d_model)), jnp.float32)
    y, _, new = jax.jit(lambda: SM._short_conv_prefill(
        cfg, x, lp, None, conv, jnp.int32(2), jnp.int32(n), True))()
    u, c = SM._short_conv_project(x, lp)
    want = np.concatenate([np.zeros((2 - n, cfg.d_model), np.float32),
                           np.asarray(u[0, :n])])
    np.testing.assert_allclose(new[2], want, rtol=0, atol=1e-6)
    # the first output is the last tap alone
    first = (c[0, 0] * (u[0, 0] * lp["conv_w"][2])) @ lp["w_out"]
    np.testing.assert_allclose(y[0, 0], first, rtol=0, atol=1e-5)


def test_the_decode_step_moves_the_live_rows_alone(toy):
    """Five rows, two idle: a live row's slot takes its new input beside the
    newer of the two it held, its output is the three taps over them; every
    slot of no live row but the dummy is bit for bit what it was."""
    cfg, lp = _conv_layer(toy)
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.standard_normal((12, 2, cfg.d_model)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, 1, cfg.d_model)), jnp.float32)
    slots = jnp.asarray([3, 0, 1, 0, 4], jnp.int32)
    y, st, new = jax.jit(lambda: SM._short_conv_decode(
        cfg, x, lp, None, pool, jnp.int32(6), slots))()
    assert st is None
    u, c = SM._short_conv_project(x, lp)
    w = lp["conv_w"]
    for b, s in enumerate(np.asarray(slots)):
        if s == 0:
            continue
        old = pool[6 + s]
        np.testing.assert_allclose(new[6 + s], jnp.stack([old[1], u[b, 0]]),
                                   rtol=0, atol=1e-6)
        taps = old[0] * w[0] + old[1] * w[1] + u[b, 0] * w[2]
        np.testing.assert_allclose(y[b, 0], (c[b, 0] * taps) @ lp["w_out"],
                                   rtol=0, atol=1e-5)
    untouched = np.array([r for r in range(12) if r not in (6, 7, 9, 10)])
    np.testing.assert_array_equal(new[untouched], pool[untouched])


# --------------------------------------------------------------------- #
# logits through the paged programs


@pytest.mark.parametrize("n", [1, 2, 3, 37, 150])
def test_logits_of_a_whole_prompt_and_four_decode_steps(toy, n):
    """The full forward at a prompt of one and of two tokens (the conv's
    zero padding comes from the slot), of three (every tap on a real input),
    inside a block and across two: the prefill's last position, then four
    decode steps through the cache, against the reference's full forward."""
    model, params = toy[:2]
    seq = tokens_of(10 + n, n + 4)
    want = reference_logits(toy, seq)
    table = np.array([2, 1])
    lg, pools = prefill(model, params, fresh_pools(model, 4, 3), seq[:n],
                        table, 2)
    np.testing.assert_allclose(lg, want[n - 1], rtol=0, atol=LOGIT_TOL)
    for step in range(4):
        lg, pools = decode(model, params, pools,
                           {1: (seq[n + step], n + step, table, 2)})
        np.testing.assert_allclose(lg[1], want[n + step], rtol=0,
                                   atol=LOGIT_TOL)


def test_logits_of_40_decode_steps_with_rows_joining_and_leaving(toy):
    """Three rows, two slots but the dummy: request A (37 tokens) decodes
    from step 0, B (150) joins at step 6, A leaves after step 17 and C (one
    token) takes A's SLOT and row at step 22, B leaves after step 30: every
    live row's logits at every one of 40 steps are the reference's full
    forward's, teacher-forced, and a slot's next holder inherits nothing."""
    model, params = toy[:2]
    plan = {"A": dict(n=37, join=0, leave=18, row=0, slot=2, table=np.array([1])),
            "B": dict(n=150, join=6, leave=31, row=2, slot=1,
                      table=np.array([2, 3])),
            "C": dict(n=1, join=22, leave=40, row=0, slot=2, table=np.array([4]))}
    for i, r in enumerate(plan.values()):
        r["seq"] = tokens_of(20 + i, r["n"] + r["leave"] - r["join"])
        r["want"] = reference_logits(toy, r["seq"])
    pools = fresh_pools(model, 6, 3)
    dispatch.reset()
    checked = 0
    for step in range(40):
        for r in plan.values():
            if r["join"] == step:
                lg, pools = prefill(model, params, pools, r["seq"][:r["n"]],
                                    r["table"], r["slot"])
                np.testing.assert_allclose(lg, r["want"][r["n"] - 1], rtol=0,
                                           atol=LOGIT_TOL)
        live = {r["row"]: (r["seq"][r["n"] + step - r["join"]],
                           r["n"] + step - r["join"], r["table"], r["slot"])
                for r in plan.values() if r["join"] <= step < r["leave"]}
        lg, pools = decode(model, params, pools, live)
        for r in plan.values():
            if r["join"] <= step < r["leave"]:
                np.testing.assert_allclose(
                    lg[r["row"]], r["want"][r["n"] + step - r["join"]],
                    rtol=0, atol=LOGIT_TOL)
                checked += 1
    assert checked == 18 + 25 + 18
    # which forms ran: the conv mixer, and the experts' dense form (the CPU's)
    assert dispatch.selected().get("mixer=short_conv", 0) >= 4 * 2
    assert "experts=dense" in dispatch.selected()


@pytest.mark.parametrize("chunk", [1, 5, 13])
def test_a_chunked_prefill_is_the_whole_prefill(toy, chunk):
    """Pieces of one token (the conv state carried every position), and of
    lengths that divide nothing, each in a bucket of 16: the last position's
    logits and the conv state are the whole prefill's."""
    model, params = toy[:2]
    toks = tokens_of(7, 11 if chunk == 1 else 41)
    table = np.array([1])
    whole, pw = prefill(model, params, fresh_pools(model, 3, 3), toks, table, 1)
    pieces, pc = prefill(model, params, fresh_pools(model, 3, 3), toks, table, 1,
                         chunk=chunk, bucket=16)
    np.testing.assert_allclose(pieces, whole, rtol=0, atol=LOGIT_TOL)
    np.testing.assert_allclose(whole, reference_logits(toy, toks)[-1], rtol=0,
                               atol=LOGIT_TOL)
    for a, b in zip(pw["conv"], pc["conv"]):
        np.testing.assert_allclose(a[:, 1], b[:, 1], rtol=0, atol=1e-5)


def test_neighbouring_slots_are_left_alone(toy):
    """Two requests in slots 1 and 2: a slot's holder starts from zeros
    whatever was left there (bit for bit the prefill into a zeroed pool),
    and a step that a slot's request takes no part in leaves that slot bit
    for bit, in the lead's array and in the periods'."""
    model, params = toy[:2]
    a, b = tokens_of(8, 20), tokens_of(9, 33)
    zeroed = model.init_paged_cache(4, BS, jnp.float32, state_slots=4)
    lg0, p0 = prefill(model, params, zeroed, a, np.array([1]), 2)
    lg1, p1 = prefill(model, params, fresh_pools(model, 4, 4), a,
                      np.array([1]), 2)
    np.testing.assert_array_equal(lg0, lg1)
    for x, y in zip(p0["conv"], p1["conv"]):
        np.testing.assert_array_equal(x[:, 2], y[:, 2])
    _, p2 = prefill(model, params, p1, b, np.array([2]), 1)
    for x, y in zip(p1["conv"], p2["conv"]):
        np.testing.assert_array_equal(x[:, np.array([0, 2, 3])],
                                      y[:, np.array([0, 2, 3])])
    want = reference_logits(toy, np.concatenate([a, [3]]))
    lg, p3 = decode(model, params, p2, {0: (3, 20, np.array([1]), 2)}, width=2)
    np.testing.assert_allclose(lg[0], want[20], rtol=0, atol=LOGIT_TOL)
    for x, y in zip(p2["conv"], p3["conv"]):
        np.testing.assert_array_equal(x[:, np.array([1, 3])],
                                      y[:, np.array([1, 3])])
        assert float(jnp.abs(x[:, 2] - y[:, 2]).max()) > 1e-3


# --------------------------------------------------------------------- #
# the router


def _without_bias(params):
    return {**params, "layers": tuple(
        {**g, "mlp": {**g["mlp"], "b_select": jnp.zeros_like(g["mlp"]["b_select"])}}
        for g in params["layers"])}


@pytest.mark.parametrize("bias", [True, False], ids=["expert_bias", "no_bias"])
def test_the_router_with_and_without_expert_bias(toy, bias):
    """The program is the reference with the seeded selection bias and with
    a zero one; and the two are apart by ~2.7 in the logits, so a bias that
    is missed on either side shows."""
    model, own = toy[:2]
    params = own if bias else _without_bias(own)
    seq = tokens_of(31, 45)
    want = reference_logits(toy, seq, params=params)
    lg, pools = prefill(model, params, fresh_pools(model, 3, 3), seq[:40],
                        np.array([1]), 1)
    np.testing.assert_allclose(lg, want[39], rtol=0, atol=LOGIT_TOL)
    for pos in range(40, 45):
        lg, pools = decode(model, params, pools,
                           {0: (seq[pos], pos, np.array([1]), 1)}, width=1)
        np.testing.assert_allclose(lg[0], want[pos], rtol=0, atol=LOGIT_TOL)
    other = reference_logits(toy, seq,
                             params=_without_bias(own) if bias else own)
    assert float(np.abs(other - want).max()) > 1e3 * LOGIT_TOL


def test_the_normalisations_epsilon_is_the_configurations():
    """``norm_topk_eps`` reaches the division: 1e-6 for this family, the
    router's own 1e-20 under sigmoid scoring where a preset gives none
    (Solar's and SDAR's), nothing under softmax."""
    logits = jnp.asarray([[-30.0, -31.0, -32.0, -40.0]], jnp.float32)
    s = jax.nn.sigmoid(logits)[0]
    for eps, kw in ((1e-6, dict(norm_eps=1e-6)), (1e-20, {})):
        w, e, _ = topk_routing(logits, 2, True, scoring="sigmoid",
                               select_bias=jnp.zeros((4,)), **kw)
        np.testing.assert_allclose(w[0], s[:2] / (s[0] + s[1] + eps), rtol=1e-6)
        assert e.tolist() == [[0, 1]]
    assert float(jnp.sum(topk_routing(logits, 2, True)[0])) == pytest.approx(1.0)
    assert get_model("lfm2_moe", "tiny").moe.norm_topk_eps == 1e-6
    assert get_model("solar_open2", "tiny").moe.norm_topk_eps is None
    assert get_model("sdar", "tiny").moe.norm_topk_eps is None


# --------------------------------------------------------------------- #
# the controls that hold LOGIT_TOL to its purpose


def _worst_decode_error(model, params, toy, seq, n):
    want = reference_logits(toy, seq)
    lg, pools = prefill(model, params, fresh_pools(model, 3, 3), seq[:n],
                        np.array([1]), 1)
    worst = float(np.abs(lg - want[n - 1]).max())
    for pos in range(n, len(seq)):
        lg, pools = decode(model, params, pools,
                           {0: (seq[pos], pos, np.array([1]), 1)}, width=1)
        worst = max(worst, float(np.abs(lg[0] - want[pos]).max()))
    return worst


@pytest.mark.parametrize("what", ["router", "conv"])
def test_a_lower_precision_fails_the_tolerance(toy, monkeypatch, what):
    """The router's logits from operands rounded to bf16, or the conv's taps
    over inputs and weights rounded to bf16 with a bf16 result (the nearest
    precision below the float32 this test's configuration states): either
    moves the logits past the tolerance."""
    model, params = toy[:2]
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if what == "router":
        route = moe_lm.MoECausalLM._route

        def rounded(self, lp, tokens):
            return route(self, {**lp, "gate_w": bf16(lp["gate_w"])}, bf16(tokens))
        monkeypatch.setattr(moe_lm.MoECausalLM, "_route", rounded)
    else:
        taps = SM._short_conv_taps
        monkeypatch.setattr(
            SM, "_short_conv_taps", lambda win, lp, Tn: bf16(taps(
                bf16(win), {**lp, "conv_w": bf16(lp["conv_w"])}, Tn)))
    faulted = type(model)(model.config, model.moe)    # a jit cache of its own
    worst = _worst_decode_error(faulted, params, toy, tokens_of(1, 37 + 8), 37)
    assert worst > 5 * LOGIT_TOL, worst


@pytest.mark.parametrize("fault", ["dropped_tap", "lost_conv_state",
                                   "shifted_conv_state", "lead_skipped"])
def test_a_planted_fault_fails_the_tolerance(toy, monkeypatch, fault):
    """A conv that leaves out its oldest tap, a decode step that reads
    zeros for the conv state, one that reads the two values a position late,
    a stack that skips its leading layer: each moves the logits by far more
    than the tolerance."""
    model, params = toy[:2]
    if fault == "dropped_tap":
        taps = SM._short_conv_taps
        monkeypatch.setattr(SM, "_short_conv_taps", lambda win, lp, Tn: taps(
            win, {**lp, "conv_w": lp["conv_w"].at[0].set(0.0)}, Tn))
    elif fault in ("lost_conv_state", "shifted_conv_state"):
        dec = SM.STATE_MIXERS[T.SHORT_CONV].decode
        lost = fault == "lost_conv_state"

        def faulty(cfg, x, lp, state, conv, base, slots):
            read = jnp.zeros_like(conv) if lost else jnp.roll(conv, 1, axis=1)
            y, state, _ = dec(cfg, x, lp, state, read, base, slots)
            return y, state, dec(cfg, x, lp, state, conv, base, slots)[2]
        monkeypatch.setitem(SM.STATE_MIXERS, T.SHORT_CONV,
                            SM.STATE_MIXERS[T.SHORT_CONV]._replace(decode=faulty))
    else:
        model = type(model)(dataclasses.replace(
            model.config, lead_kinds=(), n_layer=4), model.moe)
        params = {k: v for k, v in params.items() if k != "lead"}
    faulted = type(model)(model.config, model.moe)
    if fault == "lead_skipped":
        pools = faulted.init_paged_cache(3, BS, jnp.float32, state_slots=3)
        seq = tokens_of(1, 37)
        lg, _ = jax.jit(faulted.forward_paged_prefill)(
            params, np.pad(seq, (0, 128 - 37))[None], pools,
            np.where(np.arange(128) < 37, BS + np.arange(128),
                     np.arange(128)).astype(np.int32), np.int32(36), np.int32(1))
        worst = float(np.abs(np.asarray(lg)[0]
                             - reference_logits(toy, seq)[-1]).max())
    else:
        worst = _worst_decode_error(faulted, params, toy,
                                    tokens_of(1, 37 + 8), 37)
    assert worst > 100 * LOGIT_TOL, worst
