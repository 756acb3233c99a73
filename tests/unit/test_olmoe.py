"""OLMoE on the program's normal paths against its plain reference
(``perfbench/reference/olmoe_decoder.py``: float32, every expert the slow
way), on the ``olmoe`` ``tiny`` preset (8 experts, top-2, query/key RMSNorm,
rope, untied head) with seeded weights perturbed as ``perfbench/weights.py``
perturbs them (every norm scale 1 + N(0, 0.1); the router is random already).

Tolerances. Program and reference both compute in float32 here (the CPU's
default matmul precision is full float32), so they differ by summation order
only: logits of magnitude ~1 agree to 5e-7. ``LOGIT_TOL`` 2e-5 is forty
times that and under a tenth of what the smallest control moves them by: a
router fed bf16 logits moves them by 1e-3 and more (its weights change in
the third digit and near-ties flip), a query/key norm left out or taken per
head by 1e-1. The controls below hold the tolerance to that.
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
from deepspeed_tpu.models import moe_lm
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops import dispatch

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench")
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from reference import olmoe_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-olmoe-tiny"
LOGIT_TOL = 2e-5


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


@pytest.fixture(scope="module")
def toy():
    """(model, float32 params, the reference's cfg, the name map)."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"])
    params = make_params(model, 2600000026, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


def reference_logits(toy, tokens):
    _, params, cfg, name_map = toy
    w = ref.Weights(params, name_map)
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens))
    return np.asarray(ref.logits_rows(cfg, w, h.reshape(-1, h.shape[-1]))
                      ).reshape(*tokens.shape, -1)


@pytest.fixture(params=["dense", "sorted"])
def form(request, monkeypatch):
    """Both no-drop forms of the model's MLP, whatever the rows of the call:
    the model picks by ``_SORTED_DISPATCH_MIN_ROWS`` while it traces."""
    monkeypatch.setattr(moe_lm, "_SORTED_DISPATCH_MIN_ROWS",
                        1 << 30 if request.param == "dense" else 0)
    return request.param


def tokens_of(seed, shape, vocab=50304):
    return np.random.default_rng(seed).integers(0, vocab, size=shape).astype(np.int32)


# --------------------------------------------------------------------- #
# routing and dispatch


def test_topk_routing_is_float32_softmax_then_topk():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((33, 16)).astype(np.float32) * 3
    p = np.exp(logits.astype(np.float64) - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1)[:, :5]
    for dtype in (jnp.float32, jnp.bfloat16):
        w, e, probs = sharded_moe.topk_routing(jnp.asarray(logits, dtype), 5)
        assert w.dtype == probs.dtype == jnp.float32 and e.dtype == jnp.int32
    w, e, probs = sharded_moe.topk_routing(jnp.asarray(logits), 5)
    np.testing.assert_array_equal(np.asarray(e), order)
    np.testing.assert_allclose(np.asarray(w), np.take_along_axis(p, order, -1),
                               rtol=2e-6)
    assert float(np.asarray(w).sum(-1).max()) < 1.0     # not renormalised
    wn, _, _ = sharded_moe.topk_routing(jnp.asarray(logits), 5, norm_topk_prob=True)
    np.testing.assert_allclose(np.asarray(wn).sum(-1), 1.0, rtol=1e-6)


def _loads(kind, rows, n_experts, k, rng):
    """[rows, k] expert choices (distinct within a row)."""
    if kind == "uneven":
        p = rng.dirichlet(np.full(n_experts, 0.3))
        return np.stack([rng.choice(n_experts, size=k, replace=False, p=p)
                         for _ in range(rows)])
    if kind == "an_expert_with_no_token":
        return np.stack([rng.choice(np.arange(1, n_experts), size=k, replace=False)
                         for _ in range(rows)])
    assert kind == "an_expert_with_all_of_them"
    rest = np.stack([rng.choice(np.arange(1, n_experts), size=k - 1, replace=False)
                     for _ in range(rows)])
    return np.concatenate([np.zeros((rows, 1), int), rest], axis=1)


@pytest.mark.parametrize("form", ["sorted", "dense"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("kind", ["uneven", "an_expert_with_no_token",
                                  "an_expert_with_all_of_them"])
def test_nodrop_dispatch_against_the_slow_loop(kind, masked, form):
    """Every assignment computed, weighted and summed; a row that is not
    valid reaches no expert and is counted nowhere. float32 sums in another
    order: 1e-5 of values of magnitude ~1."""
    rng = np.random.default_rng(7)
    rows, D, F, E, k = 37, 16, 24, 6, 3
    x = rng.standard_normal((rows, D)).astype(np.float32)
    wu = rng.standard_normal((E, D, F)).astype(np.float32) / 4
    wd = rng.standard_normal((E, F, D)).astype(np.float32) / 4
    experts = _loads(kind, rows, E, k, rng)
    weights = rng.random((rows, k)).astype(np.float32)
    valid = rng.random(rows) > 0.3 if masked else None

    want = np.zeros((rows, D))
    counts = np.zeros(E, int)
    for t in range(rows):
        if valid is not None and not valid[t]:
            continue
        for j in range(k):
            e = experts[t, j]
            want[t] += weights[t, j] * (np.tanh(x[t] @ wu[e]) @ wd[e])
            counts[e] += 1

    if form == "sorted":
        def grouped(xs, sizes):
            h = jnp.tanh(jax.lax.ragged_dot(xs, jnp.asarray(wu), sizes))
            return jax.lax.ragged_dot(h, jnp.asarray(wd), sizes)
        got, n = sharded_moe.sorted_dispatch(
            jnp.asarray(x), jnp.asarray(weights), jnp.asarray(experts, jnp.int32),
            E, grouped, None if valid is None else jnp.asarray(valid))
    else:
        def dense(xs, combine):
            h = jnp.tanh(jnp.einsum("td,edf->tef", xs, wu)) * combine[:, :, None]
            return jnp.einsum("tef,efd->td", h, wd)
        got, n = sharded_moe.dense_dispatch(
            jnp.asarray(x), jnp.asarray(weights), jnp.asarray(experts, jnp.int32),
            E, dense, None if valid is None else jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(n), counts)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    if kind == "an_expert_with_no_token":
        assert counts[0] == 0
    if kind == "an_expert_with_all_of_them":
        assert counts[0] == (rows if valid is None else valid.sum())


def test_sorted_dispatch_is_differentiable():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((9, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 8, 8)), jnp.float32)
    experts = jnp.asarray(_loads("uneven", 9, 4, 2, rng), jnp.int32)
    weights = jnp.asarray(rng.random((9, 2)), jnp.float32)

    def sorted_loss(x, w, weights):
        out, _ = sharded_moe.sorted_dispatch(
            x, weights, experts, 4, lambda xs, s: jax.lax.ragged_dot(xs, w, s))
        return jnp.sum(out ** 2)

    def loop_loss(x, w, weights):
        out = sum(weights[:, j, None] * jnp.einsum("td,tdf->tf", x, w[experts[:, j]])
                  for j in range(2))
        return jnp.sum(out ** 2)

    got = jax.grad(sorted_loss, argnums=(0, 1, 2))(x, w, weights)
    want = jax.grad(loop_loss, argnums=(0, 1, 2))(x, w, weights)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_config_refuses_what_no_dispatch_can_do():
    cfg = T.TransformerConfig(vocab_size=64, n_layer=1, n_head=2, d_model=16)
    with pytest.raises(ValueError, match="nodrop"):
        MoECausalLM(cfg, MoEConfig(k=8))                     # capacity routes 1 or 2
    with pytest.raises(ValueError, match="residual"):
        MoECausalLM(cfg, MoEConfig(dispatch="nodrop", use_residual=True))
    model = MoECausalLM(cfg, MoEConfig(dispatch="nodrop", k=3, num_experts=4,
                                       expert_d_ff=8, expert_activation="swiglu"))
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))["layers"]["mlp"]
    assert shapes["w_gate"].shape == (1, 4, 16, 8) and "b_up" not in shapes
    assert model.num_parameters == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(
            jax.eval_shape(model.init_params, jax.random.key(0))))


@pytest.mark.parametrize("activation", ["gelu", "swiglu"])
def test_top1_without_drops_is_the_capacity_path_with_room_for_all(activation, form):
    """Expert kind and dispatch are independent: at k = 1 and a capacity that
    holds every token, the capacity dispatch and both no-drop forms compute
    the same layer, with biased gelu experts and with gated ones."""
    cfg = T.TransformerConfig(vocab_size=64, n_layer=1, n_head=2, d_model=16,
                              max_seq=32, remat=False)
    kind = dict(num_experts=4, k=1, expert_d_ff=24, expert_activation=activation)
    cap = MoECausalLM(cfg, MoEConfig(drop_tokens=False, use_rts=False, **kind))
    nod = MoECausalLM(cfg, MoEConfig(dispatch="nodrop", **kind))
    params = cap.init_params(jax.random.key(0))
    lp = jax.tree.map(lambda a: a[0] + 0.1 * jax.random.normal(
        jax.random.key(a.size), a.shape[1:]), params["layers"]["mlp"])
    x = jax.random.normal(jax.random.key(1), (2, 9, 16))
    used = (jnp.arange(18) % 5 != 0).astype(jnp.float32)
    want, _, n_cap = cap._moe_mlp(lp, x, None, train=False, used_token=used)
    got, _, n_nod = nod._moe_mlp(lp, x, None, train=False, used_token=used)
    np.testing.assert_array_equal(np.asarray(n_cap), np.asarray(n_nod))
    assert int(n_nod.sum()) == int(used.sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# --------------------------------------------------------------------- #
# the model against the reference


def test_full_forward_against_the_reference(toy, form):
    model, params, _, _ = toy
    tokens = tokens_of(1, (2, 24))
    got, _ = jax.jit(lambda p, t: model.forward(p, t, train=False))(params, tokens)
    err = np.abs(np.asarray(got) - reference_logits(toy, tokens)).max()
    assert err < LOGIT_TOL, err


def test_the_form_follows_the_rows_of_the_call():
    """Every expert over every row under ``_SORTED_DISPATCH_MIN_ROWS`` rows
    of a call, ragged groups from there on; no option chooses."""
    cfg = T.TransformerConfig(vocab_size=64, n_layer=1, n_head=2, d_model=16,
                              max_seq=32, remat=False)
    model = MoECausalLM(cfg, MoEConfig(dispatch="nodrop", num_experts=4, k=2,
                                       expert_d_ff=8, expert_activation="swiglu"))
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      jax.eval_shape(model.init_params,
                                     jax.random.key(0))["layers"]["mlp"])
    for rows, ragged in ((moe_lm._SORTED_DISPATCH_MIN_ROWS - 1, False),
                         (moe_lm._SORTED_DISPATCH_MIN_ROWS, True)):
        x = jax.ShapeDtypeStruct((1, rows, 16), jnp.float32)
        jaxpr = str(jax.make_jaxpr(lambda lp, x: model._nodrop_mlp(lp, x))(lp, x))
        assert ("ragged_dot" in jaxpr) == ragged, rows


@pytest.mark.parametrize("case,rows,want", [
    ("a_decode_step", 16, True),
    ("one_row_tile", moe_lm._GROUPED_KERNEL_MAX_ROWS, True),
    ("a_row_more", moe_lm._GROUPED_KERNEL_MAX_ROWS + 1, False),
    ("the_xla_backend", 16, False),
    ("off_a_tpu_on_auto", 16, False),
    ("int8_experts", 16, False),
    ("ungated_experts", 16, False),
    ("a_mesh_of_eight", 16, False),
])
def test_the_paged_form_follows_the_rows_and_where_a_kernel_is_legal(
        case, rows, want):
    """A paged program asks the layer scan for the expert stacks whole
    (``stack_keys``: the grouped kernel) by the rows of its calls, where a
    bare Pallas call is legal, for plain gated experts; no option chooses."""
    from deepspeed_tpu.ops.quant import quantize_int8
    backend = {"the_xla_backend": "xla", "off_a_tpu_on_auto": "auto"}.get(
        case, "flash")
    cfg = T.TransformerConfig(vocab_size=64, n_layer=2, n_head=2, d_model=16,
                              max_seq=32, remat=False, attention_backend=backend)
    model = MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", num_experts=4, k=2, expert_d_ff=8,
        expert_activation="gelu" if case == "ungated_experts" else "swiglu"))
    params = model.init_params(jax.random.key(0))
    if case == "int8_experts":
        params["layers"]["mlp"]["w_up"] = quantize_int8(
            params["layers"]["mlp"]["w_up"])
    if case == "a_mesh_of_eight":
        dist.set_mesh(dist.build_mesh({"dp": 8}))
    pools = model.init_paged_cache(4, 16, dtype=jnp.float32)
    mlp_fn = model._paged(params, pools, jnp.zeros((rows,), jnp.int32))
    assert (getattr(mlp_fn, "stack_keys", None) is not None) == want
    if want:
        assert mlp_fn.stack_keys == ("w_gate", "w_up", "w_down")


def _per_head_qk_norm(cfg, q, k, lp):
    def rms(x, p):
        h = x.reshape(*x.shape[:-1], cfg.n_head, cfg.head_dim)
        h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + cfg.norm_eps)
        return h.reshape(x.shape) * p["scale"]
    return rms(q, lp["q_norm"]), rms(k, lp["k_norm"])


def _bf16_router(logits, k, norm_topk_prob=False):
    return sharded_moe.topk_routing(
        logits.astype(jnp.bfloat16).astype(jnp.float32), k, norm_topk_prob)


@pytest.mark.parametrize("control", ["no_qk_norm", "qk_norm_per_head", "bf16_router"])
def test_the_tolerance_bites(toy, control, monkeypatch):
    """What must fail does: each control moves the logits by far more than
    ``LOGIT_TOL``."""
    model, params, _, _ = toy
    if control == "no_qk_norm":
        model = MoECausalLM(dataclasses.replace(model.config, qk_norm=False), model.moe)
    elif control == "qk_norm_per_head":
        monkeypatch.setattr(T, "_qk_norm", _per_head_qk_norm)
    else:
        monkeypatch.setattr(moe_lm, "topk_routing", _bf16_router)
    tokens = tokens_of(1, (2, 24))
    got, _ = model.forward(params, tokens, train=False)
    err = np.abs(np.asarray(got) - reference_logits(toy, tokens)).max()
    assert err > 10 * LOGIT_TOL, (control, err)


def test_loss_and_gradients_against_the_reference(toy, form):
    """``MoECausalLM.loss`` (cross-entropy + 0.01 x the load-balancing term)
    and its gradient against ``jax.grad`` of the reference's loss over the
    same parameter tree. Gradients of magnitude up to ~1e-2 agree to ~1e-7;
    5e-6 absolute, and 1e-5 on the loss."""
    model, params, cfg, name_map = toy
    tokens = tokens_of(4, (2, 16))
    loss, grads = jax.value_and_grad(model.loss)(params, {"input_ids": jnp.asarray(tokens)})
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss_value(cfg, ref.Weights(p, name_map), jnp.asarray(tokens)))(params)
    assert abs(float(loss) - float(want)) < 1e-5, (float(loss), float(want))
    assert abs(float(want) - ref.next_token_loss(
        cfg, ref.Weights(params, name_map), jnp.asarray(tokens))) < 1e-6
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(w).max()) > 0, f"{name}: the reference never reads it"
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-6, err_msg=name)


# --------------------------------------------------------------------- #
# the paged programs the engine serves through


def test_paged_prefill_then_decode_against_the_reference(toy, form):
    """The engine's own jitted programs: a prompt in a padded bucket, then
    fused decode steps over three rows of which one is empty. Logits at the
    prompt's last position and at every decoded position against the
    reference's full forward over the same tokens; the [L, E + 1] counts the
    decode program returns hold the two real rows' assignments (computed by
    expert, then owed) and nothing of the empty row."""
    model, params, _, _ = toy
    bs, rows = 16, 3
    engine = deepspeed_tpu.init_inference(
        model, params=params, dtype="fp32",
        serving={"block_size": bs, "max_running": rows, "max_num_blocks": 12})
    assert engine._paged_supported()
    with engine._mesh_scope():
        prefill, decode = engine._ensure_paged_jits()[:2]
    pools, _ = engine._paged_pools(12, bs)
    seqs = [tokens_of(5, (21,)), tokens_of(6, (37,))]
    prompt_len, n_max = [13, 30], 4
    tables = [np.asarray([3, 7, 0, 0], np.int32), np.asarray([5, 1, 9, 0], np.int32)]
    want = [reference_logits(toy, s[None])[0] for s in seqs]

    for i, (s, n, table) in enumerate(zip(seqs, prompt_len, tables)):
        Tb = engine._bucket(n, model.config.max_seq)
        assert Tb > n                                   # a padded bucket
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :n] = s[:n]
        slots = engine._flat_slots(table, 0, n, Tb, bs)
        logits, pools = prefill(engine.params, jnp.asarray(toks), pools,
                                jnp.asarray(slots, jnp.int32), jnp.int32(n - 1))
        assert np.abs(np.asarray(logits)[0] - want[i][n - 1]).max() < LOGIT_TOL

    k, L = model.moe.k, model.config.n_layer
    for step in range(7):
        bt = np.zeros((rows, n_max), np.int32)
        pos = np.zeros((rows,), np.int32)
        toks = np.zeros((rows, 1), np.int32)
        for i, row in enumerate((0, 2)):                # row 1 stays empty
            bt[row] = tables[i]
            pos[row] = prompt_len[i] + step
            toks[row, 0] = seqs[i][pos[row]]
        logits, pools, counts = decode(engine.params, jnp.asarray(toks), pools,
                                       jnp.asarray(bt), jnp.asarray(pos))
        for i, row in enumerate((0, 2)):
            err = np.abs(np.asarray(logits)[row] - want[i][pos[row]]).max()
            assert err < LOGIT_TOL, (step, row, err)
        counts = np.asarray(counts)
        assert counts.shape == (L, model.moe.num_experts + 1)
        np.testing.assert_array_equal(counts[:, :-1].sum(axis=1), 2 * k)
        np.testing.assert_array_equal(counts[:, -1], 2 * k)   # what was owed


def test_paged_prefill_then_decode_through_the_grouped_kernel(toy):
    """The paged programs with the experts read by
    ``ops/pallas/grouped_expert_mlp.py`` (interpreted) from the layer stack
    in place: what they select on one TPU for calls of at most
    ``_GROUPED_KERNEL_MAX_ROWS`` rows, chosen here as the paged kernel is
    (``attention_backend="flash"``; no mesh, so a bare kernel is legal). A
    prompt in a padded bucket, then decode steps over three rows of which
    two are empty, logits against the reference's full forward."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        preset = json.load(f)["preset"]
    model, params = get_model(**preset, attention_backend="flash"), toy[1]
    bs, rows, n, S = 16, 3, 21, 29
    seq = tokens_of(8, (S,))
    want = reference_logits(toy, seq[None])[0]
    pools = model.init_paged_cache(6, bs, dtype=jnp.float32)
    table = np.asarray([3, 1, 4, 0], np.int32)
    dispatch.reset()
    t = np.arange(32)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = seq[:n]
    slots = np.where(t < n, table[t // bs] * bs + t % bs, t % bs).astype(np.int32)
    logits, pools = jax.jit(model.forward_paged_prefill)(
        params, toks, pools, slots, np.int32(n - 1))
    assert np.abs(np.asarray(logits)[0] - want[n - 1]).max() < LOGIT_TOL
    decode = jax.jit(model.forward_paged_decode)
    bt = np.zeros((rows, 4), np.int32)
    bt[2] = table
    for p in range(n, S):
        pos = np.zeros((rows,), np.int32)
        pos[2] = p
        nt = np.zeros((rows, 1), np.int32)
        nt[2, 0] = seq[p]
        logits, pools, counts = decode(params, nt, pools, bt, pos)
        assert np.abs(np.asarray(logits)[2] - want[p]).max() < LOGIT_TOL, p
        # the one real row's assignments, computed and owed; none of the
        # empty rows'
        np.testing.assert_array_equal(np.asarray(counts).sum(axis=1),
                                      2 * model.moe.k)
    chosen = dispatch.selected()
    assert chosen["experts=grouped_kernel"] == 2     # the bucket and the step
    assert chosen["kernel/grouped_expert_mlp=interpret"] == 2
    assert "experts=dense" not in chosen


def test_generate_batch_pages_an_moe_model_and_counts_it(toy):
    """Through ``generate_batch``: the paged engine (no static fallback),
    tokens equal to greedy decoding by full forwards, and the
    ``serving/moe_*`` counters of the decode steps."""
    from deepspeed_tpu.monitor.metrics import get_registry
    model, params, _, _ = toy
    engine = deepspeed_tpu.init_inference(
        model, params=params, dtype="fp32", telemetry={"enabled": True},
        serving={"paged": "on", "block_size": 16, "max_running": 4,
                 "max_num_blocks": 40})
    before = dict(get_registry().snapshot()["counters"])
    prompts = [tokens_of(8, (5,)), tokens_of(9, (19,)), tokens_of(10, (33,))]
    outs = engine.generate_batch(prompts, max_new_tokens=5)
    fwd = jax.jit(lambda p, t: model.forward(p, t, train=False)[0])
    for prompt, out in zip(prompts, outs):
        seq = list(prompt)
        for _ in range(5):
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray([seq]))[0, -1])))
        assert seq == list(np.asarray(out))
    after = get_registry().snapshot()["counters"]
    grew = lambda n: after.get("serving/" + n, 0) - before.get("serving/" + n, 0)  # noqa: E731
    steps, L, k = grew("decode_steps"), model.config.n_layer, model.moe.k
    assert steps > 0 and grew("moe_layer_steps") == steps * L
    # three requests, four decode steps each after the prefill's token
    assert grew("moe_assignments") == 3 * 4 * k * L
    assert grew("moe_dropped_assignments") == 0
    assert 0 < grew("moe_experts_touched") <= grew("moe_assignments")
    assert grew("moe_max_expert_load") >= grew("moe_layer_steps")


def test_ep_and_streaming_stay_on_the_static_path(toy):
    model, params, _, _ = toy
    engine = deepspeed_tpu.init_inference(model, params=params,
                                          config={"dtype": "fp32", "moe": {"ep_size": 2}})
    assert not engine._paged_supported()
    with pytest.raises(ValueError, match="ep_size"):
        engine.open_serve_session(max_new=4)
