"""Trinity's stack on the normal path: SANDWICH norms, a gated GQA with a
per-head q/k norm, rotary WINDOW layers beside a full layer without
positions behind one leading dense window layer, a sigmoid router with a
selection bias over a share of the experts beside a shared expert, the
embedding times sqrt(d), and the window layers' blocks handed out by need
(a table from the host). The ``trinity`` ``tiny`` preset (a window of 256,
two periods) with seeded weights, float32 on the CPU, against the plain
reference (``perfbench/reference/trinity_decoder.py``): one layer of each
kind, prefill then decode through the paged cache in LOGITS with a short row
and a row past the window in one batch, the reference's planted faults that
must fail, the chip's share tied to the whole layer, what the training
forward refuses, and the engine under admission, preemption and a cancel."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.block_allocator import BlockAllocator
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serve import AsyncServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.monitor.metrics import get_registry

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "perfbench")
sys.path[:0] = [BENCH, os.path.join(ROOT, "benchmarks")]
import correctness  # noqa: E402
import trinity_check_controls as controls_tool  # noqa: E402
from reference import trinity_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-trinity-tiny"
W = 256
BS = 16
R = W // BS + 1
#: program and reference are both float32 here: under the preset's init
#: (logits of 0.3-0.7) they agree to 3e-7 at worst
LOGIT_TOL = 2e-5
#: a served token is the reference's own pick
SERVED_STEPS = 0.01


@pytest.fixture(autouse=True)
def clean_state():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"])
    params = make_params(model, 3700000037, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


def reference_logits(toy, tokens, cfg=None):
    w = ref.Weights(toy[1], toy[3])
    h = ref.final_hidden(cfg or toy[2], w, jnp.asarray(tokens[None]))
    return np.asarray(ref.logits_rows(cfg or toy[2], w, h[0]))


def paged_batch_logits(model, params, seqs, n_prompts, steps, rows=4):
    """Prefill each of ``seqs``' first ``n_prompts[r]`` tokens whole, then
    decode them TOGETHER a token a step (teacher-forced on ``seqs``) through
    the paged pools, idle rows beside them, blocks and window blocks from a
    ``BlockAllocator`` as the engine takes them: [requests, 1 + steps, V]
    logits, and how many window blocks were handed out while decoding."""
    nb = 1 + sum(-(-(n + steps) // BS) for n in n_prompts)
    alloc = BlockAllocator(
        nb, BS, window_blocks=BlockAllocator.window_pool_blocks(nb, rows, R),
        ring_blocks=R)
    pools = model.init_paged_cache(nb, BS, dtype=jnp.float32,
                                   window_blocks=alloc.window_blocks)
    prefill = jax.jit(model.forward_paged_prefill)
    decode = jax.jit(model.forward_paged_decode)
    held, rings, out = [], [], []
    for seq, n in zip(seqs, n_prompts):
        held.append(alloc.allocate(alloc.blocks_for_tokens(n)))
        rings.append([])
        alloc.grow_window(rings[-1], len(held[-1]))
        Tb = InferenceEngine._bucket(n, 1024)
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :n] = seq[:n]
        slots = InferenceEngine._flat_slots(
            np.asarray(held[-1], np.int32), 0, n, Tb, BS).astype(np.int32)
        wt = np.zeros((R,), np.int32)
        wt[:len(rings[-1])] = rings[-1]
        lg, pools = prefill(params, toks, pools, slots, np.int32(n - 1),
                            window_table=wt)
        out.append([np.asarray(lg[0])])
    handed = 0
    for s in range(steps):
        bt = np.zeros((rows, 1024 // BS), np.int32)
        wt = np.zeros((rows, R), np.int32)
        pos = np.zeros((rows,), np.int32)
        nt = np.zeros((rows, 1), np.int32)
        for r, (seq, n) in enumerate(zip(seqs, n_prompts)):
            pos[r], nt[r, 0] = n + s, seq[n + s]
            if pos[r] >= len(held[r]) * BS:
                held[r] += alloc.allocate(1)
                before = len(rings[r])
                alloc.grow_window(rings[r], len(held[r]))
                handed += len(rings[r]) - before
            bt[r, :len(held[r])] = held[r]
            wt[r, :len(rings[r])] = rings[r]
        lg, pools, _ = decode(params, nt, pools, bt, pos, window_tables=wt)
        for r in range(len(seqs)):
            out[r].append(np.asarray(lg[r]))
    return np.stack([np.stack(o) for o in out]), handed


# --------------------------------------------------------------------- #
# the model against the reference, in logits

# a short row beside a row past the window (300: its ring has wrapped, 19
# blocks in a ring of 17), the short one crossing block borders below a ring
# while it decodes; a row at the window's edge; a row on a block border
@pytest.mark.parametrize("n_prompts", [(40, 300), (15, 257), (128, 700),
                                       (255, 60, 1)])
def test_prefill_then_decode_is_the_reference(toy, n_prompts):
    model, params = toy[:2]
    steps = 40
    rng = np.random.default_rng(sum(n_prompts))
    seqs = [rng.integers(0, 512, size=n + steps + 1).astype(np.int32)
            for n in n_prompts]
    got, handed = paged_batch_logits(model, params, seqs, n_prompts, steps)
    # every short row took window blocks while it decoded
    assert handed >= sum(n + steps < W for n in n_prompts)
    for r, (seq, n) in enumerate(zip(seqs, n_prompts)):
        want = reference_logits(toy, seq[:n + steps])[n - 1:]
        np.testing.assert_allclose(got[r], want, atol=LOGIT_TOL, rtol=0)


def test_an_absent_table_is_the_slots_whole_ring(toy):
    """The callers that hand slots and no table (a sizing tool, a kernel's
    test) get the ring a slot they got before."""
    model, params = toy[:2]
    n, rows = 300, 3
    seq = np.random.default_rng(5).integers(0, 512, size=n + 9).astype(np.int32)
    nb = 64
    pools = model.init_paged_cache(nb + 1, BS, dtype=jnp.float32,
                                   state_slots=rows + 1)
    assert pools["wk"].shape[1] == rows * R + 1
    table = np.arange(1, nb + 1, dtype=np.int32)
    Tb = InferenceEngine._bucket(n, 1024)
    toks = np.zeros((1, Tb), np.int32)
    toks[0, :n] = seq[:n]
    slots = InferenceEngine._flat_slots(table, 0, n, Tb, BS).astype(np.int32)
    lg, pools = jax.jit(model.forward_paged_prefill)(
        params, toks, pools, slots, np.int32(n - 1), state_slot=np.int32(2))
    out = [np.asarray(lg[0])]
    bt = np.zeros((rows, nb), np.int32)
    bt[1] = table
    ss = np.zeros((rows,), np.int32)
    ss[1] = 2
    decode = jax.jit(model.forward_paged_decode)
    for p in range(n, n + 8):
        pos = np.zeros((rows,), np.int32)
        pos[1] = p
        nt = np.zeros((rows, 1), np.int32)
        nt[1, 0] = seq[p]
        lg, pools, _ = decode(params, nt, pools, bt, pos, state_slots=ss)
        out.append(np.asarray(lg[1]))
    want = reference_logits(toy, seq[:n + 8])[n - 1:]
    np.testing.assert_allclose(np.stack(out), want, atol=LOGIT_TOL, rtol=0)
    with pytest.raises(ValueError, match="window tables"):
        model.forward_paged_decode(params, nt, pools, bt, pos)


@pytest.mark.parametrize("l", [0, 1, 4], ids=["lead", "window", "full"])
def test_one_layer_is_the_references(toy, l):
    """The lead (a dense window layer), a window MoE layer and the full MoE
    layer, each alone on a random stream of 300 positions (past the
    window): the paged prefill's block against ``trinity_decoder.layer``."""
    model, params, rcfg, name_map = toy
    cfg = model.config
    S = 300
    x = 3.0 * jax.random.normal(jax.random.key(l), (1, S, cfg.d_model))
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    group = params["lead"][0] if l == 0 else params["layers"][(l - 1) % 4]
    lp = jax.tree.map(lambda a: a[0], group)
    kind = T.WINDOW_ATTENTION if l < 4 else T.ATTENTION
    nb = -(-S // BS) + 1
    pools = model.init_paged_cache(nb, BS, dtype=jnp.float32, window_blocks=nb)
    kp = pools["wk" if l < 4 else "k"][0]
    slots = BS + jnp.arange(S, dtype=jnp.int32)
    mlp_fn = None if l == 0 else model._paged(params, pools, slots)
    got, _, _ = T._decode_block(
        cfg, x, lp,
        lambda xn: T._paged_prefill_attention(
            cfg, xn, lp["attn"], positions, kp, kp, slots,
            window=cfg.attn_window if l < 4 else 0),
        mlp_fn, scope=kind)
    with jax.default_matmul_precision("highest"):
        want = ref.layer(rcfg, ref.Weights(params, name_map).layer(l), x, l)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


@pytest.mark.parametrize("fault", [f for f in controls_tool.REFERENCE_FAULTS
                                   if f != "float8"])
def test_a_planted_fault_fails_the_tolerance(toy, fault):
    """The controls ``benchmarks/trinity_check_controls.py`` plants in the
    reference each move the logits of a sequence past the window far beyond
    ``LOGIT_TOL``: the comparison above sees every one of them."""
    seq = np.random.default_rng(9).integers(0, 512, size=330).astype(np.int32)
    want = reference_logits(toy, seq)
    with controls_tool.faulty_reference(ref, fault) as cfg_of:
        got = reference_logits(toy, seq, cfg_of(toy[2]))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL
    # and the module is whole again
    np.testing.assert_allclose(reference_logits(toy, seq[:50]), want[:50],
                               atol=LOGIT_TOL, rtol=0)


def test_the_preset_says_what_it_is():
    cut = get_model("trinity", "large-preview-5l-ep8")
    cfg = cut.config
    assert cfg.lead_kinds == ("window_attention",)
    assert cfg.period == ("window_attention",) * 3 + ("attention",)
    assert cfg.cache_spec == {"kv": 1, "state": 0, "window": 4, "latent": 0}
    assert cfg.attn_window == 4096 and cfg.ring_blocks(128) == 33
    assert cfg.takes_rope("window_attention") and not cfg.takes_rope("attention")
    assert cfg.norm_position == "sandwich" and cfg.qk_norm == "head"
    assert cfg.attn_out_gate and not cfg.tie_embeddings
    assert cfg.embedding_multiplier == pytest.approx(3072 ** 0.5)
    assert (cut.moe.num_experts, cut.router_width, cut.moe.k) == (32, 256, 4)
    assert cut.moe.norm_topk_eps == 1e-20
    assert cut.moe.routed_scaling_factor == 2.448
    assert cut.num_parameters == 4_321_903_872
    # a layer with all 256 experts, and with it the whole model's count:
    # six dense layers, 54 MoE layers, the embedding, the head, the norm
    dense, moe_layer = 176_173_312, 7_339_782_656
    whole = get_model("trinity", "large-preview-5l-ep8",
                      moe={"num_experts": 256, "router_experts": None})
    assert whole.num_parameters == dense + 4 * moe_layer \
        + 2 * 25_024 * 3_072 + 3_072
    full = 6 * dense + 54 * moe_layer + 2 * 200_192 * 3_072 + 3_072
    assert abs(full / 1e9 - 398.6) < 0.05
    shapes = jax.eval_shape(cut.init_params, jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == cut.num_parameters
    assert {"ln_attn_post", "ln_mlp_post"} <= set(shapes["lead"][0]) \
        and {"ln_attn_post", "ln_mlp_post"} <= set(shapes["layers"][3])
    serve = json.load(open(os.path.join(
        BENCH, "configs", "trinity-large-preview.json")))["assumed"]["serve"]
    wb = BlockAllocator.window_pool_blocks(
        serve["max_num_blocks"], serve["max_running"], 33)
    assert wb == serve["max_num_blocks"] == 1825 < 64 * 33 + 1
    pools = jax.eval_shape(lambda: cut.init_paged_cache(
        1825, 128, jnp.bfloat16, window_blocks=wb))
    assert pools["k"].shape == (1, 1825, 128, 1024)
    assert pools["wk"].shape == pools["wv"].shape == (4, 1825, 128, 1024)
    other = get_model("trinity", "large-preview-5l-ep8", share=3)
    assert other.moe.expert_offset == 96


# --------------------------------------------------------------------- #
# the chip's share

def _moe_layer(share, n_shares, E=16, k=4, D=32, F=16):
    held = E // n_shares
    cfg = T.TransformerConfig(vocab_size=64, n_layer=1, n_head=2, d_model=D,
                              d_ff=F, norm="rmsnorm", activation="swiglu")
    return MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", expert_activation="swiglu", scoring="sigmoid",
        norm_topk_prob=True, norm_topk_eps=1e-20, routed_scaling_factor=2.448,
        num_experts=held, k=k, expert_d_ff=F,
        router_experts=None if n_shares == 1 else E,
        expert_offset=share * held, shared_expert_d_ff=F))


def test_the_eight_shares_add_up_to_the_uncut_references_layer():
    """The share is tied to the model: at a toy size the routed parts of all
    8 shares (each holding 2 of 16 experts, routing over all 16, the same
    scale and epsilon) plus the shared expert ONCE equal the uncut
    REFERENCE's MoE branch, and each share's own branch is the reference's
    for that share."""
    E, k, D, n_shares = 16, 4, 32, 8
    whole = _moe_layer(0, 1)
    lp = jax.tree.map(lambda a: a[0],
                      whole.init_params(jax.random.key(4))["layers"]["mlp"])
    lp["b_select"] = 0.3 * jax.random.normal(jax.random.key(5), (E,))
    m = jax.random.normal(jax.random.key(6), (2, 19, D))
    w = {"router": lp["gate_w"], "expert_bias": lp["b_select"],
         "e_gate": lp["w_gate"], "e_up": lp["w_up"], "e_down": lp["w_down"],
         "shared_gate": lp["shared"]["w_gate"], "shared_up": lp["shared"]["w_up"],
         "shared_down": lp["shared"]["w_down"]}
    rcfg = dict(n_experts=E, experts_per_token=k, route_norm=True,
                route_scale=2.448)
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe({**rcfg, "experts_held": E, "expert_offset": 0}, w, m)
        shared_only = ref.moe({**rcfg, "experts_held": 0, "expert_offset": 0},
                              w, m)
    total = 0.0
    for share in range(n_shares):
        held = slice(share * 2, share * 2 + 2)
        lps = {**lp, **{k_: lp[k_][held] for k_ in ("w_gate", "w_up", "w_down")}}
        out, _, n, owed = _moe_layer(share, n_shares)._nodrop_mlp(lps, m, None)
        assert int(owed) == int(n.sum())          # nothing dropped
        with jax.default_matmul_precision("highest"):
            want = ref.moe({**rcfg, "experts_held": 2, "expert_offset": share * 2},
                           {**w, **{k_: w[k_][held]
                                    for k_ in ("e_gate", "e_up", "e_down")}}, m)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)
        total = total + out
    np.testing.assert_allclose(
        np.asarray(total - (n_shares - 1) * shared_only), np.asarray(uncut),
        atol=5e-6)


# --------------------------------------------------------------------- #
# what only the paged path builds

def test_the_training_forward_refuses_a_sandwich_norm(toy):
    model, params = toy[:2]
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="sandwich"):
        model.forward(params, tokens)
    with pytest.raises(NotImplementedError, match="sandwich"):
        model.forward_cached(params, tokens, None, jnp.int32(0))
    # a plain stack with the norm and no layer pattern: the same message
    plain = CausalLM(T.TransformerConfig(
        vocab_size=64, n_layer=2, n_head=2, d_model=32, norm="rmsnorm",
        norm_position="sandwich"))
    p = plain.init_params(jax.random.key(0))
    assert "ln_attn_post" in p["layers"] and "ln_mlp_post" in p["layers"]
    with pytest.raises(NotImplementedError, match="ln_attn_post / ln_mlp_post"):
        plain.forward(p, tokens)
    with pytest.raises(ValueError, match="pre|post|sandwich"):
        CausalLM(T.TransformerConfig(
            vocab_size=64, n_layer=2, n_head=2, d_model=32,
            norm_position="both")).init_params(jax.random.key(0))


# --------------------------------------------------------------------- #
# through init_inference and the paged engine

def engine_of(toy, telemetry=None, **serving):
    cfg = {"block_size": BS, "max_running": 3}
    cfg.update(serving)
    kw = {"dtype": "fp32", "serving": cfg}
    if telemetry is not None:
        kw["telemetry"] = telemetry
    return deepspeed_tpu.init_inference(toy[0], params=toy[1], **kw)


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=n).astype(np.int32) for n in lens]


def alone(toy, prompts, max_new):
    engine = engine_of(toy)
    return [np.asarray(engine.generate_batch([p], max_new_tokens=max_new)[0])
            for p in prompts]


def drive(serving, limit=3000):
    n = 0
    while serving.step():
        n += 1
        assert n < limit, "serving loop did not converge"


LENS = (5, 300, 70, 520, 17, 260, 40, 129)


def test_short_and_long_requests_in_one_queue(toy):
    """Eight requests over three rows, prompts from a few tokens to twice
    the window: each request's tokens are those it gets alone and the
    reference's picks; the rows hold fewer window blocks than a ring each
    (``decode_window_blocks_held`` under ``decode_window_ring_blocks``), and
    everything comes back."""
    get_registry().reset()
    prompts = prompts_of(LENS)
    engine = engine_of(toy, telemetry={"enabled": True})
    outs = engine.generate_batch(prompts, max_new_tokens=20)
    for o, w in zip(outs, alone(toy, prompts, 20)):
        np.testing.assert_array_equal(np.asarray(o), w)
    weights = correctness.Weights(toy[1], toy[3])
    for p, o in zip(prompts, outs):
        verdict = correctness.check_served(toy[2], weights, p,
                                           list(np.asarray(o)[len(p):]))
        assert verdict["worst_gap_bf16_steps"] <= SERVED_STEPS, verdict
    snap = engine.telemetry_snapshot()
    counters = snap["counters"]
    held = counters["serving/decode_window_blocks_held"]
    ring = counters["serving/decode_window_ring_blocks"]
    assert 0 < held < ring and ring % R == 0
    # the kernel copies no more blocks than the rows hold
    assert counters["serving/decode_live_window_kv_blocks"] <= held
    assert snap["gauges"]["serving/window_blocks_used"] == 0
    assert engine._last_serve_stats["preemptions"] == 0


def test_recompute_preemption_gives_the_undisturbed_tokens(toy):
    """A full pool too small for three rows' growth: a victim gives its
    window blocks back with its blocks, is re-queued and prefilled again
    from prompt + generated."""
    prompts = prompts_of((290, 270, 300, 20), seed=2)
    engine = engine_of(toy, max_num_blocks=60)
    outs = engine.generate_batch(prompts, max_new_tokens=60)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, alone(toy, prompts, 60)):
        np.testing.assert_array_equal(np.asarray(o), w)


def test_a_cancel_gives_the_window_blocks_back(toy):
    engine = engine_of(toy, max_running=2)
    prompts = prompts_of((280, 40, 300), seed=4)
    want = alone(toy, prompts, 10)
    serving = AsyncServingEngine(engine, max_new_tokens=10, start=False)
    hs = [serving.add_request(p) for p in prompts]
    for _ in range(4):
        assert serving.step()
    sched = serving._session.sched
    alloc = sched.allocator
    assert sorted(len(r.window_blocks) for r in sched.running) == [3, R]
    assert alloc.window_used == 3 + R and alloc.slots_held == 0
    hs[0].cancel()
    drive(serving)
    assert alloc.window_used == 0 and not alloc.leak_report()
    serving.shutdown(drain=True)
    for h, w in zip(hs[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(h.result(1)), w)


@pytest.mark.parametrize("serving,match", [
    (dict(prefix_caching="on"), "snapshots at block boundaries"),
    (dict(speculative={"mode": "ngram", "k": 2}), "cannot be rewound"),
    (dict(kv_host={"enabled": True}), "ring"),
    (dict(prefill_chunk_tokens=128), "first queries still read"),
])
def test_what_cannot_hold_beside_a_window_is_refused(toy, serving, match):
    with pytest.raises(ValueError, match=match):
        engine_of(toy, **serving).generate_batch(prompts_of((5,)),
                                                 max_new_tokens=2)
