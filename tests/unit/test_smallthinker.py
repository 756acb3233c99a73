"""SmallThinker's stack on the normal path: full attention layers without
positions and rope layers with a WINDOW in one stack, their KV kept by kind
(the shared pool for the full layers, for the window layers a ring of
blocks of a second pool, handed out as a request grows and named by a table
from the host), a router that reads the attention's input, ReLU
gated experts. The ``smallthinker`` ``tiny`` preset (window 256) with seeded
weights, float32 on the CPU, against the plain reference
(``perfbench/reference/smallthinker_decoder.py``): prefill, then decode
through the paged cache, logits, for contexts under, at and well past the
window; controls that must fail; the ring under admission, preemption by
recompute and a cancel; what cannot hold beside a ring (a chunked prefill
among it) refused from ``cache_spec``; ``_bucket``. The kernels are
``test_smallthinker_kernels.py``'s."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serve import AsyncServingEngine
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.monitor.metrics import get_registry

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "perfbench"))
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from reference import smallthinker_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-smallthinker-tiny"
W = 256
#: program and reference are both float32 here: under the preset's init
#: (peaked attention, logits of 2-6) they agree to 2.4e-5 at worst
LOGIT_TOL = 1e-4
#: as test_serving_state.py: a served token is the reference's own pick
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


def paged_logits(model, params, tokens, n_prompt, bs, slot=2, rows=3):
    """Prefill ``tokens[:n_prompt]`` whole, then decode the rest a token a
    step through the paged pools, the request in row 1 and ``slot`` with
    idle rows beside it: the logits at positions n_prompt - 1 .. end."""
    S = len(tokens)
    nb = -(-1024 // bs)
    pools = model.init_paged_cache(nb + 1, bs, dtype=jnp.float32,
                                   state_slots=rows + 1)
    table = np.arange(1, nb + 1, dtype=np.int32)
    Tb = InferenceEngine._bucket(n_prompt, 1024)
    t = np.arange(Tb)
    toks = np.zeros((1, Tb), np.int32)
    toks[0, :n_prompt] = tokens[:n_prompt]
    slots = np.where(t < n_prompt, table[np.minimum(t // bs, nb - 1)] * bs
                     + t % bs, t % bs).astype(np.int32)
    lg, pools = jax.jit(model.forward_paged_prefill)(
        params, toks, pools, slots, np.int32(n_prompt - 1),
        state_slot=np.int32(slot))
    out = [np.asarray(lg[0])]
    decode = jax.jit(model.forward_paged_decode)
    bt = np.zeros((rows, nb), np.int32)
    bt[1] = table
    ss = np.zeros((rows,), np.int32)
    ss[1] = slot
    for p in range(n_prompt, S):
        pos = np.zeros((rows,), np.int32)
        pos[1] = p
        nt = np.zeros((rows, 1), np.int32)
        nt[1, 0] = tokens[p]
        lg, pools, _ = decode(params, nt, pools, bt, pos, state_slots=ss)
        out.append(np.asarray(lg[1]))
    return np.stack(out)


# a prompt that ends under the window; inside, on and after a block border
# (127 | 128 | 129); at the window (255 | 256 | 257: the first position whose
# window has lost position 0); past it on a border of the ring's third block
# (384); well past it, the ring wrapped (700); and at the toy's max_seq
@pytest.mark.parametrize("n_prompt", [60, 127, 128, 129, 255, 256, 257, 384,
                                      700, 1000])
def test_prefill_then_decode_is_the_reference(toy, n_prompt):
    model, params = toy[:2]
    n_new = 12 if n_prompt < 1000 else 24        # 1000: to position 1,023
    tokens = np.random.default_rng(n_prompt).integers(
        0, 512, n_prompt + n_new).astype(np.int32)
    got = paged_logits(model, params, tokens, n_prompt, bs=128)
    want = reference_logits(toy, tokens)[n_prompt - 1:]
    assert np.abs(got - want).max() < LOGIT_TOL, np.abs(got - want).max(axis=-1)


@pytest.mark.parametrize("n_prompt", [60, 300])
def test_the_grouped_expert_kernel_is_the_reference_too(toy, n_prompt):
    """The paged programs on their Pallas forms, interpreted: a period of
    four layers, one stack of experts a position of the period read in place
    by ``ops/pallas/grouped_expert_mlp.py`` through the period's offset (the
    decode step's 3 rows; the 64-token bucket too, and since PR 53 the
    384-token one, past one row tile: each expert over its own rows), the
    router fed the mixer's input, the ReLU gate."""
    from deepspeed_tpu.ops import dispatch
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        preset = json.load(f)["preset"]
    model = get_model(**preset, attention_backend="flash")
    tokens = np.random.default_rng(n_prompt).integers(
        0, 512, n_prompt + 12).astype(np.int32)
    dispatch.reset()
    got = paged_logits(model, toy[1], tokens, n_prompt, bs=128)
    chosen = dispatch.selected()
    n_layer = len(model.config.period)
    assert chosen["experts=grouped_kernel"] == n_layer * 2
    assert "experts=dense" not in chosen
    want = reference_logits(toy, tokens)[n_prompt - 1:]
    assert np.abs(got - want).max() < LOGIT_TOL, np.abs(got - want).max(axis=-1)


@pytest.mark.parametrize("bs,n_prompt", [(16, 250), (16, 300), (64, 500)])
def test_a_ring_of_many_small_blocks_too(toy, bs, n_prompt):
    """Block 16: a ring of 17 blocks, a decode that crosses block borders
    and wraps inside the run."""
    model, params = toy[:2]
    tokens = np.random.default_rng(bs + n_prompt).integers(
        0, 512, n_prompt + 40).astype(np.int32)
    got = paged_logits(model, params, tokens, n_prompt, bs=bs)
    want = reference_logits(toy, tokens)[n_prompt - 1:]
    assert np.abs(got - want).max() < LOGIT_TOL


def _control(toy, departure):
    """The logits of the served path and of the reference with ONE thing of
    the family's left out, on a context past the window."""
    model, params, cfg, _ = toy
    n_prompt, tokens = 400, np.random.default_rng(7).integers(
        0, 512, 408).astype(np.int32)
    over = {"window_left_out": {}, "rope_on_the_full_layer": {},
            "router_fed_the_experts_input": dict(moe=dict(router_input="mlp_input")),
            "silu_for_relu": dict(moe=dict(expert_activation="swiglu"))}[departure]
    if departure == "window_left_out":
        cfg = {**cfg, "window_layout": [0, 0, 0, 0]}
    if departure == "rope_on_the_full_layer":
        cfg = {**cfg, "rope_layout": [1, 1, 1, 1]}
    served = get_model("smallthinker", "tiny", **over)
    got = paged_logits(served, params, tokens, n_prompt, bs=128)
    return got, reference_logits(toy, tokens, cfg)[n_prompt - 1:]


@pytest.mark.parametrize("departure", [
    "window_left_out", "rope_on_the_full_layer",
    "router_fed_the_experts_input", "silu_for_relu"])
def test_a_control_that_fails(toy, departure):
    """Each thing the family does differently, taken away on one side: the
    logits then differ by thousands of times the tolerance the sound pair
    is held to (under the preset's init a head's scores are large and a few
    keys hold its mass, so the window's edge and the positions move it)."""
    got, want = _control(toy, departure)
    assert np.abs(got - want).max() > 1000 * LOGIT_TOL


def test_the_preset_says_what_it_is():
    cut = get_model("smallthinker", "21b-a3b-12l")
    cfg = cut.config
    assert cfg.period == ("attention",) + ("window_attention",) * 3
    assert cfg.cache_spec == {"kv": 3, "state": 0, "window": 9, "latent": 0}
    assert cfg.attn_window == 4096 and cfg.ring_blocks(128) == 33
    assert cfg.takes_rope("window_attention") and not cfg.takes_rope("attention")
    assert cut.num_parameters == 5_561_448_960
    full = get_model("smallthinker", "21b-a3b-12l", n_layer=52)
    assert abs(full.num_parameters / 1e9 - 21.51) < 0.005
    pools = jax.eval_shape(lambda: cut.init_paged_cache(
        1441, 128, jnp.bfloat16, state_slots=17))
    assert pools["k"].shape == (3, 1441, 128, 512)
    assert pools["wk"].shape == pools["wv"].shape == (9, 16 * 33 + 1, 128, 512)
    # a stack without window layers builds the pools it built before
    other = get_model("olmoe", "tiny")
    assert set(jax.eval_shape(lambda: other.init_paged_cache(8, 16))) == {"k", "v"}
    assert other.config.cache_spec == {"kv": 2, "state": 0, "window": 0,
                                       "latent": 0}


# --------------------------------------------------------------------- #
# through init_inference and the paged engine

def engine_of(toy, telemetry=None, **serving):
    cfg = {"block_size": 16, "max_running": 3}
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


LENS = (5, 300, 70, 520, 17, 260, 255, 129)


def test_more_requests_than_rows(toy):
    """Eight requests over three rows, prompts from under to twice the
    window: each request's tokens are those it gets alone and the
    reference's picks; a row's ring never holds more than W / bs + 1
    blocks, every slot comes back and nothing leaks."""
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
    counters, R = snap["counters"], W // 16 + 1
    steps = counters["serving/decode_steps"]
    # what a window layer reads a step: min(pos + 1, W) a live row, in at
    # most R block copies a live row (they hold every token read) and none
    # an idle one
    assert 0 < counters["serving/decode_live_window_kv_tokens"] <= 3 * W * steps
    assert counters["serving/decode_live_window_kv_tokens"] / 16 \
        <= counters["serving/decode_live_window_kv_blocks"] <= 3 * R * steps
    # ... a full layer everything: the long rows read more there
    assert counters["serving/decode_live_kv_tokens"] \
        > counters["serving/decode_live_window_kv_tokens"] - 3 * steps
    assert not [k for k in counters if "state" in k]
    pools = engine._paged_workspace[2]
    assert pools["wk"].shape[:2] == (3, 3 * R + 1)
    assert engine._active_session is None


def test_recompute_preemption_gives_the_undisturbed_tokens(toy):
    """A full-layer pool too small for three rows' growth: a victim is
    re-queued and prefilled again from prompt + generated, its ring
    refilled from that prefill's last blocks."""
    get_registry().reset()
    prompts = prompts_of((290, 270, 300, 20), seed=2)
    engine = engine_of(toy, telemetry={"enabled": True}, max_num_blocks=60)
    outs = engine.generate_batch(prompts, max_new_tokens=60)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, alone(toy, prompts, 60)):
        np.testing.assert_array_equal(np.asarray(o), w)


def test_a_cancel_gives_the_ring_back(toy):
    engine = engine_of(toy, max_running=2)
    prompts = prompts_of((280, 40, 300), seed=4)
    want = alone(toy, prompts, 10)
    serving = AsyncServingEngine(engine, max_new_tokens=10, start=False)
    hs = [serving.add_request(p) for p in prompts]
    for _ in range(4):
        assert serving.step()
    sched = serving._session.sched
    alloc = sched.allocator
    # two rows run: the 280-token one holds a whole ring of the window
    # pool, the 40-token one a block for each of its blocks of the full pool
    R = W // 16 + 1
    held = sorted(len(r.window_blocks) for r in sched.running)
    assert held == [3, R] and alloc.window_used == 3 + R
    assert all(len(r.window_blocks) == min(len(r.blocks), R)
               for r in sched.running)
    assert alloc.slots_held == 0          # a window takes no state slot
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
def test_what_cannot_hold_beside_a_ring_is_refused(toy, serving, match):
    with pytest.raises(ValueError, match=match):
        engine_of(toy, **serving).generate_batch(prompts_of((5,)),
                                                 max_new_tokens=2)


def test_auto_resolves_to_no_prefix_cache_and_no_verify_program(toy):
    engine = engine_of(toy)
    session = engine.open_serve_session(max_new=2)
    try:
        assert not session.sched.prefix_caching
        alloc = session.sched.allocator
        # no slot for a window; its pool is a ring a row or a block a block
        # of the full pool, whichever is less
        assert alloc.state_slots == 0 and alloc.ring_blocks == W // 16 + 1
        assert alloc.window_blocks == min(alloc.num_blocks,
                                          3 * (W // 16 + 1) + 1)
        assert session.pools["wk"].shape[1] == alloc.window_blocks
        with pytest.raises(NotImplementedError, match="handoff"):
            session.demote_prompt(prompts_of((40,))[0])
    finally:
        session.close()
    model, params = toy[:2]
    pools = model.init_paged_cache(8, 16, dtype=jnp.float32, state_slots=2)
    z = np.zeros((1, 2), np.int32)
    with pytest.raises(NotImplementedError, match="ring"):
        model.forward_paged_verify(params, z, pools, np.zeros((1, 4), np.int32),
                                   z, np.zeros((1,), np.int32))


@pytest.mark.parametrize("n,want", [
    (1, 128), (128, 128), (129, 256), (1000, 1024), (1792, 1792), (1793, 1920),
    (2048, 2048),                                   # as before, to 2,048
    (2049, 3072), (6145, 7168), (7168, 7168), (7169, 8192), (10240, 10240),
    (10241, 11264), (16000, 16384)])
def test_bucket(n, want):
    assert InferenceEngine._bucket(n, 16384) == want


def test_bucket_is_clamped_to_the_models_max():
    assert InferenceEngine._bucket(2049, 2560) == 2560
    assert InferenceEngine._bucket(300, 256) == 256
