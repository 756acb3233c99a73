"""SDAR-MoE, a model that GENERATES BY DIFFUSION OVER BLOCKS, through the
paged serving path against its plain reference
(``perfbench/reference/sdar_decoder.py``), at the toy preset on the CPU with
seeded float32 weights.

What is held here and by nothing on the chip's served check: logits (not
only tokens) of every deciding pass, every unmasking rule and step count,
every prompt remainder, a request cut inside a block, chunked prefill, a
recompute inside an open block, a cancel, a restart, rows out of phase, the
share of the experts, a token equal to the mask id, a block's commit as a
rider entry of the pass that opens the next block (against the two passes
it stands for, and served), and six controls that MUST fail the tolerance. The toy is drawn in a regime where that is a test:
matrices at 0.3 and the embedding at 1.0 give ten distinct tokens in ten
(at the program's default 0.02 a toy of d 64 answers the same three tokens
whatever it is asked), so a wrong mask or a lost commit moves the logits by
1e-2 and more.

``LOGIT_TOL``: program and reference are both float32 on the CPU and differ
by summation order only: 3e-6 is the largest seen over the cases below at
logits of ~1; 2e-5 leaves six times that and is a five-hundredth of what the
mildest control moves.
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
from deepspeed_tpu.inference import blockgen
from deepspeed_tpu.inference.engine import _ACTION_KINDS, _ServeSession
from deepspeed_tpu.inference.serve import AsyncServingEngine
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.monitor.metrics import get_registry

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "perfbench")
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
import correctness  # noqa: E402
from paged_program_digests import program_digests  # noqa: E402
from reference import sdar_decoder as ref  # noqa: E402
from weights import make_params  # noqa: E402

TOY = "rehearsal-sdar-tiny"
REGIME = dict(init_std=0.3, embed_init_std=1.0)
LOGIT_TOL = 2e-5
BS = 16
B = 4
RULES = T.UNMASK_RULES
#: a threshold the toy's confidences (0.02-0.3) pass now and then
FIRING = 0.05


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def load_toy(rule="sequential", steps=4, threshold=0.9, **over):
    """(model, float32 params, the reference's cfg, the name map): the toy
    configuration under one generation schedule. The weights do not depend
    on the schedule."""
    with open(os.path.join(BENCH, "configs", TOY + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(TOY)
    model = get_model(**config["preset"], rule=rule, steps=steps,
                      threshold=threshold, **REGIME, **over)
    params = make_params(model, 3300000033, jnp.float32, jax.devices()[:1])
    cfg = correctness.reference_config(config, name_map)
    cfg.update(rule=rule, steps=steps, threshold=threshold,
               head_dim=model.config.head_dim)
    return model, params, cfg, name_map


def engine_of(toy, telemetry=None, **serving):
    cfg = {"block_size": BS, "max_running": 3}
    cfg.update(serving)
    kw = {"dtype": "fp32", "serving": cfg}
    if telemetry is not None:
        kw["telemetry"] = telemetry
    return deepspeed_tpu.init_inference(toy[0], params=toy[1], **kw)


def prompts_of(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def reference_tokens(toy, prompt, max_new, record=None):
    _, params, cfg, name_map = toy
    return ref.generate(cfg, ref.Weights(params, name_map), prompt, max_new,
                        record=record)


@pytest.fixture(scope="module")
def servers():
    """Always-on engines by schedule, each made once: ``serve(rule, steps,
    threshold)`` -> (toy, AsyncServingEngine)."""
    made = {}

    def serve(rule="sequential", steps=4, threshold=0.9):
        key = (rule, steps, threshold)
        if key not in made:
            toy = load_toy(rule, steps, threshold)
            made[key] = (toy, AsyncServingEngine(engine_of(toy),
                                                 max_new_tokens=64))
        return made[key]

    yield serve
    for _, serving in made.values():
        serving.shutdown(drain=False, timeout=60)


def served(serving, prompt, max_new):
    h = serving.add_request(prompt, max_new_tokens=max_new)
    return [t for burst in h.stream(timeout=300) for t in burst]


# --------------------------------------------------------------------- #
# (1) prefill, then block generation, against the reference's own loop

SCHEDULES = [(rule, steps, 0.9) for rule in RULES for steps in (4, 2, 1)] \
    + [("low_confidence_dynamic", 4, FIRING)]


@pytest.mark.parametrize("n_prompt", [8, 9, 10, 11])
@pytest.mark.parametrize("rule,steps,threshold", SCHEDULES)
def test_served_tokens_are_the_references(servers, rule, steps, threshold,
                                          n_prompt):
    """Every rule, 4 / 2 / 1 steps a block, every prompt remainder (0..3
    mod 4), nine tokens (the last block is cut): the always-on engine gives
    the tokens of the reference's loop."""
    toy, serving = servers(rule, steps, threshold)
    prompt = prompts_of([n_prompt], seed=n_prompt)[0]
    want = reference_tokens(toy, prompt, 9)
    assert served(serving, prompt, 9) == want


def test_the_firing_threshold_fires(servers):
    """The dynamic rule's own branch is exercised: with the low threshold
    some pass decides more than its ``transfers``."""
    toy, _ = servers("low_confidence_dynamic", 4, FIRING)
    rec = []
    reference_tokens(toy, prompts_of([8], seed=8)[0], 12, record=rec)
    # one position a pass would take 12 passes
    assert len(rec) < 12


def replay(toy, prompt, rec, new, *, commit=True, dtype=jnp.float32,
           keep_denoise_kv=False, fed_mask=None, bs=BS, n_blocks=8):
    """The program's logits of every denoise pass the reference recorded
    (``rec``: (block start, decided before, logits)), driven as the serving
    session drives it: the prompt's whole blocks prefilled, each block's
    passes over the same pool slots, a commit pass of the final tokens when
    a block is whole. Returns [(program logits [B, V], reference logits)]
    at the passes' masked positions. The planted faults: ``commit`` False
    skips the commit passes; ``keep_denoise_kv`` runs the commit pass with
    the LAST denoise pass's tokens (its mask ids) in place of the final
    ones; ``fed_mask`` is the id an undecided position is fed as. ``bs``,
    ``n_blocks``: the pool (the chip tier takes blocks of 128)."""
    model, params, cfg, _ = toy
    gen = model.config.generation
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    pools = model.init_paged_cache(n_blocks, bs, dtype=dtype)
    seq = np.concatenate([prompt, np.asarray(new, np.int32)])
    table = np.arange(1, n_blocks, dtype=np.int32)
    n0 = prompt.size // B * B
    if n0:
        Tb = -(-n0 // 32) * 32
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :n0] = prompt[:n0]
        at = np.arange(Tb)
        slots = np.where(at < n0, table[np.minimum(at // bs, table.size - 1)]
                         * bs + at % bs, at % bs)
        _, pools = model.forward_paged_prefill(
            params, jnp.asarray(toks), pools, jnp.asarray(slots, jnp.int32),
            jnp.int32(n0 - 1))
    bt = jnp.asarray(table[None, :])

    def block_pass(tokens, start, pools):
        logits, pools, _ = model.forward_paged_block(
            params, jnp.asarray(tokens, jnp.int32)[None], pools, bt,
            jnp.asarray([start], jnp.int32))
        return np.asarray(logits[0], np.float32), pools

    out = []
    for i, (start, dec, want) in enumerate(rec):
        final = np.full((B,), gen.mask_id, np.int64)
        inside = seq[start:start + B]
        final[:inside.size] = inside
        fed = np.where(dec, final,
                       gen.mask_id if fed_mask is None else fed_mask)
        got, pools = block_pass(fed, start, pools)
        out.append((got[~dec], np.asarray(want)[~dec]))
        last = i + 1 == len(rec) or rec[i + 1][0] != start
        if last and commit and i + 1 < len(rec):
            _, pools = block_pass(fed if keep_denoise_kv else final, start,
                                  pools)
    return out


def worst(pairs):
    return max(float(np.abs(g - w).max()) for g, w in pairs)


@pytest.fixture(scope="module")
def toy():
    return load_toy()


@pytest.fixture(scope="module")
def recorded(toy):
    """The reference's passes for four prompts (every remainder), each to
    the end of the block at position 20 (the replay needs a block's final
    tokens whole)."""
    out = {}
    for n in (8, 9, 10, 11):
        prompt = prompts_of([n], seed=100 + n)[0]
        rec = []
        new = reference_tokens(toy, prompt, 20 - n, record=rec)
        out[n] = (prompt, rec, new)
    return out


@pytest.mark.parametrize("n_prompt", [8, 9, 10, 11])
def test_pass_logits_are_the_references(toy, recorded, n_prompt):
    """Not only tokens: every deciding pass's logits at its masked
    positions, through prefill, denoise passes and commit passes."""
    pairs = replay(toy, *recorded[n_prompt])
    assert len(pairs) >= 8
    assert worst(pairs) <= LOGIT_TOL, worst(pairs)


def test_pass_logits_through_the_grouped_expert_kernel(toy, recorded):
    """The same passes with the experts read by
    ``ops/pallas/grouped_expert_mlp.py`` (interpreted) from the layer stack
    in place: a share of the router's experts, a pass's 4 positions the
    call's rows, the prefill's 32-token bucket too."""
    from deepspeed_tpu.ops import dispatch
    flash = (load_toy(attention_backend="flash")[0], *toy[1:])
    dispatch.reset()
    pairs = replay(flash, *recorded[9])
    chosen = dispatch.selected()
    assert chosen["experts=grouped_kernel"] >= len(pairs) + 1
    assert "experts=dense" not in chosen
    assert len(pairs) >= 8
    assert worst(pairs) <= LOGIT_TOL, worst(pairs)


@pytest.mark.parametrize("rule", ["low_confidence_static",
                                  "low_confidence_dynamic"])
def test_pass_logits_under_a_confidence_order(rule):
    """The same under the confidence rules (positions decided out of
    order), two steps a block."""
    toy = load_toy(rule, 2, FIRING)
    prompt = prompts_of([10], seed=3)[0]
    rec = []
    new = reference_tokens(toy, prompt, 10, record=rec)
    assert worst(replay(toy, prompt, rec, new)) <= LOGIT_TOL


# --------------------------------------------------------------------- #
# (6) controls that MUST fail the tolerance

def _flat_qk_norm(cfg, q, k, lp):
    """OLMoE's norm over the whole projection, the head's scale tiled."""
    def rms(x, p):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        g = jnp.tile(p["scale"].astype(jnp.float32), x.shape[-1] // cfg.head_dim)
        return (x32 * jax.lax.rsqrt(var + cfg.norm_eps) * g).astype(x.dtype)
    return rms(q, lp["q_norm"]), rms(k, lp["k_norm"])


CONTROLS = {
    "plain_causal_mask": lambda mp: mp.setattr(
        T.TransformerConfig, "causal_block", property(lambda self: 1)),
    "no_commit_pass": dict(commit=False),
    "denoise_kv_kept": dict(keep_denoise_kv=True),
    "flat_qk_norm": lambda mp: mp.setattr(T, "_qk_norm", _flat_qk_norm),
    "bf16_computation": dict(dtype=jnp.bfloat16),
    "mask_fed_as_id_0": dict(fed_mask=0),
}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_a_planted_fault_fails_the_tolerance(toy, recorded, fault, monkeypatch):
    """Each control moves some deciding pass's logits far beyond
    ``LOGIT_TOL``: the comparison above would refuse it."""
    plant = CONTROLS[fault]
    kw = plant if isinstance(plant, dict) else {}
    if not isinstance(plant, dict):
        plant(monkeypatch)
    prompt, rec, new = recorded[10]
    pairs = replay(toy, prompt, rec, new, **kw)
    assert worst(pairs) > 50 * LOGIT_TOL, (fault, worst(pairs))


# --------------------------------------------------------------------- #
# (2) a request retires inside a block

@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static"])
@pytest.mark.parametrize("max_new", [1, 2, 5])
def test_max_new_cuts_inside_a_block(servers, rule, max_new):
    """Exactly ``max_new`` tokens, the reference's first (the harness's
    warm-up asks for 2)."""
    toy, serving = servers(rule, 4, 0.9)
    prompt = prompts_of([9], seed=40 + max_new)[0]
    got = served(serving, prompt, max_new)
    assert len(got) == max_new
    assert got == reference_tokens(toy, prompt, 8)[:max_new]


def test_a_prompt_shorter_than_a_block_has_no_prefill(toy):
    """Three tokens: no whole generation block, nothing to prefill; the
    request goes straight into its first open block."""
    engine = engine_of(toy)
    prompt = prompts_of([3], seed=5)[0]
    out = np.asarray(engine.generate_batch([prompt], max_new_tokens=6)[0])
    assert list(out[3:]) == reference_tokens(toy, prompt, 6)


# --------------------------------------------------------------------- #
# (3) the scheduler's other paths give each request's own tokens

LENS = (9, 30, 18, 7, 21)


def alone(toy, prompts, max_new):
    engine = engine_of(toy)
    return [np.asarray(engine.generate_batch([p], max_new_tokens=max_new)[0])
            for p in prompts]


@pytest.fixture(scope="module")
def undisturbed(toy):
    prompts = prompts_of(LENS, seed=7)
    return prompts, alone(toy, prompts, 14)


@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static"])
def test_rows_out_of_phase(rule):
    """Five requests over three rows, admitted as rows free up: rows sit at
    different passes of different blocks in one fused step, and each gets
    what it gets alone. Run a pass ahead and at depth zero alike."""
    toy = load_toy(rule, 4)
    prompts = prompts_of(LENS, seed=7)
    want = alone(toy, prompts, 14)
    engine = engine_of(toy)
    outs = engine.generate_batch(prompts, max_new_tokens=14)
    for o, w in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(o), w)
    stats = engine._last_serve_stats
    assert stats["decode_steps_ahead"] >= 0.8 * stats["decode_steps"]


def test_depth_zero_gives_the_same_tokens(toy, undisturbed, monkeypatch):
    monkeypatch.setattr(_ServeSession, "_run_ahead", False)
    prompts, want = undisturbed
    engine = engine_of(toy)
    for o, w in zip(engine.generate_batch(prompts, max_new_tokens=14), want):
        np.testing.assert_array_equal(np.asarray(o), w)
    assert engine._last_serve_stats["decode_steps_ahead"] == 0


def test_the_dynamic_rule_lands_every_pass():
    """The count a pass decides is data: the loop does not run ahead."""
    toy = load_toy("low_confidence_dynamic", 4, FIRING)
    prompts = prompts_of(LENS[:3], seed=7)
    want = alone(toy, prompts, 10)
    engine = engine_of(toy)
    for o, w in zip(engine.generate_batch(prompts, max_new_tokens=10), want):
        np.testing.assert_array_equal(np.asarray(o), w)
    assert engine._last_serve_stats["decode_steps_ahead"] == 0


def test_chunked_prefill_in_whole_blocks(toy, undisturbed):
    """Pieces of 8 tokens (and of 6, rounded down to 4): they start and end
    on multiples of the generation block."""
    prompts, want = undisturbed
    for chunk in (8, 6):
        engine = engine_of(toy, prefill_chunk_tokens=chunk)
        outs = engine.generate_batch(prompts, max_new_tokens=14)
        for o, w in zip(outs, want):
            np.testing.assert_array_equal(np.asarray(o), w)


@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static"])
def test_recompute_preemption_inside_an_open_block(rule):
    """A pool too small for three rows' growth: a victim re-prefills whole
    blocks of prompt + streamed tokens and re-enters its open block with
    what it had decided."""
    toy = load_toy(rule, 4)
    prompts = prompts_of((30, 25, 28, 20), seed=2)
    want = alone(toy, prompts, 40)
    engine = engine_of(toy, max_num_blocks=9)
    outs = engine.generate_batch(prompts, max_new_tokens=40)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(o), w)


def drive(serving, limit=3000):
    n = 0
    while serving.step():
        n += 1
        assert n < limit, "serving loop did not converge"


def step_until_in_flight(serving, limit=300):
    sess = serving._session
    for _ in range(limit):
        assert serving.step()
        if sess._flight is not None and sess._flight.name == "block" \
                and sess.sched.stats["decode_steps_ahead"] > 2:
            return sess._flight
    raise AssertionError("no block step ever ran ahead")


def test_restart_engine_with_a_pass_in_flight(toy, undisturbed):
    prompts, want = undisturbed
    engine = engine_of(toy, max_running=2)
    serving = AsyncServingEngine(engine, max_new_tokens=14, start=False)
    hs = [serving.add_request(p) for p in prompts[:3]]
    step_until_in_flight(serving)
    sess = serving._session
    with engine._mesh_scope():
        sess.restart_engine()
    assert sess._flight is None
    drive(serving)
    serving.shutdown(drain=True)
    for h, w in zip(hs, want):
        np.testing.assert_array_equal(np.asarray(h.result(1)), w)


def test_a_cancel_leaves_the_others_alone(toy, undisturbed):
    prompts, want = undisturbed
    engine = engine_of(toy)
    serving = AsyncServingEngine(engine, max_new_tokens=14, start=False)
    hs = [serving.add_request(p) for p in prompts[:3]]
    step_until_in_flight(serving)
    hs[1].cancel()
    drive(serving)
    serving.shutdown(drain=True)
    assert hs[1].status == "cancelled"
    for i in (0, 2):
        np.testing.assert_array_equal(np.asarray(hs[i].result(1)), want[i])


def test_prefix_cache_hit_of_whole_blocks(toy):
    """A second request with the same 32-token prompt hits both cached
    pool blocks: nothing is left to prefill, and its tokens are the same."""
    get_registry().reset()
    engine = engine_of(toy, telemetry={"enabled": True})
    prompt = prompts_of([32], seed=9)[0]
    first = np.asarray(engine.generate_batch([prompt], max_new_tokens=6)[0])
    again = np.asarray(engine.generate_batch([prompt], max_new_tokens=6)[0])
    np.testing.assert_array_equal(first, again)
    counters = engine.telemetry_snapshot()["counters"]
    assert counters["serving/prefix_cache_hit_tokens"] == 32
    assert list(first[32:]) == reference_tokens(toy, prompt, 6)


# --------------------------------------------------------------------- #
# (5) a token equal to the mask id is a token like any other

def test_the_mask_id_in_a_prompt_is_kept(toy):
    mask = toy[0].config.generation.mask_id
    prompt = prompts_of([10], seed=11)[0]
    prompt[[2, 7, 9]] = mask            # in a whole block and the remainder
    engine = engine_of(toy)
    out = np.asarray(engine.generate_batch([prompt], max_new_tokens=8)[0])
    np.testing.assert_array_equal(out[:10], prompt)
    assert list(out[10:]) == reference_tokens(toy, prompt, 8)


def test_the_mask_id_as_an_argmax_is_kept(toy):
    """The head's column of the mask id made 1.5 times the column of a
    token the model generates with a positive logit: the mask id becomes an
    argmax, is decided, streamed, kept as context and never decided again."""
    model, params, cfg, name_map = toy
    mask = model.config.generation.mask_id
    prompt = prompts_of([9], seed=12)[0]
    rec = []
    new = reference_tokens(toy, prompt, 8, record=rec)
    picked = next(int(t) for t, (_, dec, lg) in zip(new, rec)
                  if lg[int(np.flatnonzero(~dec)[0])].max() > 0)
    head = np.asarray(params["lm_head"]).copy()
    head[:, mask] = 1.5 * head[:, picked]
    forged = (model, {**params, "lm_head": jnp.asarray(head)}, cfg, name_map)
    want = reference_tokens(forged, prompt, 8)
    assert mask in want
    engine = engine_of(forged)
    out = np.asarray(engine.generate_batch([prompt], max_new_tokens=8)[0])
    assert list(out[9:]) == want


# --------------------------------------------------------------------- #
# (4) the shares add up

def _moe_layer(share, n_shares, E=8, k=2, D=32, F=16):
    held = E // n_shares
    cfg = T.TransformerConfig(vocab_size=64, n_layer=1, n_head=2, d_model=D,
                              d_ff=F, norm="rmsnorm", activation="swiglu")
    return MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", expert_activation="swiglu", scoring="softmax",
        norm_topk_prob=True, num_experts=held, k=k, expert_d_ff=F,
        router_experts=None if n_shares == 1 else E,
        expert_offset=share * held))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """At a toy size the expert parts of all 8 shares (each holding 1 of 8
    experts, routing over all 8 with normalised top-2 weights) sum to the
    uncut MoE layer, and to the uncut reference's."""
    E, k, D, n_shares = 8, 2, 32, 8
    whole = _moe_layer(0, 1)
    lp = jax.tree.map(lambda a: a[0],
                      whole.init_params(jax.random.key(4))["layers"]["mlp"])
    x = jax.random.normal(jax.random.key(6), (2, 19, D))
    full, _, n_full, owed_full = whole._nodrop_mlp(lp, x, None)
    assert int(owed_full) == 38 * k == int(n_full.sum())
    total, counts = 0.0, []
    for share in range(n_shares):
        lps = {**lp, **{k_: lp[k_][share:share + 1]
                        for k_ in ("w_gate", "w_up", "w_down")}}
        out, _, n, owed = _moe_layer(share, n_shares)._nodrop_mlp(lps, x, None)
        assert int(owed) == int(n.sum())          # nothing dropped
        total = total + out
        counts.append(np.asarray(n))
    np.testing.assert_allclose(np.asarray(total), np.asarray(full), atol=2e-6)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(n_full))
    w = {"ln2_g": jnp.ones((D,)), "router": lp["gate_w"],
         "w_gate": lp["w_gate"], "w_up": lp["w_up"], "w_down": lp["w_down"]}
    rcfg = dict(eps=1e-6, n_experts=E, experts_per_token=k, experts_held=E,
                expert_offset=0, norm_topk_prob=True)
    h = x.reshape(-1, D)
    # the reference's moe(h) = h + MoE(RMS(h)); the program's part takes
    # the normed input
    want = ref.moe(rcfg, w, h) - h
    got, _, _, _ = whole._nodrop_mlp(lp, ref._rms(h, w["ln2_g"], 1e-6)[None], None)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-6)


# --------------------------------------------------------------------- #
# the device side of a pass's decision

def _unmask_numpy(gen, logits, state, n, commit):
    logits = np.asarray(logits, np.float64)
    x0 = logits.argmax(-1)
    z = logits - logits.max(-1, keepdims=True)
    conf = 1.0 / np.exp(z).sum(-1)
    out = np.array(state)
    for w in range(state.shape[0]):
        if commit[w]:
            out[w] = -1
            continue
        masked = np.flatnonzero(state[w] < 0)
        k = min(int(n[w]), masked.size)
        if gen.rule == "sequential":
            take = masked[:k]
        else:
            take = masked[np.argsort(-conf[w, masked], kind="stable")[:k]]
            passing = masked[conf[w, masked] > gen.threshold]
            if gen.data_dependent and passing.size >= k:
                take = passing
        out[w, take] = x0[w, take]
    return out


@pytest.mark.parametrize("rule", RULES)
def test_unmask_is_the_rule(rule):
    gen = T.BlockGeneration(block=4, steps=2, rule=rule, threshold=0.3,
                            mask_id=63)
    rng = np.random.default_rng(3)
    W, V = 12, 64
    logits = 3.0 * rng.standard_normal((W, 4, V)).astype(np.float32)
    state = np.where(rng.random((W, 4)) < 0.4,
                     rng.integers(0, V, (W, 4)), -1).astype(np.int32)
    n = rng.integers(0, 4, (W,)).astype(np.int32)
    commit = rng.random(W) < 0.2
    got = jax.jit(lambda *a: blockgen.unmask(gen, *a))(
        jnp.asarray(logits), jnp.asarray(state), jnp.asarray(n),
        jnp.asarray(commit))
    np.testing.assert_array_equal(
        np.asarray(got), _unmask_numpy(gen, logits, state, n, commit))


def test_a_generation_record_is_checked():
    with pytest.raises(ValueError):
        T.BlockGeneration(rule="random")
    with pytest.raises(ValueError):
        T.BlockGeneration(block=4, steps=5)
    with pytest.raises(ValueError):
        T.BlockGeneration(block=3, steps=3)
    g = T.BlockGeneration(block=8, steps=3)
    assert [g.transfers(i) for i in range(3)] == [3, 3, 2]


# --------------------------------------------------------------------- #
# what the normal path refuses, counts and leaves alone

def test_what_cannot_hold_for_block_generation_is_refused(toy):
    model, params, _, _ = toy
    with pytest.raises(ValueError, match="diffusion over blocks"):
        deepspeed_tpu.init_inference(
            model, params=params, dtype="fp32",
            serving={"block_size": BS, "max_running": 2,
                     "speculative": {"mode": "ngram", "k": 2}}
        ).generate_batch([np.arange(5)], max_new_tokens=2)
    engine = engine_of(toy)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        engine.generate(np.arange(5)[None], max_new_tokens=2)
    with pytest.raises(ValueError, match="whole generation blocks"):
        engine_of(toy, block_size=6).generate_batch([np.arange(5)],
                                                    max_new_tokens=2)


def test_the_kinds_table_has_one_record_more():
    assert sorted(_ACTION_KINDS) == ["block", "decode", "prefill",
                                     "prefill_chunk", "verify", "wait"]
    kind = _ACTION_KINDS["block"]
    assert kind.fed and kind.ahead and kind.inputs is not None


def test_the_block_program_carries_its_name(toy):
    """Last of the engine's programs, under the name a device trace and
    the compile cache know it by."""
    engine = engine_of(toy, telemetry={"enabled": True})
    with engine._mesh_scope():
        jits = engine._ensure_paged_jits()
    assert len(jits) == 9 and jits[-1].inner.__name__ == "paged_block"
    assert jits[-1].__name__ == "watched[inference.paged_block]"


def test_block_counters_and_what_they_give(toy):
    """Passes, commits and decided tokens: 4 denoise passes a block of 4,
    the block's commit a rider of the next block's first, give 1.0 token a
    row pass and no commit pass (a cut last block and a prompt's remainder
    move the first a little)."""
    get_registry().reset()
    engine = engine_of(toy, telemetry={"enabled": True})
    prompts = prompts_of((8, 12, 16), seed=1)
    engine.generate_batch(prompts, max_new_tokens=24)
    c = engine.telemetry_snapshot()["counters"]
    assert c["serving/block_decided_tokens"] == 3 * 24
    assert c["serving/generated_tokens"] == 3 * 24
    # 6 blocks a request: 24 denoise passes, and 5 commits that ride the
    # next block's first (the last block's is not needed)
    assert c["serving/block_row_passes"] == 3 * 24
    assert c["serving/block_commit_row_passes"] == 0
    assert c["serving/block_commit_rides"] == 3 * 5
    assert c["serving/block_passes"] == c["serving/decode_steps"]
    assert c["serving/moe_layer_steps"] == 2 * c["serving/block_passes"]
    assert c["serving/moe_dropped_assignments"] == 0
    # an entry's pass reads its row's committed tokens and its own block,
    # a rider's too
    entries = c["serving/block_row_passes"] + c["serving/block_commit_rides"]
    assert c["serving/decode_live_kv_tokens"] >= 4 * entries
    # every entry's 4 positions are rows of the experts' work: top-2 of 8
    # routed, 4 held
    assert c["serving/moe_assignments"] <= 2 * 2 * 4 * entries
    assert c["serving/moe_assignments"] >= 2 * 4 * entries // 2


def test_a_model_that_decodes_has_no_block_counter():
    get_registry().reset()
    model = get_model("olmoe", "tiny")
    engine = deepspeed_tpu.init_inference(
        model, params=model.init_params(jax.random.key(0)), dtype="fp32",
        telemetry={"enabled": True},
        serving={"block_size": 8, "max_running": 2})
    engine.generate_batch([np.arange(5)], max_new_tokens=3)
    counters = engine.telemetry_snapshot()["counters"]
    assert not [k for k in counters if k.startswith("serving/block_")]
    assert len(engine._paged_jits) == 8          # no block program is made


# --------------------------------------------------------------------- #
# (8) a block's commit rides the pass that opens the next block

def ride_case(model, rows, W, E, bs, seed=0):
    """The entries of ONE fused pass with riders, and of the TWO passes it
    stands for. ``rows``: (depth, rides) a live row; a row that rides has a
    whole block at ``depth`` and its next block, all undecided, at ``depth
    + 4``; one that does not, an open block at ``depth``. Tables are random
    and distinct. Returns {"first", "second", "fused"}: (tokens, tables,
    depths) each, ``first`` and ``second`` W entries (a commit or denoise
    pass over each row's block as it stands; then the riding rows' next
    blocks), ``fused`` W + E (the main entries, then the riders)."""
    gen = model.config.generation
    Bg, n_max = gen.block, 4
    r = np.random.default_rng(seed)
    ids = iter(r.permutation(np.arange(1, 1 + len(rows) * n_max)))
    table = np.zeros((len(rows), n_max), np.int32)
    for i, (p, _) in enumerate(rows):
        for j in range((p + 2 * Bg - 1) // bs + 1):
            table[i, j] = next(ids)

    def entries(n):
        return (np.full((n, Bg), gen.mask_id, np.int32),
                np.zeros((n, n_max), np.int32), np.zeros((n,), np.int32))

    first, second, fused = entries(W), entries(W), entries(W + E)
    riders = 0
    for i, (p, rides) in enumerate(rows):
        block = r.integers(0, gen.mask_id, Bg).astype(np.int32)
        if not rides:
            block[r.integers(1, Bg):] = gen.mask_id      # still open
        for toks, bt, pos in (first, fused):
            toks[i], bt[i], pos[i] = block, table[i], p
        if rides:
            at = W + riders
            riders += 1
            fused[0][at], fused[1][at], fused[2][at] = block, table[i], p
            for toks, bt, pos in (second, fused):
                toks[i], bt[i], pos[i] = gen.mask_id, table[i], p + Bg
    return {"first": first, "second": second, "fused": fused}


def ride_passes(model, params, pools, case, W):
    """(logits, pools, counts) of the fused pass (the head over the W main
    entries) and of the two passes one after the other."""
    step = jax.jit(model.forward_paged_block, static_argnames=("n_logits",))

    def run(po, name, **kw):
        toks, bt, pos = map(jnp.asarray, case[name])
        return step(params, toks, po, bt, pos, **kw)

    fused = run(pools, "fused", n_logits=W)
    first = run(pools, "first")
    second = run(first[1], "second")
    return fused, first, second


#: (depth, rides): an A/B pair inside one pool block and one across two
#: (the whole block ends a pool block of 128), a row that does not ride
RIDE_ROWS = [(8, True), (124, True), (40, False), (252, True)]


@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_a_rider_and_the_next_block_are_a_commit_pass_then_a_denoise_pass(
        backend):
    """One launch, two entries of the same row over the same table (the
    whole block at ``p``, the next block at ``p + 4``), through gather +
    einsum and through the interpreted paged kernel: the next block's
    logits and every pool slot are those of a commit pass followed by a
    denoise pass, a row without a rider keeps its own pass's logits, the
    experts count both entries' positions; and with the rider left out
    the next block's logits move."""
    from deepspeed_tpu.ops import dispatch
    bs, W, E = 128, 5, 4                 # an idle main entry, an idle rider
    model = get_model("sdar", "tiny", head_size=64, attention_backend=backend,
                      **REGIME)
    params = model.init_params(jax.random.key(1))
    r = np.random.default_rng(7)
    pools = jax.tree.map(
        lambda a: jnp.asarray(r.standard_normal(a.shape), a.dtype),
        model.init_paged_cache(17, bs, dtype=jnp.float32))
    case = ride_case(model, RIDE_ROWS, W, E, bs)
    dispatch.reset()
    fused, first, second = ride_passes(model, params, pools, case, W)
    form = "paged_kernel" if backend == "flash" else "gather_einsum"
    # traced once a width: the fused pass, and the two passes' W entries
    assert dispatch.selected()[f"paged_block={form}"] == 2
    assert fused[0].shape == (W, 4, 512)
    rides = np.array([ride for _, ride in RIDE_ROWS])
    live = len(RIDE_ROWS)
    want = np.where(rides[:, None, None], np.asarray(second[0])[:live],
                    np.asarray(first[0])[:live])
    np.testing.assert_allclose(np.asarray(fused[0])[:live], want,
                               atol=LOGIT_TOL)
    for a, b in zip(jax.tree.leaves(fused[1]), jax.tree.leaves(second[1])):
        np.testing.assert_allclose(np.asarray(a[:, 1:]), np.asarray(b[:, 1:]),
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(fused[2]),
                                  np.asarray(first[2]) + np.asarray(second[2]))
    # the control: no rider, so the next block reads its row's stale block
    toks, bt, pos = case["fused"]
    bare = {"fused": (toks, np.concatenate([bt[:W], 0 * bt[W:]]), pos),
            "first": case["first"], "second": case["second"]}
    lost = ride_passes(model, params, pools, bare, W)[0][0]
    assert float(np.abs(np.asarray(lost)[:live][rides]
                        - want[rides]).max()) > 50 * LOGIT_TOL


def ride_counters(engine):
    c = engine.telemetry_snapshot()["counters"]
    return (c["serving/block_row_passes"],
            c["serving/block_commit_row_passes"],
            c["serving/block_commit_rides"], c["serving/block_decided_tokens"])


@pytest.mark.parametrize("rule,threshold", [
    ("sequential", 0.9), ("low_confidence_static", 0.9),
    ("low_confidence_dynamic", FIRING)])
def test_served_with_riders_the_tokens_are_the_references(rule, threshold):
    """Three rows out of phase, answers of 13 tokens (not a multiple of 4:
    the last block is cut and ends without a commit), planned a pass ahead
    (the rider fed from the pass in flight) and landed every pass (the
    dynamic rule: from the host): every commit rides, every row pass but a
    prompt's remainder's decides, and the tokens are the reference's."""
    get_registry().reset()
    toy = load_toy(rule, 4, threshold)
    engine = engine_of(toy, telemetry={"enabled": True})
    prompts = prompts_of((9, 30, 18, 7), seed=21)
    outs = engine.generate_batch(prompts, max_new_tokens=13)
    for p, o in zip(prompts, outs):
        assert list(np.asarray(o)[p.size:]) == reference_tokens(toy, p, 13)
    passes, alone_, rides, decided = ride_counters(engine)
    # blocks a request opens less one: the last ends with the request
    assert alone_ == 0 and rides == sum(
        -(-(p.size + 13) // 4) - p.size // 4 - 1 for p in prompts)
    stats = engine._last_serve_stats
    if rule == "sequential":
        # one position a pass, and none past the request's last token
        assert passes == decided == 4 * 13
    else:
        # a confidence order decides the cut block whole
        assert decided >= 4 * 13
    if rule != "low_confidence_dynamic":
        assert stats["decode_steps_ahead"] >= 0.8 * stats["decode_steps"]
    else:
        assert stats["decode_steps_ahead"] == 0


@pytest.mark.parametrize("rule,threshold", [
    ("sequential", 0.9), ("low_confidence_dynamic", FIRING)])
def test_more_whole_blocks_than_rider_slots(rule, threshold, monkeypatch):
    """One rider slot under three rows in phase (the derived width is 8 at
    any toy's rows, so the rule is planted): the first whole row in
    admission order rides, the others commit alone exactly as before, a
    step later and a phase apart; each gets the tokens it gets alone, and
    rides and lone commits are the blocks committed."""
    from deepspeed_tpu.inference import scheduler as sched_mod
    toy = load_toy(rule, 4, threshold)
    prompts = prompts_of((8, 8, 8), seed=23)
    want = alone(toy, prompts, 18)
    monkeypatch.setattr(sched_mod, "ride_slots", lambda gen, rows: 1)
    get_registry().reset()
    engine = engine_of(toy, telemetry={"enabled": True})
    outs = engine.generate_batch(prompts, max_new_tokens=18)
    for o, w in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(o), w)
    passes, alone_, rides, decided = ride_counters(engine)
    assert alone_ > 0 and rides > 0
    assert alone_ + rides == 3 * 4        # 5 blocks a request, the last cut
    if rule == "sequential":
        # a lone commit is a row pass that decides nothing
        assert decided == 3 * 18 and passes == decided + alone_


def test_a_preemption_with_a_block_open_under_riders():
    """A pool too small for three rows' growth, a ride needing its block a
    step sooner than a lone commit did: the victim re-prefills whole blocks
    and re-enters its open block, riders before and after it, and the
    counters say every committed block once."""
    get_registry().reset()
    toy = load_toy("sequential", 4)
    prompts = prompts_of((30, 25, 28, 20), seed=2)
    want = alone(toy, prompts, 40)
    engine = engine_of(toy, max_num_blocks=9, telemetry={"enabled": True})
    outs = engine.generate_batch(prompts, max_new_tokens=40)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(o), w)
    passes, alone_, rides, decided = ride_counters(engine)
    assert alone_ == 0 and rides > 0
    # a victim's decided tokens are decided once: what it streamed comes
    # back as prefix, the rest of its open block as it landed
    assert decided == 4 * 40


def test_an_eos_inside_a_block_under_riders(toy):
    """A request's EOS lands while its next step (a ride among them) is
    queued: that step's row is dropped, the answer ends with the EOS, and
    the rows beside it get the reference's tokens."""
    prompts = prompts_of((9, 30, 18), seed=21)
    refs = [reference_tokens(toy, p, 17) for p in prompts]
    for cut in (3, 6, 8):             # inside a block, and a block's last
        eos = refs[0][cut]
        outs = engine_of(toy).generate_batch(prompts, max_new_tokens=17,
                                             eos_token_id=int(eos))
        for p, o, r in zip(prompts, outs, refs):
            want = r[:r.index(eos) + 1] if eos in r else r
            assert list(np.asarray(o)[p.size:]) == want, cut


def test_the_rider_width_comes_from_the_rows_and_the_record():
    """``ceil(rows / steps)`` up to whole tiles of 8: 16 beside the cell's
    64 rows at 4 steps; the session's program is that wide and no wider."""
    g = T.BlockGeneration(block=4, steps=4)
    assert [blockgen.ride_slots(g, w) for w in (1, 3, 32, 33, 64, 256)] \
        == [8, 8, 8, 16, 16, 64]
    assert blockgen.ride_slots(T.BlockGeneration(block=4, steps=1), 64) == 64
    assert blockgen.ride_slots(T.BlockGeneration(block=8, steps=3), 64) == 24


# (7) an autoregressive model's programs trace to the same jaxprs as before
#: ``tests/unit/paged_program_digests.py`` run on the commit before PR 33;
#: the three ``flash.*.grad`` values re-recorded by PR 46, whose backward
#: kernels changed on purpose (the ``.fwd`` values are PR 33's still); the
#: recurrent presets' (``solar_open2.*``: KDA layers over an MoE,
#: ``granite_hybrid.*``: Mamba-2 layers; ``.kernels``: with the Pallas state
#: updates, interpreted) taken on the commit before PR 47 moved the mixers
#: to ``models/state_mixers.py``; the fifteen program values of ``opt.*``,
#: ``solar_open2.*`` and ``granite_hybrid.*`` re-recorded by PR 57, which
#: holds a projection's flat output ahead of its split into heads (``_flat``:
#: one ``optimization_barrier`` a product; with ``_flat`` the identity the
#: values of PR 33 and PR 47 came back, all fifteen); ``olmoe.*``, whose
#: norm over the whole vector takes no hold, are PR 33's still
BEFORE = {
    "opt.decode": "a0cebe767aea0e42",
    "opt.prefill": "b97540bf3b21fb89",
    "opt.prefill_chunk": "612661a8aea4cc0a",
    "olmoe.decode": "91c915b574c1bd7c",
    "olmoe.prefill": "be28345862480dd1",
    "olmoe.prefill_chunk": "90687ed94108693c",
    "solar_open2.decode": "01d1ae6357775fc5",
    "solar_open2.prefill": "866988fc177086c9",
    "solar_open2.prefill_chunk": "655c7c853e41eb5a",
    "granite_hybrid.decode": "819cc511dbf79b22",
    "granite_hybrid.prefill": "156e7c255a13384f",
    "granite_hybrid.prefill_chunk": "27b4989a1e9ec633",
    "solar_open2.kernels.decode": "dfa1eed70437d153",
    "solar_open2.kernels.prefill": "03767a55677863e3",
    "solar_open2.kernels.prefill_chunk": "3634f52605e6621f",
    "granite_hybrid.kernels.decode": "857921f7790020b2",
    "granite_hybrid.kernels.prefill": "57aed39d1187405a",
    "granite_hybrid.kernels.prefill_chunk": "27b4989a1e9ec633",
    "flash.gqa.fwd": "dc915c8588b95969",
    "flash.gqa.grad": "0a4301cd137d40bd",
    "flash.packed.fwd": "5b203011d0a6297f",
    "flash.packed.grad": "f70d37dc827f92e9",
    "flash.padded.fwd": "172e60c7c196456f",
    "flash.padded.grad": "6426b4067fb4feb8",
}


@pytest.fixture(scope="module")
def digests_now():
    return program_digests()


@pytest.mark.parametrize("program", sorted(BEFORE))
def test_an_autoregressive_models_programs_are_unchanged(digests_now, program):
    """The kinds table, the masked-softmax core, the q/k norm and the flash
    kernel are shared with block generation; a model without a generation
    record traces to what it traced to before."""
    assert digests_now[program] == BEFORE[program]


def test_the_toy_is_the_cells_configuration_in_small():
    """The rehearsal configuration and the cell's name the same preset
    family, rule and block; the cell's is at the published widths."""
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        cell = json.load(f)
    model = get_model(**cell["preset"])
    cfg, gen = model.config, model.config.generation
    assert (cfg.n_layer, cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.d_model) \
        == (48, 32, 4, 128, 2048)
    assert (model.moe.num_experts, model.router_width, model.moe.k,
            model.expert_ff) == (16, 128, 8, 768)
    assert dataclasses.asdict(gen) == dict(
        block=cell["block_length"], steps=cell["denoising_steps"],
        rule=cell["remasking_strategy"],
        threshold=cell["confidence_threshold"], mask_id=cell["mask_token_id"])
    assert cfg.vocab_size == cell["vocab_size"] == gen.mask_id + 1
    assert model.num_parameters == 48 * 94_638_336 + 77_791_232 + 2_048
