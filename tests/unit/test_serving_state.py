"""Two kinds of state in one manager: a model with recurrent layers (the
``solar_open2`` ``tiny`` preset: a GQA layer and three KDA layers a period;
the ``granite_hybrid`` ``tiny`` preset: a GQA layer among nine Mamba-2
layers a period; batching, preemption and the refusals run on both, the
engine names neither)
through ``init_inference`` and the paged engine, its state slots handed out
and taken back beside the block tables.

What is held: many requests through continuous batching with FEWER slots
than requests, so that slots are reused, rows go idle and a chunked prefill
interleaves with decode, give each request the tokens it gets alone, and
those are the reference's (``perfbench/reference/solar_open2_decoder.py``,
``granite_hybrid_decoder.py``, ``lfm2_moe_decoder.py``: the ``lfm2_moe``
``tiny`` preset, a leading dense conv layer and a period of a GQA layer and
three gated short-conv layers that keep a conv state and no recurrent one);
a recompute-preemption, an engine restart with a step in flight and a
launched-ahead step whose row turned out to be past its EOS (the overshoot)
change nothing; and what cannot hold beside a state yet is refused, each
with its reason. float32 on the CPU, greedy. The conftest
``_no_kv_block_leaks`` fixture applies file-wide; the slots are checked
here."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.engine import _ServeSession
from deepspeed_tpu.inference.serve import AsyncServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.utils import fault_injection as fi

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "perfbench"))
sys.path.insert(0, BENCH)
import correctness  # noqa: E402
from weights import make_params  # noqa: E402

TOYS = ("rehearsal-solar-open2-tiny", "rehearsal-granite-hybrid-tiny",
        "rehearsal-lfm2-moe-tiny")
#: what is the engine's own whatever the state's kind (a restart, a step
#: fault, an overshoot) runs on the first toy alone: the suite's time
one_kind = pytest.mark.parametrize("toy", TOYS[:1], indirect=True,
                                   ids=lambda name: name.split("-", 1)[1])
#: a served token's reference logit lies this close under the reference's
#: largest, in bf16 steps of that maximum: program and reference are both
#: float32 here and agree to 1e-6 of logits of ~0.6 (a bf16 step there is
#: 4e-3), so a served token is the reference's own pick unless two logits
#: tie to 1e-6; the benchmark's limit on the chip is 4
SERVED_STEPS = 0.01


@pytest.fixture(autouse=True)
def clean_state():
    dist.set_mesh(None)
    fi.clear()
    yield
    fi.clear()
    dist.set_mesh(None)


@pytest.fixture(scope="module", params=TOYS,
                ids=lambda name: name.split("-", 1)[1])
def toy(request):
    with open(os.path.join(BENCH, "configs", request.param + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(request.param)
    model = get_model(**config["preset"])
    params = make_params(model, 3100000032, jnp.float32, jax.devices()[:1])
    return model, params, correctness.reference_config(config, name_map), name_map


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


def alone(toy, prompts, max_new, **kw):
    """Each request served by itself on a fresh engine: the undisturbed run."""
    engine = engine_of(toy)
    return [np.asarray(engine.generate_batch([p], max_new_tokens=max_new, **kw)[0])
            for p in prompts]


def drive(serving, limit=3000):
    n = 0
    while serving.step():
        n += 1
        assert n < limit, "serving loop did not converge"


LENS = (5, 130, 70, 300, 17, 200, 64, 129)


def test_more_requests_than_slots(toy):
    """Eight requests over three rows (four slots, one the dummy), prompts
    from 5 to 300 tokens prefilled 128 a chunk between decode steps: every
    request's tokens are those it gets alone, and each is the reference's
    pick at its position (logits, teacher-forced)."""
    get_registry().reset()
    prompts = prompts_of(LENS)
    engine = engine_of(toy, telemetry={"enabled": True},
                       prefill_chunk_tokens=128)
    outs = engine.generate_batch(prompts, max_new_tokens=10)
    stats = engine._last_serve_stats
    want = alone(toy, prompts, 10)
    for o, w in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(o), w)
    cfg = toy[2]
    weights = correctness.Weights(toy[1], toy[3])
    for p, o in zip(prompts, outs):
        verdict = correctness.check_served(cfg, weights, p,
                                           list(np.asarray(o)[len(p):]))
        assert verdict["worst_gap_bf16_steps"] <= SERVED_STEPS, verdict
    counters = engine.telemetry_snapshot()["counters"]
    # a slot is started from zero at each request's first piece, and only there
    assert counters["serving/state_slot_resets"] == len(prompts)
    assert counters["serving/decode_state_rows"] == stats["emitted_tokens"]
    assert stats["decode_steps_ahead"] / stats["decode_steps"] >= 0.7
    if hasattr(toy[0], "num_experts"):              # the MoE toys' experts
        moe = toy[0]
        assert counters["serving/moe_dropped_assignments"] == 0
        routed = moe.n_moe_layers * moe.moe.k * counters["serving/decode_state_rows"]
        if moe.router_width == moe.num_experts:
            # every expert is here: all that the rows' routers chose
            assert counters["serving/moe_assignments"] == routed
        else:
            # a share's counters are of the experts held here: 2 of 16 take
            # about an eighth of rows x 4 assignments
            assert 0 < counters["serving/moe_assignments"] < 0.5 * routed
    assert engine._active_session is None
    assert engine._paged_workspace[2]["conv"][0].shape[1] == 4


def test_recompute_preemption_gives_the_undisturbed_tokens(toy):
    """A pool too small for three rows' growth: a victim is re-queued and
    prefilled again from prompt + generated, its slot started from zero."""
    get_registry().reset()
    prompts = prompts_of((30, 25, 28, 20), seed=2)
    engine = engine_of(toy, telemetry={"enabled": True}, max_num_blocks=9)
    outs = engine.generate_batch(prompts, max_new_tokens=40)
    assert engine._last_serve_stats["preemptions"] > 0
    for o, w in zip(outs, alone(toy, prompts, 40)):
        np.testing.assert_array_equal(np.asarray(o), w)
    counters = engine.telemetry_snapshot()["counters"]
    assert counters["serving/state_slot_resets"] == \
        len(prompts) + engine._last_serve_stats["preemptions"]


def step_until_in_flight(serving, limit=300):
    sess = serving._session
    for _ in range(limit):
        assert serving.step()
        if sess._flight is not None and sess._flight.name == "decode" \
                and sess.sched.stats["decode_steps_ahead"] > 0:
            return sess._flight
    raise AssertionError("no decode step ever ran ahead")


@one_kind
def test_restart_engine_with_a_step_in_flight(toy):
    """The pools, the allocator and the programs are rebuilt: every state
    slot is zero again and every running request recomputed."""
    engine = engine_of(toy, max_running=2)
    prompts = prompts_of((50, 11, 33), seed=3)
    want = alone(toy, prompts, 10)
    serving = AsyncServingEngine(engine, max_new_tokens=10, start=False)
    hs = [serving.add_request(p) for p in prompts]
    step_until_in_flight(serving)
    sess = serving._session
    assert sess.sched.allocator.slots_held == 2
    with engine._mesh_scope():
        sess.restart_engine()
    assert sess._flight is None and sess.sched.allocator.slots_held == 0
    assert all(float(jnp.abs(a).max()) == 0 for a in sess.pools["state"])
    drive(serving)
    assert sess.sched.allocator.slots_held == 0
    serving.shutdown(drain=True)
    for h, w in zip(hs, want):
        np.testing.assert_array_equal(np.asarray(h.result(1)), w)


@one_kind
def test_a_step_fault_requeues_and_recomputes(toy):
    """A fault before a decode dispatch: its rows go back to the queue with
    their slots freed, and come back recomputed."""
    engine = engine_of(toy)
    prompts = prompts_of((9, 40, 21), seed=4)
    want = alone(toy, prompts, 8)
    serving = AsyncServingEngine(engine, max_new_tokens=8, start=False)
    hs = [serving.add_request(p) for p in prompts]
    step_until_in_flight(serving)
    fi.install(fi.FaultInjector().fail_step("decode", count=1))
    drive(serving)
    serving.shutdown(drain=True)
    for h, w in zip(hs, want):
        np.testing.assert_array_equal(np.asarray(h.result(1)), w)


@one_kind
def test_an_overshoot_rows_state_reaches_nobody(toy, monkeypatch):
    """A request's EOS lands while its next step is already queued: that
    step advanced the request's state once more, in a slot that is then
    handed on. The tokens are the serial order's, and the slot's next
    holder starts from zero (its tokens are those it gets alone)."""
    prompts = prompts_of((12, 31, 7, 18, 26), seed=5)
    free = alone(toy, prompts, 24)
    # an EOS that some request emits mid-stream
    eos = int(free[1][len(prompts[1]) + 5])
    runs = {}
    for ahead in (True, False):
        monkeypatch.setattr(_ServeSession, "_run_ahead", ahead)
        engine = engine_of(toy, max_running=2)
        runs[ahead] = [np.asarray(o) for o in engine.generate_batch(
            prompts, max_new_tokens=24, eos_token_id=eos)]
    assert any(len(o) < len(p) + 24 for o, p in zip(runs[True], prompts))
    for a, b, f in zip(runs[True], runs[False], free):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, f[:len(a)])


def test_what_cannot_hold_beside_a_state_is_refused(toy):
    with pytest.raises(ValueError, match="snapshots at block boundaries"):
        engine_of(toy, prefix_caching="on").generate_batch(
            prompts_of((5,)), max_new_tokens=2)
    with pytest.raises(ValueError, match="cannot be rewound"):
        engine_of(toy, speculative={"mode": "ngram", "k": 2}).generate_batch(
            prompts_of((5,)), max_new_tokens=2)
    with pytest.raises(ValueError, match="no state snapshot"):
        engine_of(toy, kv_host={"enabled": True}).generate_batch(
            prompts_of((5,)), max_new_tokens=2)
    with pytest.raises(ValueError, match="no state snapshot"):
        engine_of(toy, kv_host={"enabled": True}).ensure_host_kv_pool()
    # "auto" resolves to off: no prefix is shared, no block registered
    engine = engine_of(toy)
    session = engine.open_serve_session(max_new=2)
    try:
        assert not session.sched.prefix_caching
        assert session.sched.allocator.state_slots == 4
        with pytest.raises(NotImplementedError, match="handoff"):
            session.demote_prompt(prompts_of((40,))[0])
    finally:
        session.close()
    from deepspeed_tpu.inference.block_allocator import BlockAllocator
    with pytest.raises(ValueError, match="snapshots"):
        BlockAllocator(8, 16, prefix_cache=True, state_slots=3)
    alloc = BlockAllocator(8, 16, state_slots=3)
    assert [alloc.allocate_slot(), alloc.allocate_slot(), alloc.allocate_slot()] \
        == [1, 2, None]
    alloc.free_slot(1)
    with pytest.raises(ValueError, match="not held"):
        alloc.free_slot(1)
    assert alloc.allocate_slot() == 1 and BlockAllocator(8, 16).allocate_slot() == 0


def test_a_model_without_state_has_neither_counter():
    get_registry().reset()
    model = CausalLM(TransformerConfig(vocab_size=64, n_layer=2, n_head=4,
                                       d_model=32, d_ff=64, max_seq=128,
                                       remat=False))
    engine = deepspeed_tpu.init_inference(
        model, dtype="fp32", telemetry={"enabled": True},
        serving={"block_size": 8, "max_running": 2})
    engine.generate_batch(prompts_of((5, 9), vocab=64), max_new_tokens=4)
    counters = engine.telemetry_snapshot()["counters"]
    assert counters["serving/decode_steps"] > 0
    assert not [k for k in counters if "state" in k]
    assert set(engine._paged_workspace[2]) == {"k", "v"}
