"""The serving loop one step ahead (``_ServeSession``: a step is launched
from the tokens of the step before it while those are still on the device,
and that step is fetched, committed and released under the new one).

Exactness: every request's tokens are those of the serial order, the pipe at
depth zero, which a private class attribute pins for the comparison; so are
the prefix cache's registrations. Where a rule forbids running ahead
(speculation) no decode step is counted ahead; in a steady closed loop four
in five are at least. Lifecycle and faults with a step in flight: nothing is
lost, nothing is given twice. The conftest ``_no_kv_block_leaks`` fixture
applies file-wide."""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.engine import _ServeSession
from deepspeed_tpu.inference.serve import AsyncServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.utils import fault_injection as fi

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "perfbench"))


@pytest.fixture(autouse=True)
def clean_state():
    dist.set_mesh(None)
    fi.clear()
    yield
    fi.clear()
    dist.set_mesh(None)


def dense_toy():
    return CausalLM(TransformerConfig(
        vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64, max_seq=128,
        remat=False))


def moe_toy():
    from deepspeed_tpu.models.presets import get_model
    with open(os.path.join(BENCH, "configs", "rehearsal-olmoe-tiny.json")) as f:
        return get_model(**json.load(f)["preset"])


def engine_of(model=dense_toy, telemetry=None, **serving):
    cfg = {"block_size": 8, "max_running": 3}
    cfg.update(serving)
    kw = {"dtype": "fp32", "serving": cfg}
    if telemetry is not None:
        kw["telemetry"] = telemetry
    return deepspeed_tpu.init_inference(model(), **kw)


def prompts_of(lens=(5, 11, 17, 9, 30, 7), vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def drive(serving, limit=2000):
    n = 0
    while serving.step():
        n += 1
        assert n < limit, "serving loop did not converge"


def run_closed(monkeypatch, ahead, prompts, model=dense_toy, max_new=12,
               temperature=0.0, eos=None, **serving):
    """One ``generate_batch`` at depth one or zero: the outputs, the step
    accounting, and the prefix cache's registrations (block -> key)."""
    monkeypatch.setattr(_ServeSession, "_run_ahead", ahead)
    engine = engine_of(model, **serving)
    outs = engine.generate_batch(prompts, max_new_tokens=max_new,
                                 temperature=temperature, seed=3,
                                 eos_token_id=eos)
    assert engine._active_session is None
    return ([np.asarray(o) for o in outs], dict(engine._last_serve_stats),
            dict(engine._paged_alloc._key_of))


# the repeated prefix makes admissions hit the cache (a full block of 8, so
# the tail is a chunk behind a copy-on-write split or a plain hit)
SHARED = np.arange(1, 17, dtype=np.int32)
CASES = {
    # name: (serving config, kwargs of the run, least ahead share or None)
    "max_new_by_count": ({}, {}, 0.8),
    "admission_behind_a_decode": ({"max_running": 2}, {}, 0.8),
    "chunked_prefill": ({"prefill_chunk_tokens": 8}, {}, 0.7),
    "pool_small_enough_to_preempt": ({"max_num_blocks": 9}, {}, None),
    "speculation_on": ({"speculative": {"mode": "ngram", "k": 3}}, {}, 0.0),
    "prefix_caching_on": ({"prefix_caching": "on"}, {"prompts": [
        np.concatenate([SHARED, p]) for p in prompts_of((3, 9, 1, 6))]
        + [SHARED.copy(), SHARED.copy()]}, 0.7),
    "moe_toy": ({}, {"model": moe_toy}, 0.8),
}


@pytest.mark.parametrize("case,temperature", [
    pytest.param(case, t, id=f"{case}-{'sampled' if t else 'greedy'}")
    for case in sorted(CASES) for t in (0.0, 0.8)
    if not (t and "speculative" in CASES[case][0])])   # greedy-only
def test_tokens_are_the_serial_orders(case, temperature, monkeypatch):
    serving_cfg, kw, least = CASES[case]
    kw = dict(kw)
    prompts = kw.pop("prompts", None) or prompts_of()
    ahead, stats, keys = run_closed(monkeypatch, True, prompts,
                                    temperature=temperature, **serving_cfg,
                                    **kw)
    serial, stats0, keys0 = run_closed(monkeypatch, False, prompts,
                                       temperature=temperature,
                                       **serving_cfg, **kw)
    for a, b in zip(ahead, serial):
        np.testing.assert_array_equal(a, b)
    # the scheduler chose the same steps and the allocator saw the same
    # operations in the same order: same blocks under the same keys
    assert keys == keys0
    for k in ("decode_steps", "verify_steps", "emitted_tokens",
              "preemptions"):
        assert stats[k] == stats0[k], k
    assert stats0["decode_steps_ahead"] == 0
    if case == "pool_small_enough_to_preempt":
        assert stats["preemptions"] > 0
    if least is not None:
        steps = max(stats["decode_steps"], 1)
        if least:
            assert stats["decode_steps_ahead"] / steps >= least
        else:
            assert stats["decode_steps_ahead"] == 0


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_an_eos_mid_stream_is_an_overshoot(temperature, monkeypatch):
    """A request whose EOS lands while its next step is queued: that step's
    token for it is dropped, ``pos`` and the registrations are the serial
    run's. Sampled: a lone request, because the overshoot keeps a row for a
    step more and takes a split, which moves the OTHER rows' draws."""
    prompts = prompts_of() if not temperature else prompts_of((11,))
    free, _, _ = run_closed(monkeypatch, False, prompts, max_new=24,
                            temperature=temperature)
    # an EOS that ends some request in the middle of its answer: a token
    # whose first appearance in an answer is past its second place
    mid = [int(g[i]) for g in (o[p.size:] for o, p in zip(free, prompts))
           for i in range(2, g.size - 2) if g[i] not in g[:i]]
    assert mid
    eos = mid[0]
    ahead, stats, keys = run_closed(monkeypatch, True, prompts, max_new=24,
                                    temperature=temperature, eos=eos)
    serial, _, keys0 = run_closed(monkeypatch, False, prompts, max_new=24,
                                  temperature=temperature, eos=eos)
    cut = [o.size - p.size for o, p in zip(serial, prompts)]
    assert any(1 < n < 24 for n in cut), cut
    for a, b in zip(ahead, serial):
        np.testing.assert_array_equal(a, b)
    assert set(keys.values()) == set(keys0.values())
    assert stats["decode_steps_ahead"] > 0


def test_counter_and_share_in_a_steady_closed_loop():
    """``serving/decode_steps_ahead`` over ``serving/decode_steps``: what
    ``*.ahead_step_share`` reads."""
    get_registry().reset()
    engine = engine_of(telemetry=True, max_running=2)
    engine.generate_batch(prompts_of((5, 9, 13, 7)), max_new_tokens=16)
    c = engine.telemetry_snapshot()["counters"]
    assert c["serving/decode_steps"] >= 30
    assert c["serving/decode_steps_ahead"] / c["serving/decode_steps"] >= 0.8
    with open(os.path.join(BENCH, "layer_metrics",
                           "ahead_step_share.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["params"]["require"] == ["serving/decode_steps_ahead"]


def test_verify_steps_never_run_ahead():
    get_registry().reset()
    engine = engine_of(telemetry=True,
                       speculative={"mode": "ngram", "k": 4})
    motif = np.tile(np.arange(1, 9, dtype=np.int32), 3)
    engine.generate_batch([motif, motif[:17]], max_new_tokens=12)
    c = engine.telemetry_snapshot()["counters"]
    assert c["serving/spec_verify_steps"] > 0
    assert c["serving/decode_steps_ahead"] == 0


def test_generate_batch_leaves_nothing_in_flight():
    engine = engine_of()
    seen = []
    real = _ServeSession.close

    def close(self):
        real(self)
        seen.append((self._flight, self.sched.retiring, self.pools_alive()))

    _ServeSession.close = close
    try:
        outs = engine.generate_batch(prompts_of(), max_new_tokens=5)
    finally:
        _ServeSession.close = real
    assert seen == [(None, [], True)]
    assert [o.size for o in outs] == [p.size + 5 for p in prompts_of()]
    # the pools came back: the next call reuses them
    ws = engine._paged_workspace
    engine.generate_batch(prompts_of((4,)), max_new_tokens=3)
    assert engine._paged_workspace[:2] == ws[:2]


# --------------------------------------------------------------------- #
# lifecycle and faults with a step in flight (the synchronous front-end:
# one ``step()`` a loop turn, so the test sees the state between turns)


def refs_of(engine, prompts, max_new):
    return [np.asarray(engine.generate(p[None, :], max_new_tokens=max_new))[0]
            for p in prompts]


def step_until_in_flight(serving, kind="decode", limit=200):
    """Turn the loop until a ``kind`` step is in flight behind another."""
    sess = serving._session
    for _ in range(limit):
        assert serving.step()
        if sess._flight is not None and sess._flight.name == kind \
                and sess.sched.stats["decode_steps_ahead"] > 0:
            return sess._flight
    raise AssertionError(f"no {kind} step ever ran ahead")


@pytest.mark.parametrize("phase", ["pre", "post"])
def test_a_fault_at_a_launch_lands_the_step_before_first(phase):
    """``pre``: contained per request; ``post``: the pools died, the engine
    restarts. Either way the step in flight lands first, so every request
    gets each of its tokens once, and they are the un-faulted run's."""
    engine = engine_of(max_running=2)
    prompts = prompts_of((5, 11, 3))
    refs = refs_of(engine, prompts, 10)
    serving = AsyncServingEngine(engine, max_new_tokens=10, start=False)
    hs = [serving.add_request(p) for p in prompts]
    step_until_in_flight(serving)
    sess = serving._session
    before = [len(h.generated) for h in hs]
    with fi.inject(fi.FaultInjector().fail_step("decode", count=1,
                                                phase=phase)):
        assert serving.step()           # the faulted launch
        # the step that was in flight landed before the containment
        assert sess._flight is None and not sess.sched.retiring
        assert sum(len(h.generated) for h in hs) > sum(before)
        drive(serving)
    assert serving.restarts == (1 if phase == "post" else 0)
    serving.shutdown(drain=True)
    assert [h.status for h in hs] == ["finished"] * 3
    for h, ref in zip(hs, refs):
        np.testing.assert_array_equal(np.asarray(h.result(1)), ref)


def test_cancel_of_a_row_in_flight():
    engine = engine_of(max_running=2)
    prompts = prompts_of((5, 11))
    refs = refs_of(engine, prompts, 12)
    serving = AsyncServingEngine(engine, max_new_tokens=12, start=False)
    hs = [serving.add_request(p) for p in prompts]
    flight = step_until_in_flight(serving)
    assert len(flight.reqs) == 2
    hs[0].cancel()
    drive(serving)
    serving.shutdown(drain=True)
    assert hs[0].status == "cancelled" and hs[1].status == "finished"
    # what the cancelled request got is a prefix of its answer, the step in
    # flight at the cancel included
    got = np.asarray(hs[0].generated, np.int32)
    assert 0 < got.size < 12
    np.testing.assert_array_equal(got, refs[0][5:5 + got.size])
    np.testing.assert_array_equal(np.asarray(hs[1].result(1)), refs[1])


def test_a_deadline_expires_on_a_row_in_flight():
    engine = engine_of(max_running=2)
    prompts = prompts_of((5, 11))
    refs = refs_of(engine, prompts, 12)
    serving = AsyncServingEngine(engine, max_new_tokens=12, start=False)
    slow = serving.add_request(prompts[0], deadline_steps=7)
    ok = serving.add_request(prompts[1])
    drive(serving)
    serving.shutdown(drain=True)
    assert slow.status == "timeout" and ok.status == "finished"
    # every token it was given before the deadline is its answer's
    got = np.asarray(slow.generated, np.int32)
    assert got.size >= 1
    np.testing.assert_array_equal(got, refs[0][5:5 + got.size])
    np.testing.assert_array_equal(np.asarray(ok.result(1)), refs[1])


@pytest.mark.parametrize("drain", [True, False])
def test_shutdown_with_a_step_in_flight(drain):
    engine = engine_of(max_running=2)
    prompts = prompts_of((5, 11))
    refs = refs_of(engine, prompts, 12)
    serving = AsyncServingEngine(engine, max_new_tokens=12, start=False)
    hs = [serving.add_request(p) for p in prompts]
    step_until_in_flight(serving)
    serving.shutdown(drain=drain)
    sess = serving._session
    assert sess._closed and sess._flight is None and not sess.sched.retiring
    assert engine._active_session is None
    for h, p, ref in zip(hs, prompts, refs):
        got = np.asarray(h.generated, np.int32)
        if drain:
            assert h.status == "finished"
            np.testing.assert_array_equal(np.asarray(h.result(1)), ref)
        else:
            assert h.status == "cancelled"
            np.testing.assert_array_equal(got,
                                          ref[p.size:p.size + got.size])
    # the pools were handed back: a closed loop serves on them
    out = engine.generate_batch([prompts[0]], max_new_tokens=12)[0]
    np.testing.assert_array_equal(np.asarray(out), refs[0])


def test_restart_engine_with_a_step_in_flight():
    engine = engine_of(max_running=2)
    prompts = prompts_of((5, 11, 3))
    refs = refs_of(engine, prompts, 10)
    serving = AsyncServingEngine(engine, max_new_tokens=10, start=False)
    hs = [serving.add_request(p) for p in prompts]
    step_until_in_flight(serving)
    sess = serving._session
    with engine._mesh_scope():
        sess.restart_engine()
    assert sess._flight is None and not sess.sched.retiring
    drive(serving)
    serving.shutdown(drain=True)
    for h, ref in zip(hs, refs):
        np.testing.assert_array_equal(np.asarray(h.result(1)), ref)


class _LostArray:
    def __array__(self, *a, **k):
        raise RuntimeError("device lost")


def test_a_lost_fetch_requeues_every_row_of_the_step():
    """The tokens of a launched step never arrive: its rows, those retired
    by count included, go back to the queue and are recomputed."""
    engine = engine_of(max_running=2)
    prompts = prompts_of((5, 11))
    refs = refs_of(engine, prompts, 6)
    session = engine.open_serve_session(max_new=6)
    with engine._mesh_scope():
        reqs = [session.add(p) for p in prompts]
        while not session.sched.retiring:
            assert session.step()
        flight = session._flight
        assert flight is not None
        flight.tok = _LostArray()
        with pytest.raises(RuntimeError, match="device lost"):
            session.land()
        assert session._flight is None and not session.sched.retiring
        assert {r.state for r in reqs if r.state != "finished"} == {"queued"}
        while session.step():
            pass
        session.close()
        session.end()
    for r, ref in zip(reqs, refs):
        np.testing.assert_array_equal(r.output, ref)


# ---- the step's programs: a feed operand and one sampler program ---- #

@pytest.mark.parametrize("rows", (1, 3), ids=("prefill_row", "decode_rows"))
@pytest.mark.parametrize("temperature,top_k", ((0.0, 0), (0.7, 0), (0.7, 5)),
                         ids=("greedy", "sampled", "top_k"))
def test_one_sampler_program_draws_what_the_eager_sampler_draws(
        rows, temperature, top_k):
    """``paged_sample`` is the loop's whole sampler as one program: its
    tokens are ``_sample_host``'s for the same key, at the decode width
    (a prefill's one token widened: what the next step's feed reads)."""
    import jax
    import jax.numpy as jnp
    engine = engine_of()
    with engine._mesh_scope():
        sample = engine._ensure_paged_jits()[-1]
    width = 3
    logits = jax.random.normal(jax.random.PRNGKey(7), (rows, 64),
                               jnp.bfloat16) * 3
    key = jax.random.PRNGKey(11) if temperature else None
    got = np.asarray(sample(
        logits, key, jnp.float32(temperature) if temperature else None,
        top_k, width))
    want = np.asarray(engine._sample_host(
        logits.astype(jnp.float32), temperature, top_k, key))
    assert got.shape == (width,)
    np.testing.assert_array_equal(got[:rows], want)
    if rows == 1:
        assert (got == want[0]).all()


def test_a_decode_step_is_two_programs(monkeypatch):
    """The feed is an operand of the decode program and the sampler is one
    program: a closed loop compiles the sampler at its two widths and
    dispatches ``paged_decode`` and ``paged_sample`` once a decode step,
    and a greedy session never splits a key."""
    import jax
    engine = engine_of(telemetry={"enabled": True})
    splits, seen, split = [], {}, jax.random.split
    monkeypatch.setattr(jax.random, "split",
                        lambda *a, **k: splits.append(a) or split(*a, **k))
    real = _ServeSession._dispatch

    def counting(self, site, *a, **k):
        seen[site] = seen.get(site, 0) + 1
        return real(self, site, *a, **k)
    monkeypatch.setattr(_ServeSession, "_dispatch", counting)
    # the watchdog is the process's: count from here
    before = dict(engine.telemetry_snapshot()["compile"]["by_fn"])
    engine.generate_batch(prompts_of((5, 11, 17)), max_new_tokens=8)
    steps = engine._last_serve_stats["decode_steps"]
    assert not splits
    assert seen == {"prefill": 3, "decode": steps, "sample": 3 + steps}
    by_fn = engine.telemetry_snapshot()["compile"]["by_fn"]
    compiled = {k: n - before.get(k, 0) for k, n in by_fn.items()}
    assert compiled["inference.paged_sample"] == 2
    assert compiled["inference.paged_decode"] == 1


def test_decode_program_takes_the_feed_or_plain_tokens():
    """Row i of the feed ``(prev, idx, toks)`` reads ``prev[idx[i]]`` where
    ``idx[i] >= 0`` and ``toks[i]`` otherwise: the logits are those of the
    same tokens handed over plainly."""
    import jax.numpy as jnp
    engine = engine_of()
    with engine._mesh_scope():
        decode = engine._ensure_paged_jits()[1]
        pools, _ = engine._paged_pools(6, 8)
        bt = jnp.asarray([[1, 0], [2, 0], [3, 0]], jnp.int32)
        pos = jnp.asarray([0, 0, 0], jnp.int32)
        plain = np.asarray([[9], [4], [33]], np.int32)
        want, pools, *_ = decode(engine.params, jnp.asarray(plain), pools,
                                 bt, pos)
        prev = jnp.asarray([33, 60, 9], jnp.int32)     # the sampler's rows
        idx = jnp.asarray([2, -1, 0], jnp.int32)
        toks = jnp.asarray([[0], [4], [0]], jnp.int32)
        got, pools, *_ = decode(engine.params, (prev, idx, toks), pools,
                                bt, pos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
