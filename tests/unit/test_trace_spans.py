"""The program's spans on the profiler's clock (``monitor/trace.span``): with
the span factory swapped for a recorder, the serving loop emits every phase
of a step, on its own thread only, properly nested, with the identifiers a
kept trace is followed by; the spans change no token; the counters that
ride along count what they say; ``range_push``/``range_pop`` keep to their
thread; the serving programs carry names; garbage collections are ``gc``
spans on whichever thread collects and counters the loop publishes; a
decode step launched behind a finished one is counted late; and the loop's
own time (``LoopTime``) rides the same ``with`` statements: the same spans
with a telemetry as without, each phase's counter the time planted under its
span, busy and CPU time around ``serve.step`` less ``serve.fetch``, the late
time a bracket around a planted finish, the commit loop timed on one landed
step in 64, no clock read without a telemetry."""

import gc
import threading
import time

import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.serve import AsyncServingEngine
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor import trace as trace_mod
from deepspeed_tpu.monitor.metrics import get_registry


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps, per span, its
    name, arguments, thread and the span it was opened inside."""

    def __init__(self):
        self.spans = []
        self._tls = threading.local()
        # re-entrant: an allocation under it may set the collector off, whose
        # ``gc`` span is recorded on the same thread
        self._lock = threading.RLock()

    def __call__(self, name, **args):
        return _Recorded(self, name, args)

    def names(self):
        with self._lock:
            return {s["name"] for s in self.spans}

    def named(self, name):
        with self._lock:
            return [s for s in self.spans if s["name"] == name]


class _Recorded:
    def __init__(self, rec, name, args):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self):
        tls = self.rec._tls
        if not hasattr(tls, "stack"):
            tls.stack = []
        stack = tls.stack
        with self.rec._lock:
            self.rec.spans.append({
                "name": self.name, "args": self.args,
                "thread": threading.get_ident(),
                "parent": stack[-1] if stack else None})
        stack.append(self.name)

    def __exit__(self, *exc):
        assert self.rec._tls.stack.pop() == self.name   # strictly nested


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(trace_mod, "span_factory", rec)
    return rec


def tiny_model():
    return CausalLM(TransformerConfig(
        vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64, max_seq=128,
        remat=False))


def tiny_engine(**serving):
    base = {"block_size": 8, "max_running": 2}
    base.update(serving)
    return deepspeed_tpu.init_inference(tiny_model(), dtype="fp32",
                                        telemetry=True, serving=base)


def pb(name):
    return trace_mod.SPAN_PREFIX + name


def serve(engine, prompts, max_new, rec=None):
    """Serve ``prompts`` through the background loop; returns the handles'
    tokens and the loop thread's ident."""
    serving = AsyncServingEngine(engine, max_new_tokens=max_new)
    if rec is not None:
        # the loop parks in serve.idle before the first request arrives
        deadline = time.monotonic() + 30
        while pb("serve.idle") not in rec.names():
            assert time.monotonic() < deadline, "the loop never went idle"
            time.sleep(0.005)
    handles = [serving.add_request(p) for p in prompts]
    outs = [np.asarray(h.result(120)) for h in handles]
    tid = serving._thread.ident
    serving.shutdown(drain=True, timeout=60)
    return outs, tid


STEP_PHASES = ("serve.intake", "serve.schedule", "serve.exec")
# a step is launched inside its serve.exec; it lands (PR 29: the loop runs
# one step ahead) inside the NEXT step's serve.exec, after that launch, or,
# where the next action needs its tokens on the host, ahead of the choice,
# under serve.step itself
LAUNCH_PHASES = ("serve.inputs", "serve.dispatch", "serve.sample")
LAND_PHASES = ("serve.fetch", "serve.commit", "serve.release")
EXEC_PHASES = LAUNCH_PHASES + LAND_PHASES

# serving config, prompts and what serve.exec must say, per action kind
MOTIF = np.tile(np.arange(1, 9, dtype=np.int32), 3)         # 24 tokens
KINDS = {
    "prefill": ({}, [np.arange(3, 14, dtype=np.int32)]),
    "prefill_chunk": ({"prefill_chunk_tokens": 8},
                      [np.arange(3, 23, dtype=np.int32)]),
    "decode": ({}, [np.arange(3, 14, dtype=np.int32),
                    np.arange(20, 25, dtype=np.int32)]),
    "verify": ({"speculative": {"mode": "ngram", "k": 4}}, [MOTIF]),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_serving_loop_spans(kind, recorder):
    serving_cfg, prompts = KINDS[kind]
    engine = tiny_engine(**serving_cfg)
    _, loop_tid = serve(engine, prompts, max_new=6, rec=recorder)

    # a collection is a span of whichever thread it ran on, in whatever
    # phase was open (its own cases are further down)
    spans = [s for s in recorder.spans if s["name"] != pb("gc")]
    assert {s["name"] for s in spans} >= {
        pb(n) for n in ("serve.idle", "serve.step") + STEP_PHASES
        + EXEC_PHASES}
    assert all(s["name"].startswith(trace_mod.SPAN_PREFIX) for s in spans)
    # only the loop's thread annotates its phases: a client thread's span
    # would claim idle gaps it has nothing to do with
    assert {s["thread"] for s in spans} == {loop_tid}
    for s in spans:
        name = s["name"][len(trace_mod.SPAN_PREFIX):]
        if name in ("serve.idle", "serve.step"):
            assert s["parent"] is None
        elif name in STEP_PHASES:
            assert s["parent"] == pb("serve.step")
        elif name in LAUNCH_PHASES:
            assert s["parent"] == pb("serve.exec")
        else:
            assert name in LAND_PHASES, name
            assert s["parent"] in (pb("serve.exec"), pb("serve.step"))
    fetched_under = {s["parent"] for s in recorder.named(pb("serve.fetch"))}
    # under serve.exec: a verify step lands at once (the proposer reads its
    # tokens on the host), any other under the launch that followed it;
    # under serve.step: ahead of a choice that needs the tokens, and the
    # last step of all, with nothing left to launch
    assert fetched_under == {pb("serve.exec"), pb("serve.step")}
    # a span is something that happened: an intake with no command and a
    # trailing flush with nothing to flush leave none, and a decode step's
    # token feed is an operand of its program, not a dispatch of its own
    assert all(s["args"]["n"] > 0 for s in recorder.named(pb("serve.intake")))
    assert len(recorder.named(pb("serve.commit"))) \
        < 2 * len(recorder.named(pb("serve.exec")))
    if kind == "decode":
        assert len(recorder.named(pb("serve.dispatch"))) \
            == len(recorder.named(pb("serve.exec")))

    execs = [s["args"] for s in recorder.named(pb("serve.exec"))
             if s["args"]["kind"] == kind]
    assert execs, f"no {kind} action ran"
    if kind in ("decode", "verify"):
        assert all(set(a) == {"kind", "rows"} for a in execs)
        assert max(a["rows"] for a in execs) == len(prompts)
    else:
        assert all(set(a) == {"kind", "rid", "tokens"} for a in execs)
        by_rid = {}
        for a in execs:
            by_rid.setdefault(a["rid"], []).append(a["tokens"])
        assert sorted(sum(t) for t in by_rid.values()) \
            == sorted(p.size for p in prompts)
        if kind == "prefill_chunk":
            assert max(max(t) for t in by_rid.values()) == 8


def test_kv_fetch_span_when_blocks_land(recorder):
    engine = tiny_engine(max_num_blocks=4, kv_host={"enabled": True})
    prompt = np.arange(16, dtype=np.int32)           # two full blocks
    engine.generate_batch([prompt], max_new_tokens=5)
    rng = np.random.default_rng(3)                   # flood the tiny pool:
    engine.generate_batch(                           # the blocks are demoted
        [rng.integers(0, 64, size=17).astype(np.int32)], max_new_tokens=4)
    assert not recorder.named(pb("serve.kv_fetch"))  # nothing to move yet
    engine.generate_batch([prompt], max_new_tokens=5)
    fetch, = recorder.named(pb("serve.kv_fetch"))
    assert fetch["args"] == {"blocks": 2}
    assert fetch["parent"] == pb("serve.exec")
    assert fetch["thread"] == threading.get_ident()  # the closed loop's


def test_tokens_identical_with_and_without_a_recorder(monkeypatch):
    prompts = [np.arange(3, 14, dtype=np.int32), MOTIF]
    cfg = {"speculative": {"mode": "ngram", "k": 4}}
    inert, _ = serve(tiny_engine(**cfg), prompts, max_new=12)
    rec = Recorder()
    monkeypatch.setattr(trace_mod, "span_factory", rec)
    recorded, _ = serve(tiny_engine(**cfg), prompts, max_new=12, rec=rec)
    assert rec.named(pb("serve.exec"))
    for a, b in zip(inert, recorded):
        np.testing.assert_array_equal(a, b)


def test_counters_count_what_the_steps_did():
    get_registry().reset()
    engine = tiny_engine(max_running=4, prefix_caching="off")
    lens, max_new = (5, 11, 100), 7
    prompts = [np.arange(n, dtype=np.int32) % 64 for n in lens]
    engine.generate_batch(prompts, max_new_tokens=max_new)
    c = engine.telemetry_snapshot()["counters"]
    assert c["serving/prefill_tokens"] == sum(lens)
    # each prompt padded to its multiple of 128 (max_seq is 128 here)
    assert c["serving/prefill_padded_tokens"] == 3 * 128
    # a row's first decode step reads its prompt's KV, each later one a
    # token more; the prefill emitted the first of max_new tokens
    steps = max_new - 1
    assert c["serving/decode_live_kv_tokens"] == sum(
        n + i for n in lens for i in range(steps))
    assert c["serving/decode_steps"] >= steps


def test_live_kv_blocks_counts_every_rows_block_copies():
    """``serving/decode_live_kv_blocks``: what the paged kernel copies a
    layer and pool in a fused step, ``pos // block_size + 1`` blocks a
    decoding row and nothing for an idle row of the step."""
    get_registry().reset()
    rows, bs = 4, 8
    engine = tiny_engine(max_running=rows, block_size=bs,
                         prefix_caching="off")
    lens, max_new = (5, 11, 100), 7
    prompts = [np.arange(n, dtype=np.int32) % 64 for n in lens]
    engine.generate_batch(prompts, max_new_tokens=max_new)
    c = engine.telemetry_snapshot()["counters"]
    steps = max_new - 1
    assert c["serving/decode_steps"] * rows > len(lens) * steps  # idle rows
    assert c["serving/decode_live_kv_blocks"] == sum(
        (n + i) // bs + 1 for n in lens for i in range(steps))


@pytest.fixture
def explicit_collections_only():
    """The collector runs only where a test calls it: what the ``gc`` cases
    count is then theirs alone."""
    was = gc.isenabled()
    gc.disable()
    trace_mod.watch_gc()
    yield
    if was:
        gc.enable()


def host_gc_counters():
    c = get_registry().snapshot()["counters"]
    return {k: c.get("host/gc_" + k) for k in (
        "pause_ms", "full_pause_ms", "full_collections")}


def test_a_full_collection_is_one_gc_span_on_its_thread(
        recorder, explicit_collections_only):
    seen = trace_mod.gc_totals()
    gc.collect(0)
    # generation 0: counted, no span (frequent, short, and a traced run pays
    # for every span it holds)
    assert not recorder.named(pb("gc"))
    young = trace_mod.gc_totals()
    assert young[0] > seen[0] and young[1:] == seen[1:]
    collector = threading.Thread(target=gc.collect)
    collector.start()
    collector.join(60)
    assert not collector.is_alive()
    (span,) = recorder.named(pb("gc"))
    assert span["args"] == {"generation": 2}
    assert span["thread"] == collector.ident != threading.get_ident()
    pause, full_pause, full = (
        a - b for a, b in zip(trace_mod.gc_totals(), young))
    assert full == 1 and pause == full_pause > 0
    gc.collect(1)
    assert [s["args"]["generation"] for s in recorder.named(pb("gc"))] \
        == [2, 1]
    assert trace_mod.gc_totals()[2] == young[2] + 1


def test_watch_gc_twice_leaves_one_handler():
    trace_mod.watch_gc()
    trace_mod.watch_gc()
    AsyncServingEngine(tiny_engine(), max_new_tokens=2,
                       start=False).shutdown()
    assert gc.callbacks.count(trace_mod._on_gc) == 1


def test_the_loop_publishes_collections_once_a_step(
        explicit_collections_only):
    get_registry().reset()
    serving = AsyncServingEngine(tiny_engine(), max_new_tokens=2,
                                 start=False)
    # there from the loop's start: a window without a collection reads 0
    zeros = dict(pause_ms=0.0, full_pause_ms=0.0, full_collections=0.0)
    assert host_gc_counters() == zeros
    gc.collect()
    gc.collect(0)
    assert host_gc_counters() == zeros          # the handler's own totals
    serving._step_once()
    c = host_gc_counters()
    assert c["full_collections"] == 1
    assert c["pause_ms"] > c["full_pause_ms"] > 0
    serving._step_once()                        # nothing collected since
    assert host_gc_counters() == c
    serving.shutdown()


def test_a_collection_under_the_registrys_lock_returns(
        explicit_collections_only):
    """The handler runs at any allocation of any thread, also one made
    under the registry's lock (every counter's too): it may take no lock.
    The lock is re-entrant, so the case that would hang is a collection on
    one thread while ANOTHER holds the lock."""
    reg = get_registry()
    AsyncServingEngine(tiny_engine(), max_new_tokens=2,
                       start=False).shutdown()  # the counters exist

    def collect_holding_it():
        with reg._lock:
            gc.collect()

    def joined(body):
        t = threading.Thread(target=body, daemon=True)
        t.start()
        t.join(30)
        return not t.is_alive()

    seen = trace_mod.gc_totals()[2]
    with reg._lock:
        assert joined(gc.collect), "the handler waits for the registry"
    assert joined(collect_holding_it)
    assert trace_mod.gc_totals()[2] == seen + 2


class _Tok:
    """A sampler's output that says what it is told about being ready."""

    def __init__(self, arr, ready):
        self.arr, self.ready = arr, ready

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.arr, dtype)


@pytest.mark.parametrize("ready", [True, False])
def test_late_steps_count_launches_behind_a_finished_step(ready, monkeypatch):
    class Launched(engine_mod._Launched):
        __slots__ = ()

        def __init__(self, *args):
            super().__init__(*args)
            if self.tok is not None:
                self.tok = _Tok(self.tok, ready)

    monkeypatch.setattr(engine_mod, "_Launched", Launched)
    get_registry().reset()
    engine = tiny_engine(prefill_chunk_tokens=8)
    start = engine.telemetry_snapshot()["counters"]
    prompts = [np.arange(3, 23, dtype=np.int32),
               np.arange(20, 25, dtype=np.int32)]
    out = engine.generate_batch(prompts, max_new_tokens=6)
    end = engine.telemetry_snapshot()["counters"]
    stats = engine._last_serve_stats
    # a window without a late step reads 0, not nothing
    assert start["serving/decode_steps_late"] == 0
    assert end["serving/decode_steps_late"] == stats["decode_steps_late"]
    assert stats["decode_steps_ahead"] > 0
    if ready:
        # every decode step behind a step that sampled; the one behind a
        # prefill chunk that sampled nothing has nothing to ask
        assert 0 < stats["decode_steps_late"] <= stats["decode_steps_ahead"]
    else:
        assert stats["decode_steps_late"] == 0
    monkeypatch.undo()
    plain = tiny_engine(prefill_chunk_tokens=8).generate_batch(
        prompts, max_new_tokens=6)
    for a, b in zip(out, plain):                # the query lands nothing
        np.testing.assert_array_equal(a, b)


# ---- the loop's own time: counters out of the spans' ``with`` ---- #

LOOP_PHASES = ("schedule", "inputs", "dispatch", "sample", "fetch", "commit",
               "release", "intake")
#: ns planted under each span (even, so that half of it is whole)
PLANTED = {"serve.step": 20_000, "serve.exec": 10_000, "serve.fetch":
           5_000_000, **{"serve." + p: 1_000_000 + 2_000 * i
                         for i, p in enumerate(LOOP_PHASES) if p != "fetch"}}


class PlantedClock:
    """The host's clock and the thread's CPU clock, planted: time passes
    only where a span opens, by ``PLANTED`` of its name, and the thread is
    on a CPU for half of it."""

    def __init__(self, monkeypatch, recorder):
        self.ns = 1_000_000_000
        self.cpu_reads = 0
        monkeypatch.setattr(trace_mod, "now_ns", lambda: self.ns)
        monkeypatch.setattr(trace_mod, "cpu_ns", self.cpu)
        monkeypatch.setattr(trace_mod, "span_factory", self.span)
        self.recorder = recorder

    def cpu(self):
        self.cpu_reads += 1
        return self.ns // 2

    def span(self, name, **args):
        clock, inner = self, self.recorder(name, **args)

        class Planted:
            def __enter__(self):
                clock.ns += PLANTED.get(name[len(trace_mod.SPAN_PREFIX):], 0)
                return inner.__enter__()

            def __exit__(self, *exc):
                return inner.__exit__(*exc)
        return Planted()


def toy_engine(model, telemetry=True):
    from deepspeed_tpu.models.presets import get_model
    return deepspeed_tpu.init_inference(
        tiny_model() if model == "dense" else get_model("solar_open2", "tiny"),
        dtype="fp32", telemetry=telemetry,
        serving={"block_size": 8, "max_running": 3})


def drive(engine, max_new=5):
    """Three requests through the synchronous driver, one of them cancelled
    while a step is in flight (its landing then lies under serve.intake);
    returns the session's ``LoopTime`` (None without a telemetry)."""
    serving = AsyncServingEngine(engine, max_new_tokens=max_new, start=False)
    handles = [serving.add_request(np.arange(3, 14 + i, dtype=np.int32))
               for i in range(3)]
    for _ in range(4):
        assert serving.step()
    assert serving._session._flight is not None
    handles[2].cancel()
    while serving.step():
        pass
    assert [h.status for h in handles] == ["finished", "finished",
                                           "cancelled"]
    loop = serving._session.loop
    serving.shutdown()
    return loop


@pytest.fixture(scope="module")
def planted_runs():
    """One planted run a model, for the cases below: the session's
    ``LoopTime``, the spans it recorded and the counters it published."""
    runs = {}

    def run(model):
        if model not in runs:
            with pytest.MonkeyPatch.context() as mp:
                rec = Recorder()
                clock = PlantedClock(mp, rec)
                get_registry().reset()
                loop = drive(toy_engine(model))
                counters = dict(get_registry().snapshot()["counters"])
            runs[model] = (loop, rec, counters, clock)
        return runs[model]
    return run


@pytest.mark.parametrize("phase", LOOP_PHASES)
@pytest.mark.parametrize("model", ["dense", "stateful"])
def test_a_phases_counter_is_the_time_under_its_span(model, phase,
                                                     planted_runs):
    loop, rec, counters, _ = planted_runs(model)
    spans = rec.named(pb("serve." + phase))
    assert spans, f"no serve.{phase} in the run"
    # exclusive of whatever opened inside it: the cancel landed a step
    # (fetch, commit, release) under serve.intake
    assert loop.ns[phase] == len(spans) * PLANTED["serve." + phase]
    assert counters[f"serving/loop_{phase}_ms"] \
        == pytest.approx(loop.ns[phase] / 1e6)
    if phase == "intake":
        assert any(s["parent"] == pb("serve.intake")
                   for s in rec.named(pb("serve.fetch")))


@pytest.mark.parametrize("model", ["dense", "stateful"])
def test_the_phases_cover_the_busy_time_and_never_pass_it(model,
                                                          planted_runs):
    loop, rec, counters, clock = planted_runs(model)
    # ``book`` is a phase with a counter and no span: no time passes under
    # it here, and nothing of its name is in the trace
    assert loop.ns["book"] == 0 == counters["serving/loop_book_ms"]
    assert not rec.named(pb("serve.book"))
    in_step = sum(ns for p, ns in loop.ns.items() if p != "fetch")
    outside = sum(len(rec.named(pb(n))) * PLANTED[n]
                  for n in ("serve.step", "serve.exec"))
    # every phase opened inside a serve.step here, so busy is the phases
    # (less the wait for the device) and what the two bare spans took
    assert loop.busy_ns == in_step + outside
    assert 0.9 * loop.busy_ns <= in_step <= loop.busy_ns
    # the thread's CPU clock (a system call) is read on one turn in 8,
    # around serve.step and a serve.fetch inside it: the planted half
    sampled = loop.turns // loop.CPU_SAMPLE
    assert sampled >= 1 and loop.turns == len(rec.named(pb("serve.step")))
    assert 0 < loop.cpu_ns * 2 == loop.cpu_busy_ns < loop.busy_ns
    assert 2 * sampled <= clock.cpu_reads <= 6 * sampled
    assert loop.ns["kv_fetch"] == 0 and \
        "serving/loop_kv_fetch_ms" not in counters
    # published with the steps they are a step of: the driver's last turn
    # found nothing to do, counted no step, and its serve.step waits for
    # the next publication
    said = loop.busy_ns - PLANTED["serve.step"]
    assert counters["serving/loop_busy_ms"] == pytest.approx(said / 1e6)
    assert counters["serving/loop_cpu_ms"] * 2 \
        == pytest.approx(counters["serving/loop_cpu_busy_ms"])
    assert 0 < counters["serving/loop_cpu_busy_ms"] \
        < counters["serving/loop_busy_ms"]
    # steps that launched or landed something: every serve.exec launched
    # (no ``wait`` action here), and the step that only landed the last
    assert counters["serving/loop_steps"] == loop.steps \
        >= len(rec.named(pb("serve.exec")))


@pytest.mark.parametrize("model", ["dense", "stateful"])
def test_the_helper_changes_no_span(model, planted_runs):
    """Names, arguments, nesting and number of the spans of the same
    requests, with the counters and without."""
    _, counted, _, _ = planted_runs(model)
    with pytest.MonkeyPatch.context() as mp:
        bare = Recorder()
        mp.setattr(trace_mod, "span_factory", bare)
        assert drive(toy_engine(model, telemetry=False)) is None

    def told(rec):
        return [(s["name"], s["parent"], sorted(s["args"])) for s in rec.spans
                if s["name"] != pb("gc")]
    assert told(counted) == told(bare)


def test_no_clock_is_read_without_a_telemetry(monkeypatch):
    def no_clock():
        raise AssertionError("a clock was read")
    monkeypatch.setattr(trace_mod, "now_ns", no_clock)
    monkeypatch.setattr(trace_mod, "cpu_ns", no_clock)
    assert drive(toy_engine("dense", telemetry=False)) is None


@pytest.mark.parametrize("name", sorted(
    "serving/" + n for n in engine_mod.LoopTime().take()
    if n != "loop_kv_fetch_ms"))
def test_every_loop_family_exists_at_zero(name):
    from deepspeed_tpu.inference.scheduler import ServingTelemetry
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    reg = MetricsRegistry()
    tel = ServingTelemetry(reg)
    assert reg.snapshot()["counters"][name] == 0
    assert name[len("serving/"):] in tel._LOOP
    reg.reset()
    tel.ensure()
    assert reg.snapshot()["counters"][name] == 0


class _PlantedTok:
    """Tokens that are there from ``finish`` on, by the planted clock."""

    def __init__(self, clock, finish):
        self.clock, self.finish, self.asked = clock, finish, 0

    def is_ready(self):
        self.asked += 1
        return self.clock[0] >= self.finish


@pytest.fixture
def hand_clock(monkeypatch):
    clock = [1_000]
    monkeypatch.setattr(trace_mod, "now_ns", lambda: clock[0])
    monkeypatch.setattr(trace_mod, "cpu_ns", lambda: clock[0])
    return clock


def launch(loop, clock, tok, behind, took=10):
    """What ``_launch`` does around its dispatch."""
    with loop.phase("inputs"):
        clock[0] += took
    late = loop.finished
    before = loop.t
    with loop.phase("dispatch"):
        clock[0] += took
    loop.launched(behind, before)
    with loop.phase("sample"):
        clock[0] += took
    loop.watch(tok)
    return late


@pytest.mark.parametrize("finish", [1_045, 1_075, 1_100, 1_300])
def test_late_time_brackets_a_planted_finish(finish, hand_clock):
    clock = hand_clock
    loop = trace_mod.LoopTime()
    first = _PlantedTok(clock, finish)
    # the first launch (after an idle wait: behind nothing) counts nothing,
    # whatever the clock
    assert not launch(loop, clock, first, behind=False)      # 1,000-1,030
    assert (loop.late_ns, loop.slack_ns) == (0, 0)
    for phase in ("fetch", "commit", "release", "schedule"):
        with loop.phase(phase):                              # ...-1,110
            clock[0] += 20
    late = launch(loop, clock, _PlantedTok(clock, 10**9), behind=True)
    returned = 1_130                                         # its dispatch
    true_late = max(returned - finish, 0)
    assert late == (finish <= 1_120)        # as seen at the inputs' exit
    assert loop.late_ns <= true_late <= loop.late_ns + loop.slack_ns
    if true_late:
        # asked at every phase's exit (20 apart; the first 40 after the
        # launch's own last no), and never after the first yes
        assert loop.slack_ns <= 40
        assert first.asked <= 5
    else:
        assert (loop.late_ns, loop.slack_ns) == (0, 0)


def test_a_step_that_samples_nothing_is_asked_nothing(hand_clock):
    clock = hand_clock
    loop = trace_mod.LoopTime()
    launch(loop, clock, None, behind=True)          # a chunk: ``tok is None``
    assert not loop.polling
    with loop.phase("commit"):
        clock[0] += 500
    assert not launch(loop, clock, _PlantedTok(clock, 0), behind=True)
    assert (loop.late_ns, loop.slack_ns) == (0, 0)
    # and a launch behind NOTHING drops a bracket that had closed
    with loop.phase("commit"):
        clock[0] += 500
    assert loop.finished
    launch(loop, clock, None, behind=False)
    assert (loop.late_ns, loop.slack_ns) == (0, 0)


def test_the_first_launch_after_an_idle_wait_counts_no_late_time(monkeypatch):
    """Through the session: every step's tokens are there at once, so every
    launch behind an unfetched step is late, and the launches behind
    nothing (the first of all, the first after the loop had gone idle)
    add nothing."""
    class Launched(engine_mod._Launched):
        __slots__ = ()

        def __init__(self, *args):
            super().__init__(*args)
            if self.tok is not None:
                self.tok = _Tok(self.tok, True)

    monkeypatch.setattr(engine_mod, "_Launched", Launched)
    get_registry().reset()
    serving = AsyncServingEngine(tiny_engine(), max_new_tokens=4,
                                 start=False)
    session = serving._session
    grew = []                       # (launched behind a step, late grew)
    for _ in range(2):
        serving.add_request(np.arange(3, 14, dtype=np.int32))
        while True:
            behind, was = session._flight is not None, session.loop.late_ns
            alive = serving.step()
            if session.last_action is not None:
                grew.append((behind, session.loop.late_ns > was))
            if not alive:
                break
        assert session._flight is None          # idle: nothing in flight
    assert [b for b, _ in grew].count(False) == 2
    assert all(late == behind for behind, late in grew), grew
    c = get_registry().snapshot()["counters"]
    assert c["serving/late_ms"] == pytest.approx(session.loop.late_ns / 1e6)
    assert c["serving/late_ms"] > 0 and c["serving/late_slack_ms"] >= 0
    assert c["serving/decode_steps_late"] \
        == session.sched.stats["decode_steps_late"] > 0
    serving.shutdown()


def test_the_commit_loop_is_timed_on_one_landed_step_in_64(monkeypatch):
    landed = []
    plain = engine_mod._ServeSession._commit

    def commit(self, step, rows):
        if rows:
            landed.append(len(rows))
        return plain(self, step, rows)

    monkeypatch.setattr(engine_mod._ServeSession, "_commit", commit)
    get_registry().reset()
    engine = tiny_engine(max_running=2)
    serving = AsyncServingEngine(engine, max_new_tokens=70, start=False)
    for i in range(2):
        serving.add_request(np.arange(3, 14 + i, dtype=np.int32))
    while serving.step():
        pass
    loop = serving._session.loop
    assert len(landed) == loop.landed > 64
    # a sampled step's rows once, an unsampled step's not at all
    assert loop.sampled_rows == sum(landed[::64]) == landed[0] + landed[64]
    assert loop.record_ns > 0 and loop.wake_ns > 0
    c = get_registry().snapshot()["counters"]
    assert c["serving/commit_sampled_rows"] == loop.sampled_rows
    assert c["serving/commit_record_ms"] \
        == pytest.approx(loop.record_ns / 1e6)
    serving.shutdown()


def test_range_push_pop_keep_to_their_thread(monkeypatch):
    import jax

    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    acc = get_accelerator()
    a_pushed, b_pushed, b_popped = (threading.Event() for _ in range(3))
    errors = []

    def run(body):
        try:
            body()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    def a():
        acc.range_push("a")
        a_pushed.set()
        assert b_pushed.wait(10)
        acc.range_pop()               # with one stack this would pop "b"
        assert b_popped.wait(10)

    def b():
        assert a_pushed.wait(10)
        acc.range_push("b")
        b_pushed.set()
        time.sleep(0.05)              # let a pop first
        acc.range_pop()
        acc.range_pop()               # nothing left on this thread: no-op
        b_popped.set()

    threads = [threading.Thread(target=run, args=(f,)) for f in (a, b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    # _Recorded.__exit__ asserts that each pop closed its own thread's span
    assert sorted(s["name"] for s in rec.spans) == ["a", "b"]


def test_serving_programs_carry_their_names():
    engine = tiny_engine()
    with engine._mesh_scope():
        jits = engine._ensure_paged_jits()
    names = ["paged_prefill", "paged_decode", "paged_prefill_chunk",
             "paged_cow", "paged_verify", "paged_spill_gather",
             "paged_fetch_scatter", "paged_sample"]
    assert [j.inner.__name__ for j in jits] == names
    assert [j.__name__ for j in jits] \
        == [f"watched[inference.{n}]" for n in names]


def test_step_tracer_span_goes_through_the_helper(recorder):
    tr = trace_mod.StepTracer()
    with tr.span("fwd", step=3):
        pass
    (s,) = recorder.spans
    assert s["name"] == pb("fwd") and s["args"] == {"step": 3}
    assert [e["name"] for e in tr.events] == ["fwd"]
    quiet = trace_mod.StepTracer(use_accelerator=False)
    with quiet.span("bwd"):
        pass
    assert len(recorder.spans) == 1 and len(quiet.events) == 1


def test_train_batch_emits_the_step_span(monkeypatch):
    import jax

    steps = []

    class Step:
        def __init__(self, name, step_num):
            steps.append((name, step_num))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    rec = Recorder()
    monkeypatch.setattr(trace_mod, "step_span_factory", Step)
    monkeypatch.setattr(trace_mod, "span_factory", rec)
    model = CausalLM(TransformerConfig(
        vocab_size=64, n_layer=1, n_head=2, d_model=16, d_ff=32, max_seq=16,
        remat=False, attention_backend="xla"))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.key(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "mesh": {"dp": -1}, "steps_per_print": 0})
    dp = dist.get_world_size(dist.data_parallel_axes(engine.mesh))
    batch = {"input_ids": np.zeros((dp, 16), np.int32)}
    engine.train_batch(batch)
    engine.train_batch(batch)
    assert steps == [(pb("train"), 1), (pb("train"), 2)]
    assert len(rec.named(pb("train.put_batch"))) == 2
