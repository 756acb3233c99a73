"""Flight recorder + request-lifecycle tracing + on-demand profiling:
ring-buffer invariants, the disabled-mode zero-overhead pin, the
deterministic event sequence of a pinned ``generate_batch`` (including
preemption and a prefix-cache hit), chrome-trace serving export validated
by ``tools/validate_trace.py``, events.jsonl in anomaly/emergency
bundles, the profiler capture window, and the new CLI surfaces
(``dscli trace --validate``, ``dscli profile``, ``dscli health --json``).
"""

import importlib.util
import json
import os
import threading
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor import events as events_mod
from deepspeed_tpu.monitor.events import (EVENT_KINDS, Event, FlightRecorder,
                                          get_flight_recorder,
                                          render_serving_trace)
from deepspeed_tpu.monitor.trace import ProfileWindow, StepTracer

_VT_PATH = Path(__file__).resolve().parents[2] / "tools" / "validate_trace.py"
_spec = importlib.util.spec_from_file_location("validate_trace", _VT_PATH)
validate_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(validate_trace)


@pytest.fixture(autouse=True)
def clean_state():
    """Fresh mesh + fresh global registry/watchdog/recorder per test (the
    recorder is process-global: engines enable it in place)."""
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.trace import get_compile_watchdog

    def _reset():
        dist.set_mesh(None)
        get_registry().reset()
        get_registry().set_enabled(True)
        get_compile_watchdog().reset()
        rec = get_flight_recorder()
        rec.disable()
        rec.clear()

    _reset()
    yield
    _reset()


def tiny_model(**over):
    base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                max_seq=64, remat=False)
    base.update(over)
    return CausalLM(TransformerConfig(**base))


def serving_engine(**serving):
    base = {"block_size": 8, "max_running": 2}
    base.update(serving)
    return deepspeed_tpu.init_inference(
        tiny_model(), dtype="fp32",
        telemetry={"enabled": True, "events": True}, serving=base)


def train_engine(telemetry=None):
    dist.set_mesh(None)
    model = tiny_model(max_seq=32, n_head=2, attention_backend="xla")
    params = model.init_params(jax.random.key(0))
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"dp": -1},            # all 8 virtual CPU devices
        "steps_per_print": 0,
    }
    if telemetry is not None:
        config["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    rng = np.random.default_rng(0)
    rows = engine.train_micro_batch_size_per_gpu() * \
        engine.gradient_accumulation_steps() * \
        dist.get_world_size(dist.data_parallel_axes(engine.mesh))

    def batch():
        return {"input_ids": rng.integers(0, 64, size=(rows, 32))
                .astype(np.int32)}

    return engine, batch


# --------------------------------------------------------------------- #
# the recorder itself


class TestFlightRecorder:

    def test_ring_bound_and_drop_counter(self):
        r = FlightRecorder(capacity=4, enabled=True)
        for i in range(7):
            r.emit("req.enqueue", rid=i, prompt_tokens=1, max_new=1)
        assert len(r) == 4 and r.dropped == 3
        # a flight recorder keeps the TAIL (newest events survive)
        assert [e.rid for e in r.snapshot()] == [3, 4, 5, 6]
        r.clear()
        assert len(r) == 0 and r.dropped == 0

    def test_typed_kinds_rejected(self):
        r = FlightRecorder(enabled=True)
        with pytest.raises(ValueError, match="unknown event kind"):
            r.emit("req.not_a_kind")
        assert "req.admit" in EVENT_KINDS

    def test_disabled_emit_is_flag_check_no_allocation(self, monkeypatch):
        r = FlightRecorder(enabled=False)

        def boom(*a, **k):
            raise AssertionError("Event allocated in disabled mode")

        # patch the module-global name emit() resolves (patching
        # Event.__new__ itself can't be restored cleanly)
        monkeypatch.setattr(events_mod, "Event", boom)
        for _ in range(100):
            r.emit("req.admit", rid=0, cached_tokens=0)
        assert len(r) == 0 and r.dropped == 0

    def test_monotonic_timestamps_and_explicit_start(self):
        r = FlightRecorder(enabled=True)
        r.emit("serve.begin", requests=1)
        r.emit("serve.end", t_ns=123, dur_ns=45, requests=1)
        a, b = r.snapshot()
        assert a.ts_ns > 0 and b.ts_ns == 123 and b.dur_ns == 45
        assert b.to_dict() == {"ts_ns": 123, "kind": "serve.end",
                               "dur_ns": 45, "requests": 1}

    def test_thread_safety_under_concurrent_emit(self):
        r = FlightRecorder(capacity=256, enabled=True)

        def work(tid):
            for i in range(500):
                r.emit("req.enqueue", rid=tid * 1000 + i,
                       prompt_tokens=1, max_new=1)

        threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(r) == 256
        assert r.dropped == 4 * 500 - 256

    def test_write_jsonl_roundtrip_validates(self, tmp_path):
        r = FlightRecorder(capacity=3, enabled=True)
        for i in range(5):
            r.emit("req.enqueue", rid=i, prompt_tokens=2, max_new=1)
        p = r.write_jsonl(str(tmp_path / "events.jsonl"))
        lines = Path(p).read_text().splitlines()
        # dropped header + 3 retained events
        assert json.loads(lines[0]) == {"ts_ns": json.loads(lines[0])["ts_ns"],
                                        "kind": "recorder.dropped", "count": 2}
        assert len(lines) == 4
        assert validate_trace.validate_path(p) == []

    def test_enable_resize_keeps_newest(self):
        r = FlightRecorder(capacity=8, enabled=True)
        for i in range(6):
            r.emit("req.enqueue", rid=i, prompt_tokens=1, max_new=1)
        r.enable(capacity=3)
        assert [e.rid for e in r.snapshot()] == [3, 4, 5]


# --------------------------------------------------------------------- #
# serving trace rendering (synthetic events — renderer unit coverage)


def _ev(kind, ts, **kw):
    data = {k: v for k, v in kw.items()
            if k not in ("rid", "step", "dur_ns")}
    return Event(ts_ns=ts, kind=kind, rid=kw.get("rid"),
                 step=kw.get("step"), dur_ns=kw.get("dur_ns"),
                 data=data or None)


class TestServingTraceRender:

    def test_one_span_per_request_even_when_preempted(self):
        evs = [
            _ev("req.enqueue", 100, rid=0, prompt_tokens=4),
            _ev("req.admit", 200, rid=0, cached_tokens=0, blocks=1),
            _ev("req.prefill", 300, rid=0, dur_ns=50, tokens=4),
            _ev("req.preempt", 400, rid=0, blocks=1, recompute_tokens=5),
            _ev("req.admit", 500, rid=0, cached_tokens=0, blocks=2),
            _ev("decode.tick", 600, dur_ns=40, rids=[0], n=1),
            _ev("req.retire", 700, rid=0, generated=3, preemptions=1),
        ]
        doc = render_serving_trace(evs)
        spans = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
        assert len(spans) == 1
        span = spans[0]
        # first admission -> retire, preemption folded into args
        assert span["ts"] == pytest.approx(0.1) \
            and span["dur"] == pytest.approx(0.5)
        assert span["args"]["preemptions"] == 1
        names = Counter(e["name"] for e in doc["traceEvents"]
                        if e["ph"] == "X" and e.get("cat") != "request")
        assert names["prefill"] == 1 and names["decode"] == 1
        assert validate_trace.validate_chrome_trace(doc) == []

    def test_counter_tracks_and_incomplete_requests(self):
        evs = [
            _ev("req.admit", 10, rid=7, cached_tokens=0, blocks=1),
            _ev("sched.gauge", 20, queued=2, running=1, kv_used=3, kv_free=4),
            _ev("decode.tick", 30, dur_ns=5, rids=[7], n=1),
        ]
        doc = render_serving_trace(evs)
        counters = {e["name"]: e["args"] for e in doc["traceEvents"]
                    if e["ph"] == "C"}
        assert counters["queue_depth"] == {"queued": 2, "running": 1}
        assert counters["kv_blocks"] == {"used": 3, "free": 4}
        span = next(e for e in doc["traceEvents"]
                    if e.get("cat") == "request")
        assert span["args"]["incomplete"] is True
        assert validate_trace.validate_chrome_trace(doc) == []

    def test_empty_events_render_empty_doc(self):
        doc = render_serving_trace([])
        assert doc["traceEvents"] == []
        assert validate_trace.validate_chrome_trace(doc) == []


# --------------------------------------------------------------------- #
# the schema validator (negatives: drift must not pass silently)


class TestValidator:

    def test_chrome_negatives(self):
        bad_ph = {"traceEvents": [{"ph": "Z", "name": "x", "ts": 0}]}
        assert any("unknown ph" in e
                   for e in validate_trace.validate_chrome_trace(bad_ph))
        bad_counter = {"traceEvents": [
            {"ph": "C", "name": "q", "ts": 0, "pid": 1, "tid": 0,
             "args": {"v": "high"}}]}
        assert any("counter args" in e
                   for e in validate_trace.validate_chrome_trace(bad_counter))
        two_spans = {"traceEvents": [
            {"ph": "X", "cat": "request", "name": "request 0", "ts": 0,
             "dur": 10, "pid": 1, "tid": 0},
            {"ph": "X", "cat": "request", "name": "request 0b", "ts": 20,
             "dur": 10, "pid": 1, "tid": 0}]}
        assert any("request spans" in e
                   for e in validate_trace.validate_chrome_trace(two_spans))
        outside = {"traceEvents": [
            {"ph": "X", "cat": "request", "name": "request 0", "ts": 100,
             "dur": 10, "pid": 1, "tid": 0},
            {"ph": "X", "name": "decode", "ts": 500, "dur": 10,
             "pid": 1, "tid": 0}]}
        assert any("outside its request span" in e
                   for e in validate_trace.validate_chrome_trace(outside))
        assert validate_trace.validate_chrome_trace([]) \
            == ["top level must be an object with a 'traceEvents' list"]

    def test_events_jsonl_negatives(self):
        bad_kind = [json.dumps({"ts_ns": 1, "kind": "req.bogus"})]
        assert any("unknown kind" in e
                   for e in validate_trace.validate_events_jsonl(bad_kind))
        bad_ts = [json.dumps({"ts_ns": "soon", "kind": "req.admit"})]
        assert any("ts_ns" in e
                   for e in validate_trace.validate_events_jsonl(bad_ts))
        assert validate_trace.validate_events_jsonl([]) \
            == ["no events (empty file)"]
        ok = [json.dumps({"ts_ns": 5, "kind": "req.admit", "rid": 1})]
        assert validate_trace.validate_events_jsonl(ok) == []

    def test_auto_sniff(self, tmp_path):
        chrome = tmp_path / "t.json"
        chrome.write_text(json.dumps({"traceEvents": []}))
        assert validate_trace.validate_path(str(chrome)) == []
        jsonl = tmp_path / "e.jsonl"
        jsonl.write_text(json.dumps({"ts_ns": 1, "kind": "req.admit"}) + "\n")
        assert validate_trace.validate_path(str(jsonl)) == []


# --------------------------------------------------------------------- #
# serving events end-to-end (the tentpole acceptance pins)


class TestServingEvents:

    def test_deterministic_sequence_with_preemption_and_cache_hit(self):
        # 5 blocks of 8 for two streams that outgrow them: deterministic
        # preemption; the victim's re-admission probes the cache and HITS
        # its own still-cold blocks (prefix caching is auto-on)
        engine = serving_engine(max_num_blocks=5)
        prompts = [np.arange(1, 6, dtype=np.int32),
                   np.arange(10, 21, dtype=np.int32)]
        outs = engine.generate_batch(prompts, max_new_tokens=10)
        evs = get_flight_recorder().snapshot()
        kinds = Counter(e.kind for e in evs)
        assert kinds["serve.begin"] == 1 and kinds["serve.end"] == 1
        assert kinds["req.enqueue"] == 2 and kinds["req.retire"] == 2
        assert kinds["req.preempt"] >= 1
        hits = [e for e in evs if e.kind == "req.cache_hit"]
        assert hits and all(e.data["tokens"] > 0 for e in hits)
        # per-request lifecycle: ONE enqueue and ONE retire per rid;
        # admits == 1 + that rid's preemptions; events in causal order
        for rid in (0, 1):
            seq = [e.kind for e in evs if e.rid == rid]
            assert seq[0] == "req.enqueue" and seq[-1] == "req.retire"
            assert seq.count("req.enqueue") == 1
            assert seq.count("req.retire") == 1
            assert seq.count("req.admit") == 1 + seq.count("req.preempt")
        # decode ticks carry the fused rid set
        ticks = [e for e in evs if e.kind == "decode.tick"]
        assert ticks and all(set(e.data["rids"]) <= {0, 1} for e in ticks)
        # the traced run still produces the exact greedy tokens
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=10)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])

    def test_export_serving_trace_validates(self, tmp_path):
        # THE acceptance pin: chrome-trace export with exactly one
        # admission->retire span per request (incl. the preempted one),
        # child slices for every prefill chunk / decode tick / COW copy,
        # and queue-depth + KV-block counter tracks — all validated by
        # tools/validate_trace.py
        engine = serving_engine(max_num_blocks=5)
        prompts = [np.arange(1, 6, dtype=np.int32),
                   np.arange(10, 21, dtype=np.int32),
                   np.arange(30, 33, dtype=np.int32)]
        engine.generate_batch(prompts, max_new_tokens=10)
        path = str(tmp_path / "serving.json")
        assert engine.export_serving_trace(path) == path
        assert validate_trace.validate_path(path) == []
        doc = json.loads(Path(path).read_text())
        spans = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
        assert sorted(e["tid"] for e in spans) == [0, 1, 2]
        evs = get_flight_recorder().snapshot()
        child_names = Counter(e["name"] for e in doc["traceEvents"]
                              if e["ph"] == "X" and e.get("cat") == "serving")
        # every recorded compute event has its child slice (decode ticks
        # fan out to one slice per fused rid)
        n_prefill = sum(1 for e in evs if e.kind == "req.prefill")
        n_chunk = sum(1 for e in evs if e.kind == "req.prefill_chunk")
        n_cow = sum(1 for e in evs if e.kind == "req.cow_copy")
        n_decode = sum(len(e.data["rids"]) for e in evs
                       if e.kind == "decode.tick")
        assert child_names.get("prefill", 0) == n_prefill
        assert child_names.get("prefill_chunk", 0) == n_chunk
        assert child_names.get("cow_copy", 0) == n_cow
        assert child_names.get("decode", 0) == n_decode
        assert n_decode > 0
        counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
        assert counters == {"queue_depth", "kv_blocks"}
        # rids stay unique across generate_batch calls: a second serve
        # adds three MORE request tracks instead of colliding with 0-2
        engine.generate_batch(prompts, max_new_tokens=4)
        engine.export_serving_trace(path)
        doc = json.loads(Path(path).read_text())
        spans = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
        assert sorted(e["tid"] for e in spans) == [0, 1, 2, 3, 4, 5]
        assert validate_trace.validate_path(path) == []

    def test_disabled_mode_allocates_nothing(self, monkeypatch):
        # events off (telemetry on): the scheduler/engine hot paths gate
        # at one None check — pinned by making Event allocation explode
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2})
        assert engine._events is None

        def boom(*a, **k):
            raise AssertionError("Event allocated with events disabled")

        monkeypatch.setattr(events_mod, "Event", boom)
        prompts = [np.arange(1, 6, dtype=np.int32),
                   np.arange(10, 21, dtype=np.int32)]
        outs = engine.generate_batch(prompts, max_new_tokens=6)
        assert len(outs) == 2
        assert len(get_flight_recorder()) == 0
        with pytest.raises(ValueError, match="telemetry.events"):
            engine.export_serving_trace("/tmp/nope.json")

    def test_full_prefix_rehit_emits_cow_and_chunk(self, tmp_path):
        # a fully-cached re-served prompt: COW split + exactly one tail
        # chunk ride the event stream and the exported trace
        engine = serving_engine()
        prompt = np.arange(16, dtype=np.int32)      # 2 full blocks
        engine.generate_batch([prompt], max_new_tokens=4)
        get_flight_recorder().clear()
        engine.generate_batch([prompt], max_new_tokens=4)
        evs = get_flight_recorder().snapshot()
        kinds = Counter(e.kind for e in evs)
        assert kinds["req.cache_hit"] == 1
        assert kinds["req.cow_copy"] == 1
        assert kinds["req.prefill_chunk"] == 1
        assert kinds["req.prefill"] == 0
        hit = next(e for e in evs if e.kind == "req.cache_hit")
        assert hit.data["tokens"] == 15             # target - 1
        path = engine.export_serving_trace(str(tmp_path / "rehit.json"))
        assert validate_trace.validate_path(path) == []


# --------------------------------------------------------------------- #
# training + checkpoint events, bundles


class TestTrainingEvents:

    def test_train_step_and_ckpt_phase_events(self, tmp_path):
        engine, batch = train_engine({"enabled": True, "events": True})
        for _ in range(3):
            float(engine.train_batch(batch()))
        engine.save_checkpoint(str(tmp_path / "ckpt"), asynchronous=False)
        evs = get_flight_recorder().snapshot()
        kinds = Counter(e.kind for e in evs)
        assert kinds["train.step"] == 3
        assert kinds["ckpt.snapshot"] == 1
        assert kinds["ckpt.serialize"] == 1
        assert kinds["ckpt.commit"] == 1
        steps = [e.step for e in evs if e.kind == "train.step"]
        assert steps == [1, 2, 3]
        commit = next(e for e in evs if e.kind == "ckpt.commit")
        assert commit.data["bytes"] > 0 and commit.data["tag"]
        engine.destroy()

    def test_ckpt_retry_event_on_transient_fault(self, tmp_path):
        from deepspeed_tpu.utils import fault_injection
        engine, batch = train_engine({"enabled": True, "events": True})
        float(engine.train_batch(batch()))
        engine._config.checkpoint_config.retry_backoff_s = 0.0
        inj = fault_injection.FaultInjector()
        inj.fail_writes(errno_code=28, path_substr="state.npz", count=1)
        with fault_injection.inject(inj):
            engine.save_checkpoint(str(tmp_path / "ckpt"),
                                   asynchronous=False)
        retries = [e for e in get_flight_recorder().snapshot()
                   if e.kind == "ckpt.retry"]
        assert len(retries) == 1
        assert retries[0].data["attempt"] == 1
        assert "28" in retries[0].data["error"] \
            or "space" in retries[0].data["error"].lower()
        engine.destroy()

    def test_emergency_save_ships_events_jsonl(self, tmp_path):
        engine, batch = train_engine({"enabled": True, "events": True})
        float(engine.train_batch(batch()))
        save_dir = str(tmp_path / "emergency")
        engine.emergency_save(save_dir)
        p = os.path.join(save_dir, "events.jsonl")
        assert os.path.isfile(p)
        assert validate_trace.validate_path(p) == []
        kinds = [json.loads(line)["kind"]
                 for line in Path(p).read_text().splitlines()]
        assert "train.step" in kinds and "ckpt.snapshot" in kinds
        engine.destroy()

    def test_events_off_training_hot_path_allocates_nothing(
            self, monkeypatch):
        engine, batch = train_engine({"enabled": True})   # events off
        assert engine._tel_events is None

        def boom(*a, **k):
            raise AssertionError("Event allocated with events disabled")

        monkeypatch.setattr(events_mod, "Event", boom)
        float(engine.train_batch(batch()))
        assert len(get_flight_recorder()) == 0
        engine.destroy()

    def test_anomaly_bundle_contains_events_jsonl(self, tmp_path):
        from deepspeed_tpu.monitor.config import HealthConfig
        from deepspeed_tpu.monitor.health import HealthMonitor, StepHealth
        from deepspeed_tpu.monitor.metrics import MetricsRegistry
        rec = get_flight_recorder()
        rec.enable()
        rec.emit("train.step", step=1, dur_ns=1000)
        cfg = HealthConfig(enabled=True, action="dump",
                           dump_dir=str(tmp_path / "dumps"))
        mon = HealthMonitor(cfg, registry=MetricsRegistry())
        fired = mon.observe_step(StepHealth(step=1, loss=float("nan")))
        assert "nonfinite" in fired
        bundles = list((tmp_path / "dumps").iterdir())
        assert len(bundles) == 1
        p = bundles[0] / "events.jsonl"
        assert p.is_file()
        assert validate_trace.validate_path(str(p)) == []


# --------------------------------------------------------------------- #
# on-demand device profiling


class TestProfileWindow:

    def _patch_profiler(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, **k: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop",)))
        return calls

    def test_window_arms_starts_and_stops(self, monkeypatch):
        calls = self._patch_profiler(monkeypatch)
        w = ProfileWindow("/tmp/prof_a")
        w.tick()                       # nothing armed: no-op
        w.arm(2, log_dir="/tmp/prof_b")
        for _ in range(4):
            w.tick()
        assert calls == [("start", "/tmp/prof_b"), ("stop",)]
        assert w.captures == 1 and not w.active
        with pytest.raises(ValueError, match=">= 1"):
            w.arm(0)

    def test_config_armed_window_with_start_step(self, monkeypatch):
        calls = self._patch_profiler(monkeypatch)
        w = ProfileWindow("/tmp/prof_c", start_step=2, num_steps=1)
        w.tick(); w.tick()             # steps 0, 1: before the window
        assert calls == []
        w.tick()                       # step 2: start
        assert calls == [("start", "/tmp/prof_c")] and w.active
        w.tick()                       # step 3: window over -> stop
        assert calls[-1] == ("stop",)

    def test_engine_profile_arms_via_train_batch(self, monkeypatch):
        calls = self._patch_profiler(monkeypatch)
        engine, batch = train_engine()           # telemetry OFF: still works
        assert engine._profiler is None
        engine.profile(steps=2, log_dir="/tmp/prof_d")
        for _ in range(4):
            float(engine.train_batch(batch()))
        assert calls == [("start", "/tmp/prof_d"), ("stop",)]
        engine.destroy()

    def test_config_profile_block_builds_window(self):
        engine, _ = train_engine({"enabled": True,
                                  "profile": {"start_step": 1,
                                              "num_steps": 2,
                                              "dir": "/tmp/prof_e"}})
        assert engine._profiler is not None
        assert engine._profiler._armed == {"start": 1, "steps": 2,
                                           "dir": "/tmp/prof_e"}
        engine.destroy()

    def test_destroy_stops_dangling_capture(self, monkeypatch):
        calls = self._patch_profiler(monkeypatch)
        engine, batch = train_engine()
        engine.profile(steps=100)
        float(engine.train_batch(batch()))       # start, never finishes
        assert calls[-1][0] == "start"
        engine.destroy()
        assert calls[-1] == ("stop",)


# --------------------------------------------------------------------- #
# CLI surfaces


class TestCli:

    def test_dscli_trace_validate(self, tmp_path, capsys):
        from deepspeed_tpu.cli import _trace
        good = tmp_path / "good.json"
        good.write_text(json.dumps({"traceEvents": [
            {"ph": "X", "name": "s", "ts": 0, "dur": 1,
             "pid": 0, "tid": 0}]}))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traceEvents": [{"ph": "Z"}]}))
        assert _trace(["--validate", str(good)]) == 0
        assert _trace(["--validate", str(good), str(bad)]) == 1
        out = capsys.readouterr().out
        assert "OK" in out and "unknown ph" in out

    def test_dscli_profile_chrome_summary(self, tmp_path, capsys):
        from deepspeed_tpu.cli import _profile
        trace = tmp_path / "t.json"
        trace.write_text(json.dumps({"traceEvents": [
            {"ph": "X", "name": "train_batch", "ts": 0, "dur": 2000,
             "pid": 0, "tid": 0},
            {"ph": "X", "name": "train_batch", "ts": 3000, "dur": 1000,
             "pid": 0, "tid": 0},
            {"ph": "X", "name": "fwd", "ts": 0, "dur": 500,
             "pid": 0, "tid": 0}]}))
        assert _profile([str(trace)]) == 0
        out = capsys.readouterr().out
        assert "train_batch" in out and "2 " in out

    def test_dscli_profile_logdir_inventory(self, tmp_path, capsys):
        from deepspeed_tpu.cli import _profile
        run = tmp_path / "plugins" / "profile" / "2026_08_03_12_00_00"
        run.mkdir(parents=True)
        (run / "host0.xplane.pb").write_bytes(b"\0" * 128)
        assert _profile([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "1 profiler run(s)" in out and "host0.xplane.pb" in out
        empty = tmp_path / "empty"
        empty.mkdir()
        assert _profile([str(empty)]) == 1

    def test_dscli_health_json(self, tmp_path, capsys):
        from deepspeed_tpu.monitor.health import health_cli
        sink = tmp_path / "telemetry.jsonl"
        rec = {"ts": 1000.0, "step": 7,
               "counters": {"train/steps": 7,
                            'health/anomalies{type="loss_spike"}': 2},
               "gauges": {"train/loss": 3.5, "train/mfu": 0.4,
                          "mem/host_rss_bytes": 1024},
               "histograms": {"train/step_time_ms":
                              {"count": 7, "mean": 100.0, "p50": 99.0,
                               "p99": 120.0}}}
        prev = {"ts": 990.0, "step": 5, "counters": {"train/steps": 5},
                "gauges": {}, "histograms": {}}
        sink.write_text(json.dumps(prev) + "\n" + json.dumps(rec) + "\n")
        assert health_cli(["--json", str(sink)]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["step"] == 7
        assert out["train"]["steps"] == 7 and out["train"]["mfu"] == 0.4
        assert out["train"]["steps_per_sec"] == pytest.approx(0.2)
        assert out["loss"]["loss"] == 3.5
        assert out["anomalies"] == {"loss_spike": 2}
        assert out["memory"]["host_rss_bytes"] == 1024
        assert out["snapshot"]["step"] == 7
        # missing sink: machine-readable error, rc 1
        assert health_cli(["--json", str(tmp_path / "nope.jsonl")]) == 1
        assert "error" in json.loads(capsys.readouterr().out)


# --------------------------------------------------------------------- #
# StepTracer metadata + bench skip records (satellites)


class TestStepTracerMetadata:

    def test_export_names_pid_and_tid_tracks(self, tmp_path):
        tracer = StepTracer(use_accelerator=False)
        with tracer.span("fwd"):
            pass
        path = tracer.export_chrome_trace(str(tmp_path / "host.json"))
        assert validate_trace.validate_path(path) == []
        doc = json.loads(Path(path).read_text())
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        procs = [e for e in metas if e["name"] == "process_name"]
        threads = [e for e in metas if e["name"] == "thread_name"]
        assert procs[0]["args"]["name"] == "deepspeed_tpu host"
        assert threads and threads[0]["args"]["name"] == "MainThread"
        span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert span["tid"] == threads[0]["tid"]
