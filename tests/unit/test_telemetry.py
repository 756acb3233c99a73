"""Unified telemetry: metrics registry, compile watchdog, scheduler
serving metrics, engine MFU/tokens-per-sec, and the tier-1 smoke test
that one train step + one ``generate_batch`` under ``telemetry: on``
yields a non-empty, schema-valid snapshot."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.block_allocator import BlockAllocator
from deepspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                               ServingTelemetry)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor.metrics import (MetricsRegistry, get_registry,
                                           validate_snapshot)
from deepspeed_tpu.monitor.trace import CompileWatchdog, StepTracer


@pytest.fixture(autouse=True)
def clean_state():
    """Fresh mesh + fresh GLOBAL registry/watchdog per test (engines
    create their metric families at init, so the reset must come first)."""
    from deepspeed_tpu.monitor.trace import get_compile_watchdog
    dist.set_mesh(None)
    get_registry().reset()
    get_registry().set_enabled(True)
    get_compile_watchdog().reset()
    yield
    dist.set_mesh(None)
    get_registry().reset()
    get_registry().set_enabled(True)
    get_compile_watchdog().reset()


def tiny_model(**over):
    base = dict(vocab_size=64, n_layer=2, n_head=2, d_model=32, d_ff=64,
                max_seq=64, remat=False, attention_backend="xla")
    base.update(over)
    return CausalLM(TransformerConfig(**base))


def make_train_engine(telemetry="on", **tel_over):
    model = tiny_model(max_seq=32)
    params = model.init_params(jax.random.key(0))
    tel = {"enabled": True, **tel_over} if telemetry == "on" else telemetry
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "mesh": {"dp": -1},            # all 8 virtual CPU devices
        "steps_per_print": 0,
        "telemetry": tel,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                               model_parameters=params,
                                               config=config)
    return engine


def train_batch(engine):
    dp = dist.get_world_size(dist.data_parallel_axes(engine.mesh))
    rows = engine.train_micro_batch_size_per_gpu() * \
        engine.gradient_accumulation_steps() * dp
    rng = np.random.default_rng(0)
    return {"input_ids": rng.integers(0, 64, size=(rows, 32)).astype(np.int32)}


# --------------------------------------------------------------------- #
# metrics registry


class TestMetricsRegistry:

    def test_counter_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        lc = reg.counter("ops", labelnames=("op",))
        lc.labels(op="a").inc()
        lc.labels(op="b").inc(4)
        lc.labels(op="a").inc()
        snap = reg.snapshot()
        assert snap["counters"]['ops{op="a"}'] == 2
        assert snap["counters"]['ops{op="b"}'] == 4
        with pytest.raises(ValueError, match="negative"):
            c.inc(-1)
        with pytest.raises(ValueError, match="labels"):
            lc.labels(wrong="x")

    def test_gauge(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert reg.snapshot()["gauges"]["depth"] == 5.0

    def test_reregister_type_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="re-registered"):
            reg.gauge("x")

    def test_histogram_streaming_quantiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        rng = np.random.default_rng(0)
        data = rng.lognormal(mean=2.0, sigma=1.0, size=4000)
        for v in data:
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 4000
        assert s["min"] == pytest.approx(data.min())
        assert s["max"] == pytest.approx(data.max())
        assert s["mean"] == pytest.approx(data.mean(), rel=1e-6)
        # geometric buckets at ratio 2**0.25: ~±9% relative quantile error
        for q, key in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
            assert s[key] == pytest.approx(np.percentile(data, q * 100),
                                           rel=0.15)

    def test_histogram_empty_and_single(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        assert h.summary()["count"] == 0
        h.observe(5.0)
        s = h.summary()
        assert s["count"] == 1 and s["p50"] == pytest.approx(5.0)

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("train/steps", "steps run").inc(3)
        reg.gauge("train/mfu").set(0.5)
        h = reg.histogram("lat_ms", labelnames=("op",))
        h.labels(op="ar").observe(10.0)
        text = reg.to_prometheus()
        assert "# TYPE train_steps counter" in text
        assert "train_steps 3" in text
        assert "# HELP train_steps steps run" in text
        assert "train_mfu 0.5" in text
        assert '# TYPE lat_ms histogram' in text
        assert 'lat_ms_bucket{op="ar",le="+Inf"} 1' in text
        assert 'lat_ms_count{op="ar"} 1' in text

    def test_jsonl_sink(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        path = str(tmp_path / "t" / "telemetry.jsonl")
        reg.write_jsonl(path, step=1)
        reg.counter("c").inc()
        reg.write_jsonl(path, step=2, extra={"tag": "x"})
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        assert lines[0]["step"] == 1 and lines[0]["counters"]["c"] == 1
        assert lines[1]["counters"]["c"] == 2 and lines[1]["tag"] == "x"
        for line in lines:
            validate_snapshot(line)

    def test_monitor_fanout(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(4.0)

        class FakeMonitor:
            enabled = True
            events = []

            def write_events(self, ev):
                self.events.extend(ev)

        mon = FakeMonitor()
        reg.publish(mon, step=7)
        names = {e[0] for e in mon.events}
        assert ("Telemetry/c", 2.0, 7) in mon.events
        assert ("Telemetry/g", 1.5, 7) in mon.events
        assert "Telemetry/h/p99" in names and "Telemetry/h/count" in names

    def test_snapshot_schema_validation(self):
        reg = MetricsRegistry()
        reg.histogram("h").observe(1.0)
        validate_snapshot(reg.snapshot())
        with pytest.raises(ValueError, match="section"):
            validate_snapshot({"counters": {}})
        with pytest.raises(ValueError, match="not numeric"):
            validate_snapshot({"counters": {"x": "nan?"}, "gauges": {},
                              "histograms": {}})

    def test_disabled_mode_is_noop_and_never_touches_jax(self, monkeypatch):
        """With the registry disabled every record op must return after a
        flag check: nothing recorded, and no device work — assert by
        making every sync entry point explode."""
        def boom(*a, **k):
            raise AssertionError("registry touched jax in disabled mode")

        monkeypatch.setattr(jax, "effects_barrier", boom)
        monkeypatch.setattr(jax, "block_until_ready", boom)
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("c")
        g = reg.gauge("g")
        h = reg.histogram("h")
        for _ in range(100):
            c.inc()
            g.set(1.0)
            h.observe(3.3)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 0
        assert snap["gauges"]["g"] == 0.0
        assert snap["histograms"]["h"]["count"] == 0
        reg.set_enabled(True)
        c.inc()
        assert reg.snapshot()["counters"]["c"] == 1


# --------------------------------------------------------------------- #
# compile watchdog + tracer


class TestCompileWatchdog:

    def test_counts_compiles_and_records_shapes(self):
        reg = MetricsRegistry()
        wd = CompileWatchdog(registry=reg)
        f = wd.jit(lambda x: x * 2, name="dbl")
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))          # cache hit: not a compile
        f(jnp.ones((2, 2)))        # new shape: compile
        assert wd.compile_count("dbl") == 2
        assert wd.compile_count() == 2
        shapes = [e["shapes"] for e in wd.events]
        assert any("float32[4]" in s for s in shapes)
        assert any("float32[2,2]" in s for s in shapes)
        snap = reg.snapshot()
        assert snap["counters"]['compile/count{fn="dbl"}'] == 2
        assert snap["histograms"]['compile/time_ms{fn="dbl"}']["count"] == 2

    def test_watch_preserves_outputs(self):
        wd = CompileWatchdog(registry=MetricsRegistry())
        f = wd.watch(jax.jit(lambda x: (x + 1, x * 2)), "pair")
        a, b = f(jnp.asarray(3.0))
        assert float(a) == 4.0 and float(b) == 6.0
        assert f.inner._cache_size() == 1

    def test_storm_warning(self, monkeypatch):
        # the project logger has propagate=False: capture the call directly
        from deepspeed_tpu.monitor import trace as trace_mod
        warnings = []
        monkeypatch.setattr(trace_mod.logger, "warning",
                            lambda msg, *a, **k: warnings.append(str(msg)))
        wd = CompileWatchdog(registry=MetricsRegistry(), storm_threshold=3)
        f = wd.jit(lambda x: x + 1, name="churn")
        for n in range(1, 6):
            f(jnp.ones((n,)))  # every call a fresh shape: 5 compiles
        assert any("recompilation storm" in w for w in warnings)
        assert wd.compile_count("churn") == 5

    def test_tracer_chrome_export(self, tmp_path):
        tr = StepTracer(use_accelerator=False)
        with tr.span("fwd", step=1):
            pass
        tr.add_event("bwd", 0.0, 0.002)
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        # metadata (process/thread names) precedes the spans; the span
        # payload itself is unchanged
        spans = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        assert [e["name"] for e in spans] == ["fwd", "bwd"]
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in spans)


# --------------------------------------------------------------------- #
# scheduler serving-metric invariants (no model: drive the state machine)


def drive(sched, max_steps=200):
    """Run the scheduler to completion with deterministic fake tokens."""
    tok = 0
    for _ in range(max_steps):
        action = sched.next_action()
        if action is None:
            return
        kind, payload = action
        if kind == "prefill":
            sched.record_prefill(payload, tok)
        else:
            for r in list(payload):
                sched.record_decode(r, tok)
                tok += 1
        tok += 1
    raise AssertionError("scheduler did not finish")


class TestSchedulerServingMetrics:

    def make(self, num_blocks=9, block_size=8, max_running=2, n_max=8):
        reg = MetricsRegistry()
        tel = ServingTelemetry(reg)
        sched = ContinuousBatchingScheduler(
            BlockAllocator(num_blocks, block_size), max_running, n_max,
            telemetry=tel)
        return sched, reg

    def test_ttft_once_per_request_and_counts(self):
        sched, reg = self.make()
        for n in (5, 11, 3):
            sched.add_request(np.arange(n, dtype=np.int32), max_new=4)
        drive(sched)
        snap = reg.snapshot()
        # TTFT exactly once per request; everything else is a TPOT sample
        assert snap["histograms"]["serving/ttft_ms"]["count"] == 3
        gen = snap["counters"]["serving/generated_tokens"]
        assert gen == 3 * 4
        assert snap["histograms"]["serving/tpot_ms"]["count"] == gen - 3
        assert snap["counters"]["serving/requests"] == 3
        assert snap["counters"]["serving/finished_requests"] == 3
        assert snap["counters"]["serving/preemptions"] == 0
        # all retired: occupancy gauges return to zero
        assert snap["gauges"]["serving/queue_depth"] == 0
        assert snap["gauges"]["serving/running"] == 0
        assert snap["gauges"]["serving/kv_block_utilization"] == 0

    def test_preemption_counter_matches_evictions_and_ttft_not_rerecorded(self):
        # pool of 4 allocatable blocks x 4 tokens for two 6-token prompts
        # generating 8: eviction pressure guaranteed
        sched, reg = self.make(num_blocks=5, block_size=4, max_running=2)
        sched.add_request(np.arange(6, dtype=np.int32), max_new=8)
        sched.add_request(np.arange(6, dtype=np.int32), max_new=8)
        drive(sched)
        snap = reg.snapshot()
        evictions = sum(r.preemptions for r in sched.finished)
        assert evictions > 0
        assert snap["counters"]["serving/preemptions"] == evictions
        # recompute counter saw each evicted prefix
        assert snap["counters"]["serving/recompute_tokens"] >= 6 * evictions
        # TTFT still once per REQUEST even though preempted requests
        # prefill again on re-admission
        assert snap["histograms"]["serving/ttft_ms"]["count"] == 2
        assert snap["counters"]["serving/finished_requests"] == 2

    def test_step_counters_and_kv_utilization_bounds(self):
        sched, reg = self.make()
        sched.add_request(np.arange(4, dtype=np.int32), max_new=3)
        seen_util = []
        tok = 0
        while True:
            action = sched.next_action()
            util = reg.snapshot()["gauges"]["serving/kv_block_utilization"]
            seen_util.append(util)
            assert 0.0 <= util <= 1.0
            if action is None:
                break
            kind, payload = action
            if kind == "prefill":
                sched.record_prefill(payload, tok)
            else:
                for r in list(payload):
                    sched.record_decode(r, tok)
            tok += 1
        snap = reg.snapshot()
        assert snap["counters"]["serving/prefill_steps"] == 1
        assert snap["counters"]["serving/decode_steps"] == 2  # 3 tokens: 1 prefill + 2 decodes
        assert max(seen_util) > 0.0

    def test_no_telemetry_scheduler_unchanged(self):
        # telemetry=None: the state machine runs identically with zero hooks
        sched = ContinuousBatchingScheduler(BlockAllocator(9, 8), 2, 8)
        sched.add_request(np.arange(5, dtype=np.int32), max_new=3)
        drive(sched)
        assert len(sched.finished) == 1


# --------------------------------------------------------------------- #
# engine wiring


class TestEngineTelemetry:

    def test_train_step_records_step_time_tokens_mfu_compiles(self):
        engine = make_train_engine(peak_tflops_per_chip=1.0)
        engine.train_batch(train_batch(engine))
        snap = engine.telemetry_snapshot()
        validate_snapshot(snap)
        assert snap["histograms"]["train/step_time_ms"]["count"] == 1
        assert snap["counters"]["train/steps"] == 1
        assert snap["counters"]["train/tokens"] == 8 * 32
        assert snap["gauges"]["train/tokens_per_sec"] > 0
        assert snap["gauges"]["train/mfu"] > 0          # peak pinned by config
        assert snap["gauges"]["train/achieved_tflops_per_chip"] > 0
        by_fn = snap["compile"]["by_fn"]
        assert by_fn.get("engine.train_batch[gas=1]") == 1
        assert snap["counters"][
            'compile/count{fn="engine.train_batch[gas=1]"}'] == 1
        # second identical step: no recompilation
        engine.train_batch(train_batch(engine))
        assert engine.telemetry_snapshot()["compile"]["by_fn"][
            "engine.train_batch[gas=1]"] == 1

    def test_trio_phase_breakdown(self):
        engine = make_train_engine()
        engine.forward(train_batch(engine))
        engine.backward()
        engine.step()
        snap = engine.telemetry_snapshot()
        hists = snap["histograms"]
        for phase in ("fwd", "bwd", "step"):
            assert hists[f'train/phase_time_ms{{phase="{phase}"}}']["count"] == 1

    def test_jsonl_snapshot_cadence(self, tmp_path):
        path = str(tmp_path / "tel.jsonl")
        engine = make_train_engine(jsonl_path=path, steps_per_snapshot=1)
        engine.train_batch(train_batch(engine))
        engine.train_batch(train_batch(engine))
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        for line in lines:
            validate_snapshot(line)
        assert lines[1]["counters"]["train/steps"] == 2

    def test_telemetry_off_is_inert(self):
        engine = make_train_engine(telemetry=False)
        engine.train_batch(train_batch(engine))
        assert engine.telemetry_snapshot() == {}
        # compiled entry points are NOT wrapped (no watchdog indirection)
        fn = engine._train_batch_jit[1]
        assert not hasattr(fn, "inner")

    @pytest.mark.slow  # StepTracer export is covered cheaply in
    # TestCompileWatchdog::test_tracer_chrome_export; this exercises the
    # engine plumbing end to end
    def test_export_trace(self, tmp_path):
        engine = make_train_engine()
        engine.train_batch(train_batch(engine))
        path = engine.export_trace(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert any(e["name"] == "train_batch" for e in doc["traceEvents"])


class TestServingTelemetrySmoke:
    """Tier-1 smoke: one train step + one generate_batch under
    ``telemetry: on`` -> non-empty, schema-valid snapshot carrying every
    acceptance series."""

    def _prompts(self, lens=(5, 11, 3)):
        rng = np.random.default_rng(0)
        return [rng.integers(0, 64, size=n).astype(np.int32) for n in lens]

    def test_generate_batch_snapshot(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2})
        outs = engine.generate_batch(self._prompts(), max_new_tokens=4)
        assert len(outs) == 3
        snap = engine.telemetry_snapshot()
        validate_snapshot(snap)
        assert snap["histograms"]["serving/ttft_ms"]["count"] == 3
        assert snap["histograms"]["serving/tpot_ms"]["count"] == 3 * 4 - 3
        assert snap["counters"]["serving/prefill_steps"] == 3
        assert snap["counters"]["serving/decode_steps"] > 0
        assert snap["counters"]["serving/preemptions"] == 0
        assert "serving/queue_depth" in snap["gauges"]
        assert "serving/kv_block_utilization" in snap["gauges"]
        assert snap["compile"]["by_fn"].get("inference.paged_decode") == 1

    @pytest.mark.slow  # scheduler-level test pins the counter invariant;
    # this adds the engine-level token-identity check under preemption
    def test_eviction_pressure_counters(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2, "max_num_blocks": 5})
        prompts = self._prompts((5, 11))
        outs = engine.generate_batch(prompts, max_new_tokens=10)
        # greedy identity preserved under telemetry + eviction
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=10)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])
        snap = engine.telemetry_snapshot()
        assert snap["counters"]["serving/preemptions"] > 0
        assert snap["counters"]["serving/recompute_tokens"] > 0
        assert snap["histograms"]["serving/ttft_ms"]["count"] == 2

    def test_full_smoke_train_plus_serve(self):
        """The acceptance checklist in one snapshot: step-time breakdown,
        tokens/sec, MFU, compile count, TTFT/TPOT, queue depth, KV-block
        utilization, preemption counters."""
        train = make_train_engine(peak_tflops_per_chip=1.0)
        train.train_batch(train_batch(train))
        dist.set_mesh(None)
        serve = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2})
        serve.generate_batch(self._prompts((4, 7)), max_new_tokens=3)
        snap = serve.telemetry_snapshot()   # shared global registry
        validate_snapshot(snap)
        assert snap  # non-empty
        required_hists = ("train/step_time_ms", "serving/ttft_ms",
                          "serving/tpot_ms")
        for k in required_hists:
            assert snap["histograms"][k]["count"] > 0, k
        for k in ("train/tokens_per_sec", "train/mfu",
                  "serving/queue_depth", "serving/kv_block_utilization"):
            assert k in snap["gauges"], k
        assert snap["gauges"]["train/mfu"] > 0
        for k in ("train/steps", "serving/preemptions"):
            assert k in snap["counters"], k
        assert snap["compile"]["total"] > 0


# --------------------------------------------------------------------- #
# satellites


class TestSatellites:

    def test_csv_monitor_groups_events_per_file(self, tmp_path):
        from deepspeed_tpu.monitor.config import CSVConfig
        from deepspeed_tpu.monitor.monitor import csvMonitor
        mon = csvMonitor(CSVConfig(enabled=True, output_path=str(tmp_path),
                                   job_name="job"))
        mon.write_events([("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1),
                          ("Train/loss", 0.9, 2), ("Train/loss", 0.8, 3)])
        loss = open(tmp_path / "job" / "Train_loss.csv").read().splitlines()
        assert loss == ["step,value", "1,1.0", "2,0.9", "3,0.8"]
        lr = open(tmp_path / "job" / "Train_lr.csv").read().splitlines()
        assert lr == ["step,value", "1,0.1"]
        # append across calls keeps one header
        mon.write_events([("Train/loss", 0.7, 4)])
        loss = open(tmp_path / "job" / "Train_loss.csv").read().splitlines()
        assert loss[0] == "step,value" and loss[-1] == "4,0.7"

    def test_model_times_resets_and_double_enable_guard(self):
        engine = deepspeed_tpu.init_inference(tiny_model(), dtype="fp32")
        with pytest.raises(RuntimeError, match="not enabled"):
            engine.model_times()
        engine.profile_model_time()
        tokens = np.arange(8, dtype=np.int32)[None, :]
        engine.forward(tokens)
        # double enable must NOT drop the recorded latency
        engine.profile_model_time()
        times = engine.model_times()
        assert len(times) == 1 and times[0] > 0
        assert engine.model_times() == []   # reset after read

    def test_throughput_timer_honors_batch_size_ramp(self, monkeypatch):
        from deepspeed_tpu.utils import timer as timer_mod
        clock = {"t": 0.0}

        def fake_clock():
            clock["t"] += 1.0
            return clock["t"]

        monkeypatch.setattr(timer_mod.time, "perf_counter", fake_clock)
        monkeypatch.setattr(timer_mod, "_device_synchronize", lambda: None)
        t = timer_mod.ThroughputTimer(batch_size=4, start_step=0,
                                      steps_per_output=100)
        for _ in range(2):          # 2 steps x 4 samples, 1s each
            t.start()
            t.stop(global_step=True)
        t.batch_size = 8            # dynamic reassignment (ramp-up)
        for _ in range(2):          # 2 steps x 8 samples, 1s each
            t.start()
            t.stop(global_step=True)
        # cumulative: (2*4 + 2*8) samples / 4s = 6.0 — NOT the buggy
        # current_batch_size/avg_step_time = 8.0
        assert t.avg_samples_per_sec() == pytest.approx(6.0)
        assert t.total_samples == 24

    def test_telemetry_config_parsing(self):
        from deepspeed_tpu.monitor.config import get_telemetry_config
        assert get_telemetry_config({}).enabled is False
        assert get_telemetry_config({"telemetry": "on"}).enabled is True
        assert get_telemetry_config({"telemetry": "off"}).enabled is False
        assert get_telemetry_config({"telemetry": True}).enabled is True
        cfg = get_telemetry_config(
            {"telemetry": {"enabled": True, "steps_per_snapshot": 5}})
        assert cfg.enabled and cfg.steps_per_snapshot == 5
        with pytest.raises(ValueError, match="telemetry"):
            get_telemetry_config({"telemetry": "sometimes"})

    def test_comms_logger_feeds_registry(self):
        from deepspeed_tpu.utils.comms_logging import CommsLogger
        cl = CommsLogger()
        cl.append("all_reduce", "all_reduce", latency=2.0,
                  msg_size=1024, n_ranks=4)
        cl.append("all_reduce", "all_reduce", latency=3.0,
                  msg_size=2048, n_ranks=4)
        snap = get_registry().snapshot()
        assert snap["counters"]['comm/ops{op="all_reduce"}'] == 2
        assert snap["counters"]['comm/bytes{op="all_reduce"}'] == 3072
        assert snap["histograms"]['comm/latency_ms{op="all_reduce"}'][
            "count"] == 2
