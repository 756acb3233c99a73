"""Live SLO telemetry plane: Prometheus exposition correctness (name
sanitization, label escaping, histogram ``_bucket``/``_sum``/``_count``
series, exemplars) validated through a minimal text-format parser,
``GET /metrics`` scraped DURING a live streamed completion (and 503 after
``stop()`` like ``/healthz``), the standalone exporter, the background
snapshot sampler (rotated JSONL + ring, zero device work), the burn-rate
SLO engine — THE acceptance pin: a deterministic trace replay drives a
p99-TTFT objective into breach, the alert fires exactly once per window,
lands in the flight recorder, and renders in ``dscli top`` /
``health_summary`` — the ``serving_metrics_steady`` compile-budget
contract (sampler + exporter beside a warm serving loop add ZERO
compiles), dslint DS009 (metrics-plane modules must not import jax), and
the ``events/dropped`` ring-loss gauges."""

import http.client
import importlib.util
import json
import math
import os
import sys
import textwrap
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.serve import (AsyncServingEngine,
                                           build_http_server)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor.config import get_telemetry_config
from deepspeed_tpu.monitor.events import (FlightRecorder,
                                          export_recorder_metrics)
from deepspeed_tpu.monitor.exporter import MetricsExporter
from deepspeed_tpu.monitor.health import (health_summary, multilabel_series,
                                          render_summary_table)
from deepspeed_tpu.monitor.metrics import (MetricsRegistry,
                                           parse_prometheus_text,
                                           validate_snapshot)
from deepspeed_tpu.monitor.sampler import MetricsSampler, sampler_from_config
from deepspeed_tpu.monitor.slo import (SloEngine, parse_objectives,
                                       serving_objectives, slo_from_config)
from deepspeed_tpu.monitor.top import (render_top, snapshot_from_prometheus,
                                       top_cli)

_TOOLS = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                      "..", "..", "tools"))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

_VT_PATH = Path(__file__).resolve().parents[2] / "tools" / "validate_trace.py"
_spec = importlib.util.spec_from_file_location("validate_trace", _VT_PATH)
validate_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(validate_trace)


def tiny_model(**over):
    base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                max_seq=64, remat=False)
    base.update(over)
    return CausalLM(TransformerConfig(**base))


# --------------------------------------------------------------------- #
# Prometheus exposition correctness (satellite: parser-validated)


class TestPrometheusExposition:

    def test_name_sanitization(self):
        reg = MetricsRegistry()
        reg.counter("serving/requests", "total").inc(3)
        reg.gauge("mem/hbm-bytes.in use").set(1)
        txt = reg.to_prometheus()
        assert "# TYPE serving_requests counter" in txt
        assert "serving_requests 3" in txt
        assert "mem_hbm_bytes_in_use 1" in txt
        for line in txt.splitlines():
            if not line.startswith("#"):
                assert "/" not in line.split("{")[0]

    def test_label_escaping_roundtrip(self):
        reg = MetricsRegistry()
        nasty = 'we"ird\\path\nnewline'
        reg.gauge("health/anomalies", "by type",
                  labelnames=("type",)).labels(type=nasty).set(7)
        txt = reg.to_prometheus()
        line = [l for l in txt.splitlines() if l.startswith(
            "health_anomalies{")][0]
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        assert "\n" not in line            # the raw newline never leaks
        snap = parse_prometheus_text(txt)
        key = f'health_anomalies{{type="{nasty}"}}'
        assert snap["gauges"][key] == 7.0

    def test_histogram_bucket_series(self):
        reg = MetricsRegistry()
        h = reg.histogram("serving/ttft_ms", "ttft")
        values = [0.5, 3.0, 3.0, 40.0, 900.0]
        for v in values:
            h.observe(v)
        txt = reg.to_prometheus()
        assert "# TYPE serving_ttft_ms histogram" in txt
        buckets = []
        for line in txt.splitlines():
            if line.startswith("serving_ttft_ms_bucket{"):
                le = line.split('le="')[1].split('"')[0]
                cum = int(line.split("} ")[1].split(" #")[0])
                buckets.append((math.inf if le == "+Inf" else float(le),
                                cum))
        # cumulative and monotone, closed by +Inf == count
        assert buckets == sorted(buckets)
        assert all(b1[1] <= b2[1] for b1, b2 in zip(buckets, buckets[1:]))
        assert buckets[-1] == (math.inf, len(values))
        # every observation is inside its bucket's bound
        for v in values:
            assert any(le >= v and cum > 0 for le, cum in buckets)
        assert f"serving_ttft_ms_count {len(values)}" in txt
        assert f"serving_ttft_ms_sum {sum(values)}" in txt
        snap = parse_prometheus_text(txt)
        s = snap["histograms"]["serving_ttft_ms"]
        assert s["count"] == len(values)
        assert s["sum"] == pytest.approx(sum(values))
        # parser quantiles mirror the registry's bucket-midpoint rule:
        # within one geometric bucket (~19 %) of the live estimate
        assert s["p50"] == pytest.approx(h.quantile(0.5), rel=0.25)
        assert s["p99"] == pytest.approx(h.quantile(0.99), rel=0.25)

    def test_labeled_histogram_series(self):
        reg = MetricsRegistry()
        fam = reg.histogram("train/phase_time_ms", "phases",
                            labelnames=("phase",))
        fam.labels(phase="fwd").observe(3.0)
        fam.labels(phase="bwd").observe(7.0)
        snap = parse_prometheus_text(reg.to_prometheus())
        assert snap["histograms"]['train_phase_time_ms{phase="fwd"}'][
            "count"] == 1
        assert snap["histograms"]['train_phase_time_ms{phase="bwd"}'][
            "sum"] == pytest.approx(7.0)

    def test_exemplar_rides_its_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("serving/ttft_ms", "ttft")
        h.observe(5.0, exemplar={"rid": "3"})
        h.observe(500.0, exemplar={"rid": "17"})   # newest exemplar wins
        # exemplars are ILLEGAL in the classic 0.0.4 format: the default
        # rendering must not include them (a strict scraper would reject
        # the whole body) — they appear only when OpenMetrics was asked
        assert " # {" not in reg.to_prometheus()
        txt = reg.to_prometheus(exemplars=True)
        ex_lines = [l for l in txt.splitlines() if " # {" in l]
        assert len(ex_lines) == 1
        line = ex_lines[0]
        assert 'rid="17"' in line and line.endswith(" 500")
        le = float(line.split('le="')[1].split('"')[0])
        assert le >= 500.0                 # attached to ITS bucket
        # the parser tolerates (and drops) the exemplar suffix
        snap = parse_prometheus_text(txt)
        assert snap["histograms"]["serving_ttft_ms"]["count"] == 2

    def test_parser_survives_foreign_lines(self):
        txt = ("# some comment\n"
               "weird{ 1\n"
               "up 1\n"
               "# TYPE go_goroutines gauge\n"
               "go_goroutines 42\n")
        snap = parse_prometheus_text(txt)
        assert snap["gauges"]["go_goroutines"] == 42.0
        validate_snapshot(snap)


class TestSummaryAtomicity:

    def test_summary_never_torn_under_concurrent_observe(self):
        """The satellite fix: ONE registry-lock hold for the whole
        summary, so a concurrent observe can never yield p50 > max (or
        p50 read from a different instant than p99)."""
        reg = MetricsRegistry()
        h = reg.histogram("t/h", "x")
        stop = threading.Event()

        def writer(seed):
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                # adversarial: alternate tiny and huge so a torn read
                # would visibly cross the ordering invariants
                h.observe(float(rng.choice([1e-3, 1e6])))

        threads = [threading.Thread(target=writer, args=(i,), daemon=True)
                   for i in range(4)]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 0.3
            while time.monotonic() < deadline:
                s = h.summary()
                if s["count"] == 0:
                    continue
                assert s["min"] <= s["p50"] <= s["p90"] <= s["p99"] \
                    <= s["max"]
                assert s["min"] <= s["mean"] <= s["max"]
                assert s["mean"] == pytest.approx(s["sum"] / s["count"])
        finally:
            stop.set()
            for t in threads:
                t.join(5)


# --------------------------------------------------------------------- #
# flight-recorder ring-loss gauges (satellite)


class TestRecorderMetrics:

    def test_dropped_and_capacity_exported(self):
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=4, enabled=True)
        for i in range(10):
            rec.emit("train.step", step=i)
        export_recorder_metrics(reg, rec)
        snap = reg.snapshot()
        assert snap["gauges"]["events/capacity"] == 4
        assert snap["gauges"]["events/dropped"] == 6

    def test_disabled_recorder_exports_nothing(self):
        reg = MetricsRegistry()
        export_recorder_metrics(reg, FlightRecorder(enabled=False))
        assert reg.snapshot()["gauges"] == {}

    def test_slo_breach_events_jsonl_validates(self, tmp_path):
        rec = FlightRecorder(enabled=True)
        rec.emit("slo.breach", objective="ttft_p99", tick=6,
                 burn_rate=55.6, threshold=1.0, window=8)
        path = rec.write_jsonl(str(tmp_path / "events.jsonl"))
        assert validate_trace.main(["--kind", "events", path]) == 0


# --------------------------------------------------------------------- #
# the sampler daemon


class TestSampler:

    def test_tick_ring_and_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("serving/requests").inc(2)
        path = str(tmp_path / "s.jsonl")
        s = MetricsSampler(reg, interval_s=0.05, path=path, ring=3)
        for _ in range(5):
            s.tick()
        assert s.seq == 5
        assert len(s.ring) == 3 and s.ring[-1]["seq"] == 5
        recs = [json.loads(l) for l in open(path)]
        assert [r["seq"] for r in recs] == [1, 2, 3, 4, 5]
        for r in recs:
            validate_snapshot(r)
            assert r["counters"]["serving/requests"] == 2

    def test_rotation_keeps_bounded_history(self, tmp_path):
        reg = MetricsRegistry()
        for i in range(40):
            reg.counter(f"t/c{i}").inc()       # fat snapshots
        path = str(tmp_path / "s.jsonl")
        s = MetricsSampler(reg, interval_s=1, path=path, max_bytes=2048,
                           keep=2)
        for _ in range(30):
            s.tick()
        assert os.path.exists(path)
        assert os.path.getsize(path) <= 2048
        assert os.path.exists(path + ".1")
        assert not os.path.exists(path + ".3")
        # the live file still tails cleanly: every line parses and seq
        # is contiguous ascending
        seqs = [json.loads(l)["seq"] for l in open(path)]
        assert seqs == sorted(seqs)
        assert seqs[-1] == 30

    def test_background_thread_and_stop(self, tmp_path):
        reg = MetricsRegistry()
        s = MetricsSampler(reg, interval_s=0.02,
                           path=str(tmp_path / "s.jsonl"))
        s.start()
        deadline = time.monotonic() + 5
        while s.seq < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        s.stop()
        assert s.seq >= 3
        final = s.seq
        time.sleep(0.08)
        assert s.seq == final              # really stopped

    def test_from_config_shorthands(self):
        tcfg = get_telemetry_config({"telemetry": {"sampler": True}})
        assert tcfg.enabled and tcfg.sampler.enabled
        s = sampler_from_config(tcfg, MetricsRegistry())
        assert isinstance(s, MetricsSampler) and s.slo is None
        off = get_telemetry_config({"telemetry": True})
        assert sampler_from_config(off, MetricsRegistry()) is None
        # slo implies the sampler (something must tick the evaluation)
        tcfg2 = get_telemetry_config({"telemetry": {"slo": {
            "enabled": True,
            "objectives": [{"metric": "serving/ttft_ms",
                            "threshold_ms": 50}]}}})
        assert tcfg2.sampler.enabled
        s2 = sampler_from_config(tcfg2, MetricsRegistry())
        assert s2 is not None and isinstance(s2.slo, SloEngine)


# --------------------------------------------------------------------- #
# the SLO engine


class TestSloObjectives:

    def test_parse_validation(self):
        with pytest.raises(ValueError, match="missing 'metric'"):
            parse_objectives([{"name": "x"}])
        with pytest.raises(ValueError, match="kind"):
            parse_objectives([{"metric": "m", "kind": "vibes"}])
        with pytest.raises(ValueError, match="threshold_ms"):
            parse_objectives([{"metric": "m", "kind": "latency"}])
        with pytest.raises(ValueError, match="total_metric"):
            parse_objectives([{"metric": "m", "kind": "ratio"}])
        with pytest.raises(ValueError, match="unknown keys"):
            parse_objectives([{"metric": "m", "threshold_ms": 1,
                               "surprise": 2}])
        with pytest.raises(ValueError, match="duplicate"):
            parse_objectives([{"metric": "m", "threshold_ms": 1},
                              {"metric": "m", "threshold_ms": 2}])
        objs = parse_objectives(serving_objectives(
            ttft_p99_ms=500, tpot_p99_ms=50, error_rate=0.01),
            default_windows=[12, 3])
        assert [o.name for o in objs] == ["ttft_p99", "tpot_p99",
                                          "error_rate"]
        assert objs[0].windows == (12, 3)
        assert objs[2].kind == "ratio"
        assert objs[2].error_budget == pytest.approx(0.01)

    def test_idle_service_never_breaches(self):
        reg = MetricsRegistry()
        slo = SloEngine(parse_objectives(
            [{"metric": "serving/ttft_ms", "threshold_ms": 10,
              "windows": [4, 2]}]), registry=reg)
        reg.histogram("serving/ttft_ms")
        for _ in range(20):
            assert slo.sample() == []      # zero observations = zero burn
        burns = multilabel_series(reg.snapshot()["gauges"], "slo/burn_rate")
        assert all(v == 0.0 for _, v in burns)

    def test_long_window_needs_full_history(self):
        """Startup blips cannot page: a window reads zero burn until the
        ring holds its complete history, so all-bad traffic from tick 1
        stays silent until the LONG window is actually provable."""
        reg = MetricsRegistry()
        slo = SloEngine(parse_objectives(
            [{"metric": "serving/ttft_ms", "threshold_ms": 10,
              "windows": [8, 2]}]), registry=reg)
        h = reg.histogram("serving/ttft_ms")
        fired = []
        for tick in range(1, 13):
            h.observe(100.0)           # every observation blows budget
            if slo.sample():
                fired.append(tick)
        assert fired == [9]            # first full-8-window tick, once

    def test_ratio_objective(self):
        reg = MetricsRegistry()
        bad = reg.counter("serving/rejected_requests")
        total = reg.counter("serving/requests")
        slo = SloEngine(parse_objectives(
            [{"name": "err", "metric": "serving/rejected_requests",
              "kind": "ratio", "total_metric": "serving/requests",
              "objective": 0.9, "windows": [4, 2]}]), registry=reg)
        for _ in range(6):                 # healthy: 0 rejected
            total.inc(10)
            assert slo.sample() == []
        fired = []
        for _ in range(4):                 # 50 % rejected >> 10 % budget
            total.inc(10)
            bad.inc(5)
            fired += slo.sample()
        assert len(fired) == 1 and fired[0]["objective"] == "err"


class TestSloTraceReplay:
    """THE acceptance pin: a recorded TTFT trace replayed through sampler
    ticks deterministically drives the p99-TTFT objective into breach;
    the burn-rate alert fires exactly once per window, re-fires while the
    burn sustains, lands in the flight recorder, and renders in
    ``health_summary`` / ``dscli top``."""

    # (tick, ttft observations in ms) — 5 healthy ticks, then sustained
    # 200 ms TTFT against a 50 ms p99 budget
    TRACE = [(t, [10.0] * 4) for t in range(5)] + \
            [(t, [200.0] * 5) for t in range(5, 25)]
    WINDOWS = [8, 2]

    def _replay(self, jsonl=None):
        reg = MetricsRegistry()
        rec = FlightRecorder(enabled=True)
        slo = SloEngine(parse_objectives(
            [{"name": "ttft_p99", "metric": "serving/ttft_ms",
              "kind": "latency", "threshold_ms": 50.0, "objective": 0.99,
              "windows": self.WINDOWS}]), registry=reg, events=rec)
        sampler = MetricsSampler(reg, interval_s=1.0, path=jsonl, slo=slo)
        h = reg.histogram("serving/ttft_ms", "ttft")
        fired = []
        for tick, observations in self.TRACE:
            for i, v in enumerate(observations):
                h.observe(v, exemplar={"rid": str(tick * 100 + i)})
            r = sampler.tick()
            for b in r.get("slo_breaches", []):
                fired.append(b["tick"])
        return fired, sampler, rec

    def test_breach_fires_once_per_window_deterministically(self):
        fired, sampler, rec = self._replay()
        # bad traffic starts at tick 6, but the LONG window only reads a
        # real burn once it holds its full 8-tick history (a window with
        # partial history reads zero — startup blips cannot page), so
        # the first firing is tick 9, then once per longest window (8
        # ticks) while the burn sustains — exactly these ticks
        assert fired == [9, 17, 25]
        fired2, _, _ = self._replay()
        assert fired2 == fired             # replay-identical
        snap = sampler.ring[-1]
        assert snap["counters"]['slo/breaches{objective="ttft_p99"}'] == 3
        burns = multilabel_series(snap["gauges"], "slo/burn_rate")
        assert {tuple(sorted(l.items())) for l, _ in burns} == {
            (("objective", "ttft_p99"), ("window", "2")),
            (("objective", "ttft_p99"), ("window", "8"))}
        assert all(v > 1.0 for _, v in burns)
        # the alert is ON the flight recorder's shared timeline
        breaches = [e for e in rec.snapshot() if e.kind == "slo.breach"]
        assert [e.data["tick"] for e in breaches] == [9, 17, 25]
        assert all(e.data["objective"] == "ttft_p99" for e in breaches)

    def test_renders_in_health_summary_and_top(self, tmp_path, capsys):
        path = str(tmp_path / "samples.jsonl")
        self._replay(jsonl=path)
        # health_summary: machine-readable slo section
        rec = json.loads(open(path).read().splitlines()[-1])
        s = health_summary(rec)
        assert s["slo"]["breaches"] == {"ttft_p99": 3}
        assert s["slo"]["burn_rate"]["ttft_p99"]["8"] > 1.0
        table = render_summary_table(s)
        assert "slo" in table and "BREACH x3" in table
        assert "ttft_p99" in table
        # dscli top over the sampler's JSONL
        assert top_cli([path, "--once"]) == 0
        out = capsys.readouterr().out
        assert "BREACH x3" in out and "TTFT" in out
        # and the --json surface carries the same dict
        assert top_cli([path, "--json"]) == 0
        js = json.loads(capsys.readouterr().out)
        assert js["slo"]["breaches"] == {"ttft_p99": 3}


# --------------------------------------------------------------------- #
# exposition endpoints: standalone exporter + dscli serve /metrics


class TestExporterHTTP:

    def test_scrape_and_healthz(self):
        reg = MetricsRegistry()
        reg.counter("serving/requests", "total").inc(4)
        reg.histogram("serving/ttft_ms").observe(12.0,
                                                 exemplar={"rid": "1"})
        with MetricsExporter(reg) as ex:
            with urllib.request.urlopen(ex.url, timeout=30) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                text = resp.read().decode()
            assert "serving_requests 4" in text
            assert "serving_ttft_ms_bucket{" in text
            host, port = ex.address
            with urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=30) as resp:
                assert resp.status == 200
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://{host}:{port}/nope",
                                       timeout=30)
        with pytest.raises(OSError):
            urllib.request.urlopen(ex.url, timeout=2)   # stopped

    def test_scrape_refreshes_recorder_gauges(self):
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=2, enabled=True)
        import deepspeed_tpu.monitor.events as events_mod
        old = events_mod._recorder
        events_mod._recorder = rec
        try:
            for i in range(5):
                rec.emit("train.step", step=i)
            ex = MetricsExporter(reg)
            text = ex.render()
            assert "events_dropped 3" in text
            assert "events_capacity 2" in text
        finally:
            events_mod._recorder = old


@pytest.mark.usefixtures("clean_engine_state")
class TestServeMetricsRoute:

    @pytest.fixture()
    def clean_engine_state(self):
        from deepspeed_tpu.monitor.metrics import get_registry
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

    def _get(self, port, path, headers=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", path, headers=headers or {})
        r = conn.getresponse()
        return r.status, r.getheader("Content-Type"), r.read().decode()

    def test_metrics_scraped_during_live_completion(self):
        """THE exposition acceptance pin: ``GET /metrics`` DURING a live
        streamed completion returns valid Prometheus text containing the
        ``serving/ttft_ms`` histogram series (with its rid exemplar),
        and 503 once the loop stops — stale numbers must not scrape as
        healthy."""
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2})
        serving = AsyncServingEngine(engine, max_new_tokens=16)
        server = build_http_server(serving, port=0)
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            port = server.server_address[1]
            rng = np.random.default_rng(0)
            h = serving.add_request(
                rng.integers(0, 64, size=9).astype(np.int32))
            stream = h.stream(timeout=300)
            next(stream)               # first burst: TTFT observed, the
            # request is mid-decode — the scrape below is truly LIVE
            status, ctype, text = self._get(port, "/metrics")
            assert status == 200
            assert ctype.startswith("text/plain; version=0.0.4")
            assert "# TYPE serving_ttft_ms histogram" in text
            assert "serving_ttft_ms_bucket{" in text
            assert " # {" not in text  # exemplars are 0.0.4-illegal
            # a scraper negotiating OpenMetrics gets the exemplar that
            # links the newest TTFT observation back to its request track
            status_om, ctype_om, text_om = self._get(
                port, "/metrics",
                headers={"Accept": "application/openmetrics-text"})
            assert status_om == 200
            assert ctype_om.startswith("application/openmetrics-text")
            assert ' # {rid="' in text_om
            assert text_om.endswith("# EOF\n")
            snap = parse_prometheus_text(text)
            validate_snapshot(snap)
            assert snap["histograms"]["serving_ttft_ms"]["count"] >= 1
            assert snap["counters"]["serving_requests"] >= 1
            assert "serving_queue_depth" in snap["gauges"]
            for _ in stream:
                pass
            assert h.status == "finished"
            serving.shutdown(drain=True)
            status, _, _ = self._get(port, "/metrics")
            assert status == 503       # same liveness rule as /healthz
        finally:
            server.shutdown()
            t.join(60)
            if not serving._stopped:
                serving.shutdown(drain=False)


class TestEngineWiring:

    @pytest.fixture(autouse=True)
    def clean_state(self):
        from deepspeed_tpu.monitor.metrics import get_registry
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

    def test_training_engine_config_starts_plane(self):
        """``telemetry.metrics_port`` + ``telemetry.sampler``/``slo`` on
        the TRAINING engine stand the exposition plane up (the
        'standalone exporter usable from training' half), and
        ``destroy()`` tears it down."""
        import jax
        model = tiny_model(max_seq=32)
        params = model.init_params(jax.random.key(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config={
                "train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "mesh": {"dp": -1}, "steps_per_print": 0,
                "telemetry": {
                    "enabled": True, "metrics_port": 0,
                    "sampler": {"enabled": True, "interval_s": 0.05},
                    "slo": {"enabled": True, "objectives": [
                        {"name": "step_p99",
                         "metric": "train/step_time_ms",
                         "threshold_ms": 1e9, "objective": 0.99}]}}})
        try:
            assert engine._tel_exporter is not None
            assert engine._tel_sampler is not None
            assert isinstance(engine._tel_sampler.slo, SloEngine)
            rng = np.random.default_rng(0)
            dp = dist.get_world_size(dist.data_parallel_axes(engine.mesh))
            batch = {"input_ids": rng.integers(
                0, 64, size=(dp, 32)).astype(np.int32)}
            engine.train_batch(batch)
            url = engine._tel_exporter.url
            with urllib.request.urlopen(url, timeout=30) as resp:
                text = resp.read().decode()
            assert "train_step_time_ms_bucket{" in text
            assert "slo_burn_rate{" in text
        finally:
            engine.destroy()
        assert engine._tel_exporter is None and engine._tel_sampler is None
        with pytest.raises(OSError):
            urllib.request.urlopen(url, timeout=2)

    def test_serve_main_slo_flags(self, tmp_path):
        """``dscli serve --slo-ttft-ms --sample-jsonl`` stands the whole
        plane up: the sampler writes snapshots with SLO burn gauges and
        the run exits cleanly."""
        from deepspeed_tpu.inference.serve import serve_main
        import jax
        model = tiny_model()
        params = model.init_params(jax.random.key(0))
        path = str(tmp_path / "samples.jsonl")
        holder, ready, rc = {}, threading.Event(), {}

        def cb(server, serving):
            holder.update(server=server, serving=serving)
            ready.set()

        def run():
            rc["rc"] = serve_main(
                ["--port", "0", "--dtype", "fp32", "--max-new", "4",
                 "--block-size", "8", "--max-running", "2",
                 "--sample-jsonl", path, "--sample-interval", "0.02",
                 "--slo-ttft-ms", "500"],
                model=model, params=params, ready_cb=cb)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert ready.wait(300)
        port = holder["server"].server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [1, 2, 3], "max_tokens": 4}),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
        holder["server"].shutdown()
        t.join(300)
        assert rc["rc"] == 0
        recs = [json.loads(l) for l in open(path)]
        assert recs, "sampler wrote nothing"
        last = recs[-1]
        assert any(k.startswith('slo/burn_rate{objective="ttft_p99"')
                   for k in last["gauges"])
        assert last["histograms"]["serving/ttft_ms"]["count"] >= 1


# --------------------------------------------------------------------- #
# the serving_metrics_steady compile-budget contract


class TestServingMetricsContract:

    @pytest.fixture(autouse=True)
    def clean_state(self):
        from deepspeed_tpu.monitor.metrics import get_registry
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

    def test_sampler_and_exporter_add_zero_compiles(self):
        """A warmed serving loop with the sampler ticking (SLO evaluation
        included) and /metrics scraped between engine steps compiles
        NOTHING new: scrapes and snapshots are host-side registry reads
        (by_fn equality with the warm-up), and every entry stays within
        the serving_metrics_steady budgets."""
        from dslint.contracts import check_compile_budgets

        from deepspeed_tpu.monitor.metrics import get_registry

        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry={"events": True},
            serving={"block_size": 8, "max_running": 2})
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=n).astype(np.int32)
                   for n in (9, 11, 5)]
        engine.generate_batch(prompts, max_new_tokens=10)   # warm closed
        engine.generate_batch(prompts, max_new_tokens=10)   # + cache hits
        warm = dict(engine.telemetry_snapshot()["compile"]["by_fn"])

        reg = get_registry()
        slo = SloEngine(parse_objectives(serving_objectives(
            ttft_p99_ms=500.0, tpot_p99_ms=50.0)), registry=reg,
            events=engine._events)
        sampler = MetricsSampler(reg, interval_s=1.0, slo=slo)
        with MetricsExporter(reg) as ex:
            serving = AsyncServingEngine(engine, max_new_tokens=10,
                                         start=False)
            for p in prompts:
                serving.add_request(p)
            i = 0
            while serving.step():
                i += 1
                sampler.tick()         # snapshot + SLO tick every step
                if i % 3 == 0:         # and a real HTTP scrape
                    with urllib.request.urlopen(ex.url,
                                                timeout=30) as resp:
                        assert b"serving_ttft_ms" in resp.read()
            serving.shutdown(drain=True)
            sampler.tick()
        assert sampler.seq > 3

        by_fn = engine.telemetry_snapshot()["compile"]["by_fn"]
        assert by_fn == warm, (
            f"the metrics plane recompiled: warm {warm} -> {by_fn}")
        violations = check_compile_budgets(by_fn, "serving_metrics_steady",
                                           strict=True)
        assert violations == [], "\n".join(violations)


# --------------------------------------------------------------------- #
# dslint DS009: metrics-plane device isolation


class TestDs009:

    def _lint(self, tmp_path, sources):
        from dslint.callgraph import PackageIndex
        from dslint.core import LintContext, run_lint
        pkg = tmp_path / "pkg"
        pkg.mkdir(exist_ok=True)
        for rel, src in sources.items():
            p = pkg / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        ctx = LintContext(repo_root=str(tmp_path),
                          index=PackageIndex(str(tmp_path), ["pkg"]),
                          tests_index=None, pytest_ini=None, conftest=None)
        return run_lint(ctx, select=["DS009"],
                        baseline_path=str(tmp_path / "no_baseline"))

    def test_jax_import_in_plane_module_flagged(self, tmp_path):
        res = self._lint(tmp_path, {"monitor/sampler.py": """
            import jax

            def tick():
                from jax import numpy as jnp    # lazy import: still runs
                return jnp.zeros(())            # on the sampler thread
        """, "monitor/exporter.py": """
            from deepspeed_tpu.accelerator import get_accelerator

            def render():
                return get_accelerator().memory_report()
        """})
        found = sorted((f.path, f.rule) for f in res.findings)
        assert ("pkg/monitor/exporter.py", "DS009") in found
        assert ("pkg/monitor/sampler.py", "DS009") in found
        assert len([f for f in res.findings
                    if f.path.endswith("sampler.py")]) == 2

    def test_clean_plane_and_foreign_modules_pass(self, tmp_path):
        res = self._lint(tmp_path, {"monitor/slo.py": """
            import json, threading

            def sample(registry):
                return dict(registry)
        """, "runtime/engine.py": """
            import jax                          # engines MAY touch jax

            def step(x):
                return jax.numpy.sum(x)
        """})
        assert [f for f in res.findings if f.rule == "DS009"] == []

    def test_real_plane_modules_are_clean_and_contract_registered(self):
        """The shipped sampler/exporter/slo/top modules pass their own
        rule, and the serving_metrics_steady budgets exist."""
        from dslint.contracts import budgets_for
        table = budgets_for("serving_metrics_steady")
        assert {"inference.paged_decode", "inference.paged_sample",
                "inference.paged_verify", "inference.paged_prefill",
                "inference.paged_prefill_chunk",
                "inference.paged_cow"} == set(table)
        import deepspeed_tpu.monitor as mon
        root = os.path.dirname(mon.__file__)
        import ast as _ast
        for name in ("sampler.py", "exporter.py", "slo.py", "top.py"):
            tree = _ast.parse(open(os.path.join(root, name)).read())
            for node in _ast.walk(tree):
                if isinstance(node, _ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, _ast.ImportFrom):
                    mods = [node.module or ""]
                else:
                    continue
                for m in mods:
                    assert not (m == "jax" or m.startswith("jax.")), \
                        f"{name} imports {m}"


# --------------------------------------------------------------------- #
# dscli top plumbing


class TestTopCli:

    def test_cli_routes_top(self):
        from deepspeed_tpu import cli
        assert cli._COMMANDS["top"] is cli._top

    def test_desanitized_scrape_snapshot(self):
        reg = MetricsRegistry()
        reg.histogram("serving/ttft_ms").observe(10.0)
        reg.gauge("serving/queue_depth").set(3)
        reg.counter("slo/breaches", labelnames=("objective",)) \
            .labels(objective="ttft_p99").inc()
        rec = snapshot_from_prometheus(reg.to_prometheus())
        assert "serving/ttft_ms" in rec["histograms"]
        assert rec["gauges"]["serving/queue_depth"] == 3
        assert rec["counters"]['slo/breaches{objective="ttft_p99"}'] == 1
        s = health_summary(rec)
        assert s["serving"]["ttft_ms"]["count"] == 1
        assert s["slo"]["breaches"] == {"ttft_p99": 1}

    def test_top_over_live_scrape_url(self):
        from deepspeed_tpu.monitor.top import fetch_snapshots
        reg = MetricsRegistry()
        reg.histogram("serving/ttft_ms").observe(25.0)
        with MetricsExporter(reg) as ex:
            url = ex.url
            rec, prev = fetch_snapshots(url)
            out = render_top(rec, prev, url)
        assert "TTFT" in out and url in out

    def test_top_missing_source(self, tmp_path, capsys):
        assert top_cli([str(tmp_path / "nope.jsonl"), "--once"]) == 1
        assert "no data" in capsys.readouterr().out
