"""Benchmark: causal-LM training throughput on one TPU chip.

Prints one JSON line per metric: {"metric", "value", "unit", "vs_baseline"}.

1. GPT-2 125M, MHA, ZeRO-1 — the historical bench config (every round).
2. A llama-style GQA model (rope/rmsnorm/swiglu, n_kv_head < n_head) under
   ZeRO-3 — the BASELINE.md north-star shape (Llama-7B ZeRO-3), sized to
   the largest that fits one chip, so the driver measures the GQA flash
   index maps and ZeRO-3 gather-on-use paths, not just the easy config.
   Disable with BENCH_LLAMA=0.

Baseline: the reference's single-GPU fused-kernel result — BERT-large at
>50% of V100 peak (docs/_posts/2020-05-28-fastest-bert-training.md, see
BASELINE.md). vs_baseline = achieved MFU / 0.50, i.e. >1.0 means this
framework exceeds the reference's best published hardware efficiency class.

Env knobs (defaults are the chip-measured fast path):
  BENCH_STEPS=10           timed steps per window (best of two windows)
  BENCH_GPT2/LLAMA=1       enable metric 1 / 2; BENCH_BERT=1 enables the
                           bert-large MLM metric (un-gated now that the
                           fused CE kernel removes the head bottleneck)
  BENCH_BATCH=64 BENCH_SEQ=1024            gpt2 metric shape
  BENCH_LLAMA_BATCH=4 BENCH_LLAMA_SEQ=2048 llama metric shape
  BENCH_BERT_BATCH=32 BENCH_BERT_SEQ=512   bert metric shape (bs48+ OOMs)
  BENCH_BERT_REMAT=none    bert-only remat (falls back to BENCH_REMAT;
                           measured fastest: none — fits at bs32)
  BENCH_BERT_SCAN=0        bert layer stacking (unrolled measured +12%)
  BENCH_BERT_GATHER=0.25   MLM masked-position gather budget (fraction of
                           B*S routed through the vocab head; 0 = full)
  BENCH_REMAT=dots         1/true/full | 0/false/none | dots | selective...
  BENCH_FUSED_CE=auto      vocab-head CE path: auto = fused logits-free
                           Pallas kernel on TPU, XLA loss_chunk streaming
                           elsewhere | on | off
  BENCH_LOSS_CHUNK=2048    vocab-head streaming chunk when the fused kernel
                           is off/unavailable (0 = off; the bert metric
                           defaults to 4096, its measured best)
  BENCH_ATTN=auto          auto | flash | xla
  BENCH_OPT=AdamW          AdamW | FusedAdam | ...
  BENCH_SCAN=0             gpt2 layer stacking (0 = unrolled, measured
                           ~12% faster); BENCH_LLAMA_SCAN=0 for metric 2
                           (unrolled measured 13.5% faster on-chip)
  BENCH_BLOCK_Q/K=0        flash kernel block override (0 = tuned default)
  BENCH_DECODE_DENSE/PAGED=1  serving decode metrics: the same mixed
                           prompt set through the static generate path vs
                           the paged continuous-batching generate_batch
                           (the paged record's vs_baseline = speedup over
                           dense); BENCH_DECODE_REQS=16 BENCH_DECODE_NEW=128
                           BENCH_DECODE_BLOCK=128 BENCH_DECODE_RUNNING=8
  BENCH_SERVE_PREFIX=1     shared-system-prompt TTFT probe: prefix caching
                           off vs on (vs_baseline = off/on TTFT ratio);
                           BENCH_SERVE_REQS=8 BENCH_SERVE_PREFIX_LEN=768
                           BENCH_SERVE_NEW=16
  BENCH_KV_TIER=1          tiered-KV re-hit probe: shared-prefix TTFT at
                           forced cache pressure, host spill on vs
                           destroy-on-reclaim (vs_baseline = off/on);
                           BENCH_KV_TIER_PREFIX_LEN=512
                           BENCH_KV_TIER_BLOCKS=24
  BENCH_SERVE_SPEC=1       speculative-decode probe: p50 TPOT on repetitive
                           motif prompts, serving.speculative off vs ngram
                           (vs_baseline = off/on p50 ratio; accepted
                           tokens/step in the telemetry blob);
                           BENCH_SERVE_SPEC_REQS=8 BENCH_SERVE_SPEC_K=4
                           BENCH_SERVE_SPEC_NEW=64 BENCH_SERVE_SPEC_MOTIF=48
  BENCH_SERVE_CHUNKED=1    decode-interference probe: p99 TPOT with long
                           prompts prefilling whole vs chunked
                           (vs_baseline = whole/chunked p99 ratio);
                           BENCH_SERVE_LONG_LEN=896 BENCH_SERVE_CHUNK=256
  BENCH_SERVE_TP=1         multi-chip tensor-parallel serving probe: paged
                           decode tokens/s at serving.tp=1 vs tp=N on the
                           same prompt set (vs_baseline = scaling
                           efficiency, (tpN/tp1)/N); skip record on a
                           single-device backend; BENCH_SERVE_TP_N=auto
                           BENCH_SERVE_TP_REQS=8 BENCH_SERVE_TP_NEW=64
  BENCH_SERVE_ASYNC=1      open-loop async serving probe: Poisson arrivals
                           through the always-on AsyncServingEngine, value
                           = GOODPUT (generated tokens/s from requests
                           whose own p99 TPOT met the target), vs_baseline
                           = goodput/throughput (SLO attainment, <= 1);
                           BENCH_SERVE_ASYNC_RATE=8 (req/s)
                           BENCH_SERVE_ASYNC_REQS=24
                           BENCH_SERVE_ASYNC_NEW=32
                           BENCH_SERVE_ASYNC_TPOT_MS=50 (p99 target)
  BENCH_SERVE_CHAOS=1      serving fault-tolerance probe: the Poisson
                           async run re-run under a seeded injection
                           schedule (one engine-fatal fault + scattered
                           per-request step faults), value = faulted-run
                           goodput, vs_baseline = GOODPUT RETENTION
                           (faulted/clean); restart/retry/quarantine
                           counters ride the telemetry blob;
                           BENCH_SERVE_CHAOS_RATE=8 (req/s)
                           BENCH_SERVE_CHAOS_REQS=16
                           BENCH_SERVE_CHAOS_NEW=32
  BENCH_SERVE_DP=1         replica scale-out probe: the same seeded Poisson
                           trace through one AsyncServingEngine (dp=1) and
                           through a two-replica ReplicaRouter with session
                           affinity (dp=2), value = dp=2 goodput,
                           vs_baseline = SCALING EFFICIENCY
                           ((goodput_dp2/goodput_dp1)/2, 1.0 = linear);
                           BENCH_SERVE_DP_RATE=8 (req/s)
                           BENCH_SERVE_DP_REQS=16 BENCH_SERVE_DP_NEW=32
  BENCH_CTL=1              adaptive-autopilot spike probe: one engine, the
                           same seeded Poisson trace with a mid-trace
                           arrival SPIKE, driven twice — controller OFF
                           (static config posture) then ON (the
                           monitor/controller.py SLO-burn autopilot,
                           dscli serve --adaptive); value = adaptive-run
                           goodput at the p99 TPOT target, vs_baseline =
                           adaptive/static goodput; per-run SLO breach /
                           shed / knob-action counts and the decision
                           ledger ride the telemetry blob;
                           BENCH_CTL_RATE=6 (req/s) BENCH_CTL_REQS=18
                           BENCH_CTL_NEW=32 BENCH_CTL_TPOT_MS=50
                           BENCH_CTL_SPIKE=6 (spike factor)

The bench measures the chip: ``main()`` starts with the device guard
(``deepspeed_tpu.accelerator.require_tpu``) and exits non-zero when jax is
not on a TPU whose ``device_kind`` has a published peak. There is no CPU
leg and no skip record; a probe that fails raises.
"""

import json
import os
import sys
import time


def _parse_remat(env: str):
    """BENCH_REMAT accepts 1/true/full/0/false/none or a policy name —
    shared by every bench builder."""
    return {"1": True, "true": True, "full": True,
            "0": False, "false": False, "none": False}.get(env.lower(), env)


def _reset_telemetry():
    """Fresh registry/watchdog per metric so each record's embedded
    telemetry blob describes THAT metric's run only. Must run before the
    engine is constructed (families created at init would be orphaned)."""
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.trace import get_compile_watchdog
    get_compile_watchdog().reset()
    get_registry().reset()


def _bench_telemetry():
    """The train metrics' shared telemetry block: health on in "record"
    mode with device sentinels OFF — the host detectors (spike / stall /
    overflow) and anomaly counters ride along without perturbing the
    measured step (no in-step reductions beyond the grad-norm reuse
    telemetry records anyway). Fresh dict per call: the engine parses the
    raw config and a shared literal could alias across builders."""
    return {"enabled": True,
            "health": {"enabled": True, "sentinels": False,
                       "action": "record"}}


def _telemetry_blob(engine):
    """Compact telemetry summary for the result record: compile counts,
    MFU/step-time (training engines), serving histograms (decode bench)."""
    snap = engine.telemetry_snapshot() \
        if hasattr(engine, "telemetry_snapshot") else {}
    if not snap:
        return None
    blob = {"compile_counts": snap.get("compile", {}).get("by_fn", {})}
    g, h, c = (snap.get("gauges", {}), snap.get("histograms", {}),
               snap.get("counters", {}))
    for k in ("train/mfu", "train/tokens_per_sec",
              "train/achieved_tflops_per_chip", "train/data_stall_fraction",
              "serving/queue_depth", "serving/kv_block_utilization",
              "serving/kv_fragmentation", "serving/running",
              "serving/kv_host_blocks", "serving/kv_host_bytes"):
        if k in g:
            blob[k] = round(g[k], 6)
    for k in ("train/step_time_ms", "serving/ttft_ms", "serving/tpot_ms",
              "serving/queue_wait_ms",
              "checkpoint/save_ms", "checkpoint/snapshot_ms",
              "checkpoint/bytes"):
        if k in h:
            blob[k] = {kk: round(float(vv), 3) for kk, vv in h[k].items()}
    for k in ("serving/preemptions", "serving/recompute_tokens",
              "serving/prefill_steps", "serving/decode_steps",
              "serving/generated_tokens", "serving/spec_verify_steps",
              "serving/spec_proposed_tokens", "serving/spec_accepted_tokens",
              "serving/spec_rollbacks", "serving/rejected_requests",
              "serving/kv_spills", "serving/kv_fetch_hits",
              "serving/kv_fetch_tokens", "serving/kv_host_errors",
              "serving/engine_restarts", "serving/request_retries",
              "serving/timeouts", "serving/shed_requests",
              "checkpoint/saves",
              "checkpoint/failures"):
        if k in c:
            blob[k] = c[k]
    # request latency anatomy: per-phase p50/p99 (fleet-summed counts
    # keep the record compact — per-replica detail stays in /metrics)
    # and the wasted-token causes, so BENCH records carry TTFT anatomy
    from deepspeed_tpu.monitor.health import multilabel_series
    phases = {}
    for labels, v in multilabel_series(h, "serving/phase_ms"):
        p = labels.get("phase")
        if p is None or not (v or {}).get("count"):
            continue
        agg = phases.setdefault(p, {"count": 0, "p50": 0.0, "p99": 0.0})
        agg["count"] += int(v["count"])
        agg["p50"] = round(max(agg["p50"], float(v.get("p50", 0.0))), 3)
        agg["p99"] = round(max(agg["p99"], float(v.get("p99", 0.0))), 3)
    if phases:
        blob["serving/phase_ms"] = phases
    wasted = {}
    for labels, v in multilabel_series(c, "serving/wasted_tokens"):
        cause = labels.get("cause")
        if cause is not None and v:
            wasted[cause] = wasted.get(cause, 0) + int(v)
    if wasted:
        blob["serving/wasted_tokens"] = wasted
    # health summary: detector firings (zero-valued on a clean run)
    from deepspeed_tpu.monitor.health import labeled_series
    faults = {k: int(v)
              for k, v in labeled_series(c, "serving/step_faults").items()}
    if faults:
        blob["serving/step_faults"] = faults
    anoms = {k: int(v)
             for k, v in labeled_series(c, "health/anomalies").items()}
    if anoms:
        blob["health_anomalies"] = anoms
    # SLO burn-rate alerts + flight-recorder ring loss, when the plane ran
    slo_fired = {k: int(v)
                 for k, v in labeled_series(c, "slo/breaches").items() if v}
    if slo_fired:
        blob["slo_breaches"] = slo_fired
    if g.get("events/dropped"):
        blob["events/dropped"] = int(g["events/dropped"])
    # peak HBM straight from the accelerator — device truth, present even
    # when gauge sampling never ran (e.g. telemetry flush cadence 0)
    try:
        from deepspeed_tpu.accelerator import get_accelerator
        acc = get_accelerator()
        peaks = [acc.max_memory_allocated(i)
                 for i in range(acc.local_device_count())]
        if any(peaks):
            blob["peak_hbm_bytes"] = int(max(peaks))
    except Exception:
        pass
    return blob


def build_bench_engine():
    """The bench's env knobs → (engine, model, batch_fn, knobs dict). Shared
    with benchmarks/profile_bench.py so the profile always measures the
    exact configuration the bench reports."""
    _reset_telemetry()
    import jax
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import gpt2

    BATCH = int(os.environ.get("BENCH_BATCH", 64))  # bs64 ≈ +0.6% over bs32
    SEQ = int(os.environ.get("BENCH_SEQ", 1024))

    # Memory/speed knobs (see models/transformer.py): the default is the
    # tuned fast path — "dots" remat (save matmul outputs, recompute the
    # cheap elementwise parts; the packed flash kernel is fast enough to
    # recompute) + chunked cross-entropy (never materialises the
    # [B, S, vocab] fp32 logits) + unrolled layers.
    remat_env = os.environ.get("BENCH_REMAT", "dots")
    REMAT = _parse_remat(remat_env)
    LOSS_CHUNK = int(os.environ.get("BENCH_LOSS_CHUNK", 2048))
    FUSED_CE = os.environ.get("BENCH_FUSED_CE", "auto")
    ATTN = os.environ.get("BENCH_ATTN", "auto")
    SCAN = os.environ.get("BENCH_SCAN", "0") == "1"  # unrolled: XLA schedules
    # the 12 blocks better than a lax.scan (measured ~12% faster)
    model = gpt2("125m", remat=REMAT, loss_chunk=LOSS_CHUNK, attention_backend=ATTN,
                 scan_layers=SCAN, fused_cross_entropy=FUSED_CE)
    params = model.init_params(jax.random.key(0))

    dist.set_mesh(None)
    # BENCH_OPT=FusedAdam selects the Pallas fused single-pass optimizer
    OPT = os.environ.get("BENCH_OPT", "AdamW")
    config = {
        "train_micro_batch_size_per_gpu": BATCH,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": OPT, "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "telemetry": _bench_telemetry(),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)

    rng = np.random.default_rng(0)

    def batch_fn():
        return {"input_ids": rng.integers(0, 50257, size=(BATCH, SEQ)).astype(np.int32)}

    return engine, model, batch_fn, dict(BATCH=BATCH, SEQ=SEQ,
                                         remat_env=remat_env,
                                         LOSS_CHUNK=LOSS_CHUNK,
                                         FUSED_CE=FUSED_CE)


def build_llama_bench_engine():
    """Llama-style GQA + ZeRO-3 bench config (north-star shape, one chip).

    ~500M params: d_model 1536, 12 q heads over 4 kv heads (head_dim 128 —
    the flash kernel's native GQA envelope), swiglu/rmsnorm/rope, seq 2048.
    ZeRO-3 so the driver exercises parameter sharding + gather-on-use even
    at world size 1 (the sharding rules, master-param update, and donation
    paths are identical; only the collective extent changes)."""
    _reset_telemetry()
    import jax
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import llama

    BATCH = int(os.environ.get("BENCH_LLAMA_BATCH", 4))
    SEQ = int(os.environ.get("BENCH_LLAMA_SEQ", 2048))
    blk_q = int(os.environ.get("BENCH_BLOCK_Q", 0)) or None
    blk_k = int(os.environ.get("BENCH_BLOCK_K", 0)) or None
    model = llama("tiny", n_layer=16, n_head=12, n_kv_head=4, d_model=1536,
                  d_ff=4096, max_seq=SEQ,
                  remat=_parse_remat(os.environ.get("BENCH_REMAT", "dots")),
                  loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", 2048)),
                  fused_cross_entropy=os.environ.get("BENCH_FUSED_CE", "auto"),
                  attention_backend=os.environ.get("BENCH_ATTN", "auto"),
                  scan_layers=os.environ.get("BENCH_LLAMA_SCAN", "0") == "1",
                  attn_block_q=blk_q, attn_block_k=blk_k)
    params = model.init_params(jax.random.key(0))

    dist.set_mesh(None)
    config = {
        "train_micro_batch_size_per_gpu": BATCH,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": os.environ.get("BENCH_OPT", "AdamW"),
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "telemetry": _bench_telemetry(),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)

    rng = np.random.default_rng(0)

    def batch_fn():
        return {"input_ids": rng.integers(0, 32000, size=(BATCH, SEQ)).astype(np.int32)}

    return engine, model, batch_fn, dict(BATCH=BATCH, SEQ=SEQ)


def build_bert_bench_engine():
    """BERT-large MLM (the reference's headline fastest-BERT-training
    benchmark: 53 TFLOPS = >50% of V100 peak at seq 512,
    docs/_posts/2020-05-28-fastest-bert-training.md): 24L/1024d/16h,
    seq 512, ZeRO-2, bf16. On by default (BENCH_BERT=0 gates it) now that
    the fused logits-free CE kernel removes the vocab-head bottleneck the
    metric was gated on."""
    _reset_telemetry()
    import jax
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.bert import BertConfig, BertModel

    BATCH = int(os.environ.get("BENCH_BERT_BATCH", 32))
    SEQ = int(os.environ.get("BENCH_BERT_SEQ", 512))
    # chip-measured fastest knobs (bs32, no remat, 4096 CE chunks, unrolled
    # layers, 0.25 masked-gather budget): 48.3k tok/s = MFU 0.496 on v5e
    model = BertModel(BertConfig(vocab_size=30522, max_seq=SEQ, n_layer=24,
                                 n_head=16, d_model=1024, d_ff=4096,
                                 remat=_parse_remat(os.environ.get(
                                     "BENCH_BERT_REMAT",
                                     os.environ.get("BENCH_REMAT", "none"))),
                                 loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", 4096)),
                                 fused_cross_entropy=os.environ.get("BENCH_FUSED_CE", "auto"),
                                 scan_layers=os.environ.get("BENCH_BERT_SCAN", "0") == "1",
                                 mlm_gather_budget=float(os.environ.get("BENCH_BERT_GATHER", "0.25"))),
                      with_mlm_head=True)
    params = model.init_params(jax.random.key(0))

    dist.set_mesh(None)
    config = {
        "train_micro_batch_size_per_gpu": BATCH,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": os.environ.get("BENCH_OPT", "AdamW"),
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "mesh": {"dp": -1},
        "steps_per_print": 0,
        "telemetry": _bench_telemetry(),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=config)

    rng = np.random.default_rng(0)

    def batch_fn():
        ids = rng.integers(0, 30522, size=(BATCH, SEQ)).astype(np.int32)
        labels = np.full_like(ids, -100)
        pos = rng.random((BATCH, SEQ)) < 0.15
        labels[pos] = ids[pos]
        ids[pos] = 103  # [MASK]
        return {"input_ids": ids, "labels": labels}

    return engine, model, batch_fn, dict(BATCH=BATCH, SEQ=SEQ)


def _run_metric(name, engine, model, batch, BATCH, SEQ, steps, extra_unit):
    import jax
    import time as _t

    jax.block_until_ready(engine.train_batch(batch()))  # warmup/compile
    # best of two timed windows: device throughput is stable but transient
    # host contention (another process) can pollute a single window; the
    # max is the hardware's number
    dt = None
    for _ in range(2):
        t0 = _t.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch())
        # chained state => the last step's loss waits for every step
        loss_val = float(jax.block_until_ready(loss))
        w = _t.perf_counter() - t0
        dt = w if dt is None else min(dt, w)

    tokens_per_sec = BATCH * SEQ * steps / dt
    achieved_tflops = tokens_per_sec * model.flops_per_token(SEQ) / 1e12

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "unknown").lower()
    # one peak table for the whole system (the accelerator's device-kind
    # map, the denominator the telemetry MFU gauge uses); an unknown kind
    # raises
    from deepspeed_tpu.accelerator import get_accelerator
    mfu = achieved_tflops / get_accelerator().peak_tflops()

    rec = {
        "metric": name,
        "value": round(tokens_per_sec, 1),
        "unit": f"tokens/s (bf16, bs{BATCH}xseq{SEQ}, {extra_unit}, {kind}, "
                f"{achieved_tflops:.1f} TFLOPs, MFU {mfu:.3f}, loss {loss_val:.3f})",
        "vs_baseline": round(mfu / 0.50, 3),
    }
    tel = _telemetry_blob(engine)
    if tel:
        rec["telemetry"] = tel
    print(json.dumps(rec), flush=True)


# single registry: (env gate, default, metric name)
BENCH_METRICS = [
    ("BENCH_GPT2", "1", "gpt2_125m_train_tokens_per_sec_per_chip"),
    ("BENCH_LLAMA", "1", "llama_gqa_500m_zero3_train_tokens_per_sec_per_chip"),
    ("BENCH_BERT", "1", "bert_large_mlm_train_tokens_per_sec_per_chip"),
    ("BENCH_DECODE_DENSE", "1", "gpt2_decode_dense_tokens_per_sec_per_chip"),
    ("BENCH_DECODE_PAGED", "1", "gpt2_decode_paged_tokens_per_sec_per_chip"),
    ("BENCH_SERVE_PREFIX", "1", "gpt2_serving_prefix_cache_ttft_ms"),
    ("BENCH_KV_TIER", "1", "gpt2_serving_kv_tier_ttft_ms"),
    ("BENCH_SERVE_CHUNKED", "1", "gpt2_serving_chunked_prefill_tpot_p99_ms"),
    ("BENCH_SERVE_SPEC", "1", "gpt2_serving_spec_decode_tpot_ms"),
    ("BENCH_SERVE_ASYNC", "1", "gpt2_serving_async_goodput_tokens_per_sec"),
    ("BENCH_SERVE_CHAOS", "1", "gpt2_serving_chaos_goodput_tokens_per_sec"),
    ("BENCH_SERVE_DP", "1", "gpt2_serving_dp_goodput_tokens_per_sec"),
    ("BENCH_CTL", "1", "gpt2_serving_adaptive_goodput_tokens_per_sec"),
    ("BENCH_SERVE_TP", "1", "gpt2_serving_tp_tokens_per_sec"),
    ("BENCH_CKPT", "1", "gpt2_ckpt_async_stall_ms_per_step"),
]


def _metric_enabled(env: str) -> bool:
    default = next(d for e, d, _ in BENCH_METRICS if e == env)
    return os.environ.get(env, default) != "0"


def _metric_name(env: str) -> str:
    return next(n for e, _, n in BENCH_METRICS if e == env)


def run_decode_bench():
    """Serving decode throughput: the same mixed-length prompt set through
    the static per-request ``generate`` path (dense KV workspace) and the
    paged continuous-batching ``generate_batch`` path. The paged record's
    vs_baseline is its speedup over the dense record — the serving layer's
    trajectory number (BENCH is empty for inference before this)."""
    import time as _t

    import jax
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import gpt2

    dist.set_mesh(None)
    NREQ = int(os.environ.get("BENCH_DECODE_REQS", 16))
    MAX_NEW = int(os.environ.get("BENCH_DECODE_NEW", 128))
    BLOCK = int(os.environ.get("BENCH_DECODE_BLOCK", 128))
    RUNNING = int(os.environ.get("BENCH_DECODE_RUNNING", 8))
    model = gpt2("125m", remat=False,
                 attention_backend=os.environ.get("BENCH_ATTN", "auto"))
    _reset_telemetry()
    engine = deepspeed_tpu.init_inference(
        model, dtype="bf16", telemetry=True,
        serving={"block_size": BLOCK, "max_running": RUNNING,
                 # cache off: this metric tracks the PR-2 paged-decode
                 # trajectory — a warm-call cache hit skipping timed prefill
                 # would silently change what it measures (the prefix-cache
                 # win has its own BENCH_SERVE_PREFIX probe)
                 "prefix_caching": "off"})
    rng = np.random.default_rng(0)
    # mixed prompt lengths: the tail-convoy shape continuous batching wins on
    prompts = [rng.integers(0, 50257, size=int(n)).astype(np.int32)
               for n in rng.integers(32, 256, size=NREQ)]

    results = {}
    for gate, mode in (("BENCH_DECODE_DENSE", "off"),
                       ("BENCH_DECODE_PAGED", "auto")):
        if not _metric_enabled(gate):
            continue
        name = _metric_name(gate)
        # per-mode reset: the dense record's blob must not leak into the
        # paged one (warm-up compiles after the reset are part of that
        # mode's run and stay). Safe mid-engine: every telemetry handle on
        # the inference path re-resolves its registry family per use.
        _reset_telemetry()
        engine._config.serving.paged = mode
        # warm ONE prompt per 128-bucket present in the mix (the prefill
        # program compiles per bucket) with a max_new in the SAME 128-bucket
        # as the timed MAX_NEW (the dense decode loop's out buffer is keyed
        # by it) — an uncovered compile landing inside the timed window
        # would skew the metric
        buckets = {}
        for p in prompts:
            buckets.setdefault(-(-p.size // 128), p)
        # cheapest max_new in the SAME 128-bucket as MAX_NEW
        warm_new = 128 * ((MAX_NEW - 1) // 128) + 1
        warm = engine.generate_batch(list(buckets.values()),
                                     max_new_tokens=warm_new)
        jax.block_until_ready(warm)
        t0 = _t.perf_counter()
        outs = engine.generate_batch(prompts, max_new_tokens=MAX_NEW)
        gen_tokens = sum(int(o.shape[0]) - p.size
                         for p, o in zip(prompts, outs))
        dt = _t.perf_counter() - t0
        results[mode] = gen_tokens / dt
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "unknown").lower()
        vs = (round(results["auto"] / results["off"], 3)
              if mode == "auto" and results.get("off") else 0.0)
        rec = {
            "metric": name,
            "value": round(gen_tokens / dt, 1),
            "unit": f"generated tokens/s (bf16, {NREQ} reqs x {MAX_NEW} new, "
                    f"prompts 32-256, block={BLOCK}, running={RUNNING}, "
                    f"{kind})",
            "vs_baseline": vs,
        }
        tel = _telemetry_blob(engine)
        if tel:
            rec["telemetry"] = tel
        print(json.dumps(rec), flush=True)


def _serve_hist(engine, name, key):
    """One serving-histogram stat from the engine's telemetry snapshot."""
    h = engine.telemetry_snapshot().get("histograms", {}).get(name, {})
    return float(h.get(key, 0.0))


def run_prefix_cache_bench():
    """Shared-system-prompt serving probe: NREQ requests whose prompts all
    start with the same long prefix, prefix caching OFF vs ON. The ON
    record's value is its p50 TTFT and vs_baseline the OFF/ON TTFT ratio
    (>1 = caching cut time-to-first-token): request 1 prefills the shared
    blocks, every later admission hits them with zero prefill compute."""
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import gpt2

    dist.set_mesh(None)
    NREQ = int(os.environ.get("BENCH_SERVE_REQS", 8))
    SYS = int(os.environ.get("BENCH_SERVE_PREFIX_LEN", 768))
    TAIL, MAX_NEW = 32, int(os.environ.get("BENCH_SERVE_NEW", 16))
    model = gpt2("125m", remat=False,
                 attention_backend=os.environ.get("BENCH_ATTN", "auto"))
    rng = np.random.default_rng(0)
    system = rng.integers(0, 50257, size=SYS).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(0, 50257, size=TAIL)
                               .astype(np.int32)]) for _ in range(NREQ)]

    results = {}
    for mode in ("off", "auto"):
        _reset_telemetry()
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry=True,
            serving={"block_size": 128, "max_running": 8,
                     "prefix_caching": mode})
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)   # warm:
        # compiles, and (ON mode) the steady-state populated cache
        _reset_telemetry()
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)
        results[mode] = _serve_hist(engine, "serving/ttft_ms", "p50")
        if mode == "auto":
            rec = {
                "metric": _metric_name("BENCH_SERVE_PREFIX"),
                "value": round(results["auto"], 2),
                "unit": f"p50 TTFT ms (bf16, {NREQ} reqs sharing a {SYS}-tok "
                        f"prefix +{TAIL} tail, prefix cache on; off = "
                        f"{results['off']:.1f} ms)",
                # >1 = prefix caching sped TTFT up by this factor
                "vs_baseline": (round(results["off"] / results["auto"], 3)
                                if results["auto"] else 0.0),
            }
            tel = _telemetry_blob(engine)
            if tel:
                rec["telemetry"] = tel
            print(json.dumps(rec), flush=True)


def run_kv_tier_bench():
    """Tiered-KV re-hit probe at FORCED cache pressure: NREQ requests
    share a long prefix, then a scratch burst floods the (deliberately
    small) device pool so the shared prefix's cold blocks are reclaimed
    before the requests return. With ``kv_host`` off, reclaim destroys —
    the re-hit re-prefills the whole prefix; on, reclaim demotes to host
    RAM and the re-hit re-materializes it H2D. Value = p50 re-hit TTFT
    with tiering ON, vs_baseline = OFF/ON (>1 = spilling beat
    destroy-on-reclaim)."""
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import gpt2

    dist.set_mesh(None)
    NREQ = int(os.environ.get("BENCH_SERVE_REQS", 4))
    SYS = int(os.environ.get("BENCH_KV_TIER_PREFIX_LEN", 512))
    TAIL, MAX_NEW = 32, int(os.environ.get("BENCH_SERVE_NEW", 8))
    POOL = int(os.environ.get("BENCH_KV_TIER_BLOCKS", 24))
    model = gpt2("125m", remat=False,
                 attention_backend=os.environ.get("BENCH_ATTN", "auto"))
    rng = np.random.default_rng(0)
    system = rng.integers(0, 50257, size=SYS).astype(np.int32)
    prompts = [np.concatenate([system, rng.integers(0, 50257, size=TAIL)
                               .astype(np.int32)]) for _ in range(NREQ)]
    # the pressure burst: enough cold-block churn to reclaim every shared
    # block between re-hits (the tier's whole reason to exist)
    scratch = [rng.integers(0, 50257, size=SYS + 128).astype(np.int32)
               for _ in range(6)]

    results = {}
    for mode in (False, True):
        _reset_telemetry()
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry=True,
            serving={"block_size": 128, "max_running": 4,
                     "max_num_blocks": POOL,
                     "kv_host": {"enabled": mode}})
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)   # warm +
        # populate; the burst then reclaims (destroys or demotes) the
        # shared prefix's cold blocks
        engine.generate_batch(scratch, max_new_tokens=MAX_NEW)
        _reset_telemetry()
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)   # re-hit
        results[mode] = _serve_hist(engine, "serving/ttft_ms", "p50")
        if mode:
            snap = engine.telemetry_snapshot().get("counters", {})
            rec = {
                "metric": _metric_name("BENCH_KV_TIER"),
                "value": round(results[True], 2),
                "unit": f"p50 re-hit TTFT ms (bf16, {NREQ} reqs sharing a "
                        f"{SYS}-tok prefix, {POOL}-block pool + scratch "
                        f"burst; destroy-on-reclaim = "
                        f"{results[False]:.1f} ms; "
                        f"fetch_hits={int(snap.get('serving/kv_fetch_hits', 0))}"
                        f" spills={int(snap.get('serving/kv_spills', 0))})",
                # >1 = demote+fetch cut re-hit TTFT by this factor
                "vs_baseline": (round(results[False] / results[True], 3)
                                if results[True] else 0.0),
            }
            tel = _telemetry_blob(engine)
            if tel:
                rec["telemetry"] = tel
            print(json.dumps(rec), flush=True)
        del engine


def run_chunked_prefill_bench():
    """Decode-throughput interference probe: short requests decode while
    long prompts keep arriving and prefilling. Whole-prompt prefill stalls
    every running decode for the full prompt (TPOT tail spike); chunked
    prefill interleaves one chunk per decode step. Value = p99 TPOT with
    chunking ON, vs_baseline = OFF/ON p99 ratio (>1 = chunking cut the
    decode stall)."""
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import gpt2

    dist.set_mesh(None)
    LONG = int(os.environ.get("BENCH_SERVE_LONG_LEN", 896))
    CHUNK = int(os.environ.get("BENCH_SERVE_CHUNK", 256))
    MAX_NEW = int(os.environ.get("BENCH_SERVE_NEW", 16))
    model = gpt2("125m", remat=False,
                 attention_backend=os.environ.get("BENCH_ATTN", "auto"))
    rng = np.random.default_rng(0)
    # FIFO admission: the short prompts admit first and decode while each
    # long prompt prefills into a freed slot mid-run
    prompts = [rng.integers(0, 50257, size=64).astype(np.int32)
               for _ in range(3)]
    prompts += [rng.integers(0, 50257, size=LONG).astype(np.int32)
                for _ in range(4)]

    results = {}
    for chunk in (0, CHUNK):
        _reset_telemetry()
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry=True,
            serving={"block_size": 128, "max_running": 4,
                     "prefix_caching": "off", "prefill_chunk_tokens": chunk})
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)   # warm
        _reset_telemetry()
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)
        results[chunk] = _serve_hist(engine, "serving/tpot_ms", "p99")
        if chunk:
            rec = {
                "metric": _metric_name("BENCH_SERVE_CHUNKED"),
                "value": round(results[chunk], 2),
                "unit": f"p99 TPOT ms (bf16, 3 short decodes vs 4x{LONG}-tok "
                        f"prefills, chunk={chunk}; whole-prompt = "
                        f"{results[0]:.1f} ms)",
                "vs_baseline": (round(results[0] / results[chunk], 3)
                                if results[chunk] else 0.0),
            }
            tel = _telemetry_blob(engine)
            if tel:
                rec["telemetry"] = tel
            print(json.dumps(rec), flush=True)


def run_spec_decode_bench():
    """Speculative-decode probe: a repetitive / shared-pattern prompt set
    (the n-gram self-speculation sweet spot — templated text where the
    continuation has literally been seen before) decoded with
    ``serving.speculative`` OFF vs ON at the same greedy settings. Value =
    p50 TPOT with speculation on; vs_baseline = OFF/ON p50 TPOT ratio
    (>1 = fewer fused steps per emitted token); the same run's
    ``accepted_tokens_per_step`` and spec counters ride in the record's
    telemetry blob, so the acceptance rate that produced the speedup is
    part of the data point."""
    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import gpt2

    dist.set_mesh(None)
    NREQ = int(os.environ.get("BENCH_SERVE_SPEC_REQS", 8))
    K = int(os.environ.get("BENCH_SERVE_SPEC_K", 4))
    MAX_NEW = int(os.environ.get("BENCH_SERVE_SPEC_NEW", 64))
    MOTIF = int(os.environ.get("BENCH_SERVE_SPEC_MOTIF", 48))
    model = gpt2("125m", remat=False,
                 attention_backend=os.environ.get("BENCH_ATTN", "auto"))
    rng = np.random.default_rng(0)
    # repetitive prompts: a short unique PREFIX then a motif tiled several
    # times — the prompt's tail n-gram recurs earlier in the tiling, so
    # the proposer speculates from the very first decode turn (a unique
    # suffix would leave the tail unmatchable and measure nothing); greedy
    # loops then extend the win into generated text
    prompts = []
    for _ in range(NREQ):
        motif = rng.integers(0, 50257, size=MOTIF).astype(np.int32)
        prompts.append(np.concatenate(
            [rng.integers(0, 50257, size=8).astype(np.int32),
             np.tile(motif, 5)]))

    results, stats = {}, {}
    for mode in ("off", "ngram"):
        _reset_telemetry()
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry=True,
            serving={"block_size": 128, "max_running": 8,
                     # cache off: both modes pay identical prefill, so the
                     # TPOT delta is the multi-token decode win alone
                     "prefix_caching": "off",
                     "speculative": {"mode": mode, "k": K}})
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)   # warm
        _reset_telemetry()
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)
        results[mode] = _serve_hist(engine, "serving/tpot_ms", "p50")
        stats[mode] = dict(getattr(engine, "_last_serve_stats", {}) or {})
        if mode == "ngram":
            st = stats[mode]
            steps = st.get("decode_steps", 0) + st.get("verify_steps", 0)
            rec = {
                "metric": _metric_name("BENCH_SERVE_SPEC"),
                "value": round(results["ngram"], 3),
                "unit": f"p50 TPOT ms (bf16, {NREQ} reqs x {MAX_NEW} new, "
                        f"5x{MOTIF}-tok motif prompts, ngram k={K}; off = "
                        f"{results['off']:.2f} ms)",
                # >1 = speculation cut per-token latency by this factor
                "vs_baseline": (round(results["off"] / results["ngram"], 3)
                                if results["ngram"] else 0.0),
            }
            tel = _telemetry_blob(engine) or {}
            tel["accepted_tokens_per_step"] = (
                round(st.get("emitted_tokens", 0) / steps, 3) if steps
                else 0.0)
            tel["spec_stats"] = st
            rec["telemetry"] = tel
            print(json.dumps(rec), flush=True)
        # free this mode's engine (params + pools + executables) BEFORE
        # building the next one: both resident at once doubles peak HBM
        # and perturbs the very TPOT number the probe measures
        del engine


def _drive_open_loop(engine, prompts, gaps, max_new, consume,
                     injector=None, serving=None, sessions=None):
    """Shared Poisson open-loop driver for the async/chaos/dp serving
    probes: submit the seeded arrival trace (`sleep(gap)` then
    `add_request`) to a fresh ``AsyncServingEngine``, fan one
    ``consume(handle, rec)`` thread per request, join, drain — so the
    probes' goodput accounting can never drift methodologically.
    ``injector`` (a ``FaultInjector``) is installed for the run's
    duration. ``serving`` overrides the engine-wrapping default (the dp
    probe passes a ``ReplicaRouter`` — same ``add_request``/``shutdown``
    surface); ``sessions`` is an optional per-request session-key list
    (drives the router's affinity hash). Returns ``(recs, wall_seconds,
    serving)``; ``serving`` is already shut down (aborted if the drain
    failed)."""
    import threading
    import time as _t

    from deepspeed_tpu.inference.serve import AsyncServingEngine
    from deepspeed_tpu.utils import fault_injection as fi

    if serving is None:
        serving = AsyncServingEngine(engine, max_new_tokens=max_new)
    recs, threads = [], []
    t0 = _t.perf_counter()
    try:
        if injector is not None:
            fi.install(injector)
        for i, (p, gap) in enumerate(zip(prompts, gaps)):
            _t.sleep(gap)
            h = serving.add_request(
                p, session=sessions[i] if sessions else None)
            rec = {"tpot": [], "tokens": 0}
            th = threading.Thread(target=consume, args=(h, rec),
                                  daemon=True)
            th.start()
            recs.append(rec)
            threads.append(th)
        for th in threads:
            th.join(600)
        serving.shutdown(drain=True, timeout=600)
    finally:
        if injector is not None:
            fi.clear()
        if not serving._stopped:
            try:
                serving.shutdown(drain=False, timeout=60)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
    return recs, _t.perf_counter() - t0, serving


def run_async_serving_bench():
    """Open-loop async serving probe: Poisson arrivals (exponential
    inter-arrival gaps at BENCH_SERVE_ASYNC_RATE req/s, seeded — the
    trace replays) submitted to the always-on ``AsyncServingEngine``
    while earlier requests are mid-decode — the arrival pattern
    ``generate_batch`` benches can never produce. Value = GOODPUT at a
    p99 TPOT target: generated tokens/s counted only from requests whose
    own p99 per-token latency met BENCH_SERVE_ASYNC_TPOT_MS;
    vs_baseline = goodput / raw throughput (SLO attainment, 1.0 = every
    request met the target). The same run exercises the open-loop
    telemetry (TTFT/TPOT/queue-wait histograms ride the record's blob)
    and the flight recorder — the per-request chrome trace is exported
    next to the tempdir and its path embedded."""
    import tempfile
    import time as _t

    import numpy as np

    RATE = float(os.environ.get("BENCH_SERVE_ASYNC_RATE", 8.0))
    NREQ = int(os.environ.get("BENCH_SERVE_ASYNC_REQS", 24))
    MAX_NEW = int(os.environ.get("BENCH_SERVE_ASYNC_NEW", 32))
    TARGET = float(os.environ.get("BENCH_SERVE_ASYNC_TPOT_MS", 50.0))
    engine = sampler = None
    try:
        import deepspeed_tpu
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.models import gpt2

        dist.set_mesh(None)
        _reset_telemetry()
        model = gpt2("125m", remat=False,
                     attention_backend=os.environ.get("BENCH_ATTN", "auto"))
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry={"events": True},
            serving={"block_size": 128, "max_running": 8,
                     "prefix_caching": "off"})
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 50257, size=int(n)).astype(np.int32)
                   for n in rng.integers(64, 192, size=NREQ)]
        gaps = rng.exponential(1.0 / max(RATE, 1e-6), size=NREQ)
        # warm the fused programs CLOSED-loop — the open loop reuses them
        # (the serving_async_steady contract), so compile time never
        # pollutes the measured arrival window
        engine.generate_batch(prompts[:2], max_new_tokens=MAX_NEW)
        _reset_telemetry()

        def consume(h, rec):
            last = None
            for burst in h.stream():
                now = _t.perf_counter()
                if last is not None:
                    rec["tpot"] += [(now - last) / len(burst)] * len(burst)
                last = now
                rec["tokens"] += len(burst)
            rec["status"] = h.status

        # the SLO plane rides the run: default serving objectives at the
        # probe's own TPOT target, evaluated on background sampler ticks
        # (zero compiles — the serving_metrics_steady contract), so the
        # record can report whether the burn-rate alerts fired
        from deepspeed_tpu.monitor.sampler import MetricsSampler
        from deepspeed_tpu.monitor.slo import (SloEngine, parse_objectives,
                                               serving_objectives)
        slo = SloEngine(
            parse_objectives(serving_objectives(tpot_p99_ms=TARGET),
                             default_windows=[16, 4]),
            events=engine._events)
        sampler = MetricsSampler(interval_s=0.25, slo=slo).start()

        recs, wall, _serving = _drive_open_loop(engine, prompts, gaps,
                                                MAX_NEW, consume)
        sampler.stop()                  # final tick lands shutdown state

        good = total = met = 0
        for rec in recs:
            total += rec["tokens"]
            p99_ms = (float(np.percentile(rec["tpot"], 99)) * 1e3
                      if rec["tpot"] else 0.0)
            if rec.get("status") == "finished" and p99_ms <= TARGET:
                good += rec["tokens"]
                met += 1
        goodput = good / wall if wall > 0 else 0.0
        throughput = total / wall if wall > 0 else 0.0
        out = {
            "metric": _metric_name("BENCH_SERVE_ASYNC"),
            "value": round(goodput, 1),
            "unit": f"goodput tokens/s (bf16 open loop, Poisson {RATE}/s x "
                    f"{NREQ} reqs x {MAX_NEW} new, p99 TPOT target "
                    f"{TARGET:.0f} ms: {met}/{NREQ} requests met it; raw "
                    f"throughput = {throughput:.1f} tok/s)",
            # SLO attainment: 1.0 = every request inside the TPOT target
            "vs_baseline": (round(goodput / throughput, 3)
                            if throughput else 0.0),
        }
        tel = _telemetry_blob(engine) or {}
        tel["slo_met_requests"] = met
        tel["throughput_tokens_per_sec"] = round(throughput, 1)
        # final registry snapshot (the sampler's last tick) + any SLO
        # breach events the burn-rate engine fired during the run
        if sampler.ring:
            final = dict(sampler.ring[-1])
            final.pop("ts", None)
            tel["final_metrics_snapshot"] = final
        from deepspeed_tpu.monitor.health import labeled_series
        breaches = {k: int(v) for k, v in labeled_series(
            (engine.telemetry_snapshot() or {}).get("counters", {}),
            "slo/breaches").items() if v}
        if breaches:
            tel["slo_breaches"] = breaches
        ev = engine._events
        if ev is not None:
            breach_events = [e.to_dict() for e in ev.snapshot()
                             if e.kind == "slo.breach"]
            if breach_events:
                tel["slo_breach_events"] = breach_events
        trace_path = os.path.join(tempfile.gettempdir(),
                                  "bench_serve_async_trace.json")
        try:
            # the open-loop per-request chrome trace, finally exercised
            # under realistic arrivals (ROADMAP item 1's telemetry ask)
            tel["serving_trace"] = engine.export_serving_trace(trace_path)
        except Exception:  # noqa: BLE001 — trace export is best-effort
            pass
        out["telemetry"] = tel
        print(json.dumps(out), flush=True)
    finally:
        # the open-loop driver owns the serving teardown
        if sampler is not None:
            sampler.stop(final_tick=False)
        del engine


def run_serve_chaos_bench():
    """Serving fault-tolerance probe: the Poisson-arrival async goodput
    run executed twice on one engine — CLEAN, then again under a SEEDED
    fault-injection schedule (one engine-fatal fault that forces a
    crash-safe engine restart, plus scattered per-request step faults that
    exercise retry/backoff containment). Value = the faulted run's goodput
    (generated tokens/s over FINISHED requests); vs_baseline = GOODPUT
    RETENTION, faulted/clean — 1.0 means the fault-tolerance spine cost
    nothing, 0 means the loop died (it must not: a crashed loop fails the
    probe). Restart/retry/quarantine counters and the
    step-fault breakdown ride the record's telemetry blob."""
    import numpy as np

    RATE = float(os.environ.get("BENCH_SERVE_CHAOS_RATE", 8.0))
    NREQ = int(os.environ.get("BENCH_SERVE_CHAOS_REQS", 16))
    MAX_NEW = int(os.environ.get("BENCH_SERVE_CHAOS_NEW", 32))
    engine = None
    try:
        import deepspeed_tpu
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.models import gpt2
        from deepspeed_tpu.utils import fault_injection as fi

        dist.set_mesh(None)
        _reset_telemetry()
        model = gpt2("125m", remat=False,
                     attention_backend=os.environ.get("BENCH_ATTN", "auto"))
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry={"events": True},
            serving={"block_size": 128, "max_running": 8,
                     "prefix_caching": "off"})
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 50257, size=int(n)).astype(np.int32)
                   for n in rng.integers(64, 192, size=NREQ)]
        gaps = rng.exponential(1.0 / max(RATE, 1e-6), size=NREQ)
        # closed-loop warm-up so neither run pays compile time in its
        # arrival window (the faulted run recompiles once mid-run by
        # design — that recovery cost IS part of what it measures)
        engine.generate_batch(prompts[:2], max_new_tokens=MAX_NEW)

        def consume(h, rec):
            for burst in h.stream():
                rec["tokens"] += len(burst)
            rec["status"] = h.status

        def one_run(injector):
            recs, wall, serving = _drive_open_loop(
                engine, prompts, gaps, MAX_NEW, consume, injector=injector)
            good = sum(r["tokens"] for r in recs
                       if r.get("status") == "finished")
            done = sum(r.get("status") == "finished" for r in recs)
            return (good / wall if wall > 0 else 0.0, done,
                    serving.restarts)

        clean, clean_done, _ = one_run(None)
        _reset_telemetry()       # the record's blob describes the faulted run
        # the seeded schedule: an engine-fatal mid-run + per-request
        # faults scattered through the action stream (deterministic given
        # the injector's step counter)
        inj = fi.FaultInjector()
        inj.fail_step("decode", at_step=max(NREQ, 8), count=1, phase="post")
        inj.fail_step("prefill", at_step=3, count=1)
        inj.fail_step("decode", at_step=2 * max(NREQ, 8), count=1)
        faulted, faulted_done, restarts = one_run(inj)

        out = {
            "metric": _metric_name("BENCH_SERVE_CHAOS"),
            "value": round(faulted, 1),
            "unit": f"goodput tokens/s under injected faults (bf16 open "
                    f"loop, Poisson {RATE}/s x {NREQ} reqs x {MAX_NEW} "
                    f"new; 1 engine-fatal + 2 per-request faults; "
                    f"{faulted_done}/{NREQ} finished vs {clean_done}/"
                    f"{NREQ} clean at {clean:.1f} tok/s)",
            # goodput retention: how much serving capacity survives the
            # fault schedule (restart recompiles + recompute retries)
            "vs_baseline": round(faulted / clean, 3) if clean else 0.0,
        }
        tel = _telemetry_blob(engine) or {}
        tel["engine_restarts"] = restarts
        out["telemetry"] = tel
        print(json.dumps(out), flush=True)
    finally:
        del engine


def run_serve_adaptive_bench():
    """Adaptive-autopilot spike probe: one engine, the same seeded
    Poisson arrival trace with a MID-TRACE ARRIVAL SPIKE (the middle
    third's inter-arrival gaps divided by BENCH_CTL_SPIKE), driven twice
    — STATIC first (controller off: the config posture rides the spike),
    then ADAPTIVE (the monitor/controller.py burn-rate autopilot ticking
    on a background sampler, actions applied between engine steps — the
    ``dscli serve --adaptive`` wiring). Value = the adaptive run's
    goodput at the p99 TPOT target (the async probe's definition:
    tokens/s from finished requests whose own p99 TPOT met it);
    vs_baseline = adaptive/static goodput — above 1.0 the autopilot
    bought goodput under the spike. Per-run SLO breach / shed /
    knob-action counts plus the decision ledger's audit lines ride the
    telemetry blob."""
    import time as _t

    import numpy as np

    RATE = float(os.environ.get("BENCH_CTL_RATE", 6.0))
    NREQ = int(os.environ.get("BENCH_CTL_REQS", 18))
    MAX_NEW = int(os.environ.get("BENCH_CTL_NEW", 32))
    TARGET = float(os.environ.get("BENCH_CTL_TPOT_MS", 50.0))
    SPIKE = max(float(os.environ.get("BENCH_CTL_SPIKE", 6.0)), 1.0)
    engine = None
    try:
        import deepspeed_tpu
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.inference.serve import AsyncServingEngine
        from deepspeed_tpu.models import gpt2
        from deepspeed_tpu.monitor.controller import (AdaptiveController,
                                                      explain_decisions,
                                                      knobs_from_serving)
        from deepspeed_tpu.monitor.health import (labeled_series,
                                                  multilabel_series)
        from deepspeed_tpu.monitor.sampler import MetricsSampler
        from deepspeed_tpu.monitor.slo import (SloEngine, parse_objectives,
                                               serving_objectives)

        dist.set_mesh(None)
        _reset_telemetry()
        model = gpt2("125m", remat=False,
                     attention_backend=os.environ.get("BENCH_ATTN", "auto"))
        # chunked prefill gives the controller a real prefill_chunk
        # ladder; admission/shed knobs bootstrap from the default policy
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry={"events": True},
            serving={"block_size": 128, "max_running": 8,
                     "prefix_caching": "off",
                     "prefill_chunk_tokens": 256})
        rng = np.random.default_rng(19)
        prompts = [rng.integers(0, 50257, size=int(n)).astype(np.int32)
                   for n in rng.integers(64, 192, size=NREQ)]
        gaps = rng.exponential(1.0 / max(RATE, 1e-6), size=NREQ)
        # the spike: the middle third arrives SPIKE x faster than the
        # steady Poisson rate — the burn the autopilot is built to read
        lo, hi = NREQ // 3, 2 * NREQ // 3
        gaps[lo:hi] /= SPIKE
        # closed-loop warm-up: both runs reuse the warm programs, and
        # every knob-ladder rung stays inside the compiled buckets (the
        # serving_adaptive_steady contract), so neither run pays compile
        # time inside its measured arrival window
        engine.generate_batch(prompts[:2], max_new_tokens=MAX_NEW)

        def consume(h, rec):
            last = None
            for burst in h.stream():
                now = _t.perf_counter()
                if last is not None:
                    rec["tpot"] += [(now - last) / len(burst)] * len(burst)
                last = now
                rec["tokens"] += len(burst)
            rec["status"] = h.status

        def one_run(adaptive):
            _reset_telemetry()
            serving = AsyncServingEngine(engine, max_new_tokens=MAX_NEW)
            slo = SloEngine(
                parse_objectives(serving_objectives(tpot_p99_ms=TARGET),
                                 default_windows=[16, 4]),
                events=engine._events)
            ctl = None
            if adaptive:
                ctl = AdaptiveController(
                    knobs_from_serving(engine.config.serving,
                                       policy=serving.policy),
                    events=engine._events,
                    apply_fn=serving.apply_knobs)
            sampler = MetricsSampler(interval_s=0.2, slo=slo,
                                     ctl=ctl).start()
            try:
                recs, wall, _serving = _drive_open_loop(
                    engine, prompts, gaps, MAX_NEW, consume,
                    serving=serving)
            finally:
                sampler.stop(final_tick=False)
            good = met = 0
            for rec in recs:
                p99_ms = (float(np.percentile(rec["tpot"], 99)) * 1e3
                          if rec["tpot"] else 0.0)
                if rec.get("status") == "finished" and p99_ms <= TARGET:
                    good += rec["tokens"]
                    met += 1
            counters = (engine.telemetry_snapshot() or {}).get(
                "counters", {})
            return {
                "goodput": good / wall if wall > 0 else 0.0,
                "met": met,
                "breaches": int(sum(labeled_series(
                    counters, "slo/breaches").values())),
                "shed": int(counters.get("serving/shed_requests", 0)),
                "actions": int(sum(v for _, v in multilabel_series(
                    counters, "ctl/actions"))),
            }

        static = one_run(adaptive=False)
        adapt = one_run(adaptive=True)

        out = {
            "metric": _metric_name("BENCH_CTL"),
            "value": round(adapt["goodput"], 1),
            "unit": f"goodput tokens/s under a {SPIKE:.0f}x arrival spike "
                    f"(bf16 open loop, Poisson {RATE}/s x {NREQ} reqs x "
                    f"{MAX_NEW} new, p99 TPOT target {TARGET:.0f} ms; "
                    f"adaptive {adapt['met']}/{NREQ} met it with "
                    f"{adapt['breaches']} SLO breaches vs static "
                    f"{static['met']}/{NREQ} with {static['breaches']} "
                    f"at {static['goodput']:.1f} tok/s)",
            # the autopilot's value: goodput bought (or lost) vs riding
            # the spike in the static config posture
            "vs_baseline": (round(adapt["goodput"] / static["goodput"], 3)
                            if static["goodput"] else 0.0),
        }
        tel = _telemetry_blob(engine) or {}
        for label, run in (("static", static), ("adaptive", adapt)):
            tel[label] = {"goodput_tokens_per_sec": round(run["goodput"], 1),
                          "slo_met_requests": run["met"],
                          "slo_breaches": run["breaches"],
                          "shed_requests": run["shed"]}
        tel["ctl_actions"] = adapt["actions"]
        ev = engine._events
        if ev is not None:
            ledger = explain_decisions(
                e.to_dict() for e in ev.snapshot())
            if ledger:
                tel["ctl_ledger"] = ledger[:40]
        out["telemetry"] = tel
        print(json.dumps(out), flush=True)
    finally:
        del engine


def run_serve_dp_bench():
    """Replica scale-out probe: the SAME seeded Poisson arrival trace
    through one ``AsyncServingEngine`` (dp=1) and through a two-replica
    ``ReplicaRouter`` with session affinity (dp=2, replicas share the
    model params — per-replica state is just the KV pools). Value = the
    dp=2 run's goodput (generated tokens/s over FINISHED requests);
    vs_baseline = SCALING EFFICIENCY, (goodput_dp2 / goodput_dp1) / 2 —
    1.0 means a second serving replica doubles goodput, and on a
    single-chip box the number quantifies how much of the dp axis is
    compute-bound (replicas time-slice one chip) vs queue-bound (open-
    loop arrivals wait less when two intakes drain the backlog).
    Per-replica routing counters ride the record's telemetry blob."""
    import numpy as np

    RATE = float(os.environ.get("BENCH_SERVE_DP_RATE", 8.0))
    NREQ = int(os.environ.get("BENCH_SERVE_DP_REQS", 16))
    MAX_NEW = int(os.environ.get("BENCH_SERVE_DP_NEW", 32))
    engines = []
    try:
        import deepspeed_tpu
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.inference.router import ReplicaRouter
        from deepspeed_tpu.inference.serve import AsyncServingEngine
        from deepspeed_tpu.models import gpt2

        dist.set_mesh(None)
        _reset_telemetry()
        model = gpt2("125m", remat=False,
                     attention_backend=os.environ.get("BENCH_ATTN", "auto"))
        serving_cfg = {"block_size": 128, "max_running": 8,
                       "prefix_caching": "off"}
        engines.append(deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry={"events": True},
            serving=serving_cfg))
        engines.append(deepspeed_tpu.init_inference(
            model, params=engines[0].params, dtype="bf16",
            telemetry={"events": True}, serving=serving_cfg))
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 50257, size=int(n)).astype(np.int32)
                   for n in rng.integers(64, 192, size=NREQ)]
        gaps = rng.exponential(1.0 / max(RATE, 1e-6), size=NREQ)
        # one session per request: the affinity hash spreads fresh
        # sessions over the ring deterministically
        sessions = [f"dp-bench-{i}" for i in range(NREQ)]
        # closed-loop warm-up on BOTH replicas so neither run pays
        # compile time inside its measured arrival window
        for e in engines:
            e.generate_batch(prompts[:2], max_new_tokens=MAX_NEW)
        _reset_telemetry()

        def consume(h, rec):
            for burst in h.stream():
                rec["tokens"] += len(burst)
            rec["status"] = h.status

        def one_run(serving):
            recs, wall, serving = _drive_open_loop(
                engines[0], prompts, gaps, MAX_NEW, consume,
                serving=serving, sessions=sessions)
            good = sum(r["tokens"] for r in recs
                       if r.get("status") == "finished")
            done = sum(r.get("status") == "finished" for r in recs)
            return (good / wall if wall > 0 else 0.0), done

        dp1, dp1_done = one_run(
            AsyncServingEngine(engines[0], max_new_tokens=MAX_NEW))
        _reset_telemetry()       # the record's blob describes the dp=2 run
        dp2, dp2_done = one_run(ReplicaRouter(
            [AsyncServingEngine(e, max_new_tokens=MAX_NEW)
             for e in engines]))

        eff = (dp2 / dp1) / 2 if dp1 else 0.0
        out = {
            "metric": _metric_name("BENCH_SERVE_DP"),
            "value": round(dp2, 1),
            "unit": f"goodput tokens/s at dp=2 (bf16 open loop, Poisson "
                    f"{RATE}/s x {NREQ} reqs x {MAX_NEW} new, session-"
                    f"affine router; {dp2_done}/{NREQ} finished vs "
                    f"{dp1_done}/{NREQ} at dp=1, {dp1:.1f} tok/s)",
            # replica scaling efficiency: 1.0 = second replica doubles
            # goodput (expect << 1.0 when both time-slice one chip)
            "vs_baseline": round(eff, 3),
        }
        tel = _telemetry_blob(engines[0]) or {}
        from deepspeed_tpu.monitor.health import labeled_series
        counters = (engines[0].telemetry_snapshot() or {}).get(
            "counters", {})
        routed = {k: int(v) for k, v in labeled_series(
            counters, "router/requests").items()}
        if routed:
            tel["router_requests"] = routed
        out["telemetry"] = tel
        print(json.dumps(out), flush=True)
    finally:
        del engines


def run_serving_tp_bench():
    """Tensor-parallel serving scaling probe: the same mixed prompt set
    through the paged engine at serving.tp=1 and serving.tp=N on one
    slice. Value = paged decode throughput (generated tokens/s) at tp=N;
    vs_baseline = SCALING EFFICIENCY, (tpN tokens/s ÷ tp1 tokens/s) ÷ N —
    1.0 means decode scales linearly with the slice, and anything near it
    means one model's max size scales with the slice too (params and KV
    pools are really sharded: per-chip bytes drop to 1/N). Emits a skip
    record on a single-device backend (nothing to shard over)."""
    import time as _t

    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist

    import jax
    n_dev = jax.device_count()
    if n_dev < 2:
        print(json.dumps({
            "metric": _metric_name("BENCH_SERVE_TP"),
            "value": 0.0,
            "unit": "tokens/s (skipped: single-device backend, nothing to "
                    "shard over)",
            "vs_baseline": 0.0,
            "skipped": True,
            "skip_stage": "single_device",
            "skip_error": f"jax.device_count()={n_dev}",
        }), flush=True)
        return

    from deepspeed_tpu.models import gpt2
    model = gpt2("125m", remat=False,
                 attention_backend=os.environ.get("BENCH_ATTN", "auto"))
    heads = model.config.kv_heads
    tp_env = os.environ.get("BENCH_SERVE_TP_N", "auto")
    if tp_env == "auto":
        # largest tp <= min(devices, 4) that divides BOTH the device count
        # and the KV heads (gpt2-125m: 12 heads -> 2, 3, 4 all legal);
        # no legal degree (e.g. 5 devices) -> skip record, not a crash
        TP = max((t for t in range(2, min(n_dev, 4) + 1)
                  if n_dev % t == 0 and heads % t == 0), default=0)
    else:
        TP = int(tp_env)
    if TP < 2:
        print(json.dumps({
            "metric": _metric_name("BENCH_SERVE_TP"),
            "value": 0.0,
            "unit": "tokens/s (skipped: no tp in 2..4 divides both "
                    f"device count {n_dev} and kv heads {heads})",
            "vs_baseline": 0.0,
            "skipped": True,
            "skip_stage": "no_divisible_tp",
            "skip_error": f"devices={n_dev}, kv_heads={heads}",
        }), flush=True)
        return
    NREQ = int(os.environ.get("BENCH_SERVE_TP_REQS", 8))
    MAX_NEW = int(os.environ.get("BENCH_SERVE_TP_NEW", 64))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 50257, size=int(n)).astype(np.int32)
               for n in rng.integers(64, 192, size=NREQ)]

    results = {}
    for tp in (1, TP):
        dist.set_mesh(None)
        _reset_telemetry()
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", telemetry=True,
            serving={"block_size": 128, "max_running": 8,
                     # cold decode both times: the cache win is
                     # BENCH_SERVE_PREFIX's story, this one is scaling
                     "prefix_caching": "off", "tp": tp})
        engine.generate_batch(prompts, max_new_tokens=MAX_NEW)   # warm
        t0 = _t.perf_counter()
        outs = engine.generate_batch(prompts, max_new_tokens=MAX_NEW)
        dt = _t.perf_counter() - t0
        gen = sum(int(o.shape[0]) - len(p) for o, p in zip(outs, prompts))
        results[tp] = gen / dt
        if tp == TP:
            rec = {
                "metric": _metric_name("BENCH_SERVE_TP"),
                "value": round(results[TP], 1),
                "unit": f"generated tokens/s (bf16 paged decode, tp={TP} "
                        f"over {n_dev} devices, {NREQ} reqs x {MAX_NEW} "
                        f"new; tp=1 = {results[1]:.1f} tok/s)",
                # scaling efficiency: 1.0 = linear decode scaling
                "vs_baseline": (round(results[TP] / results[1] / TP, 3)
                                if results[1] else 0.0),
            }
            tel = _telemetry_blob(engine)
            if tel:
                rec["telemetry"] = tel
            print(json.dumps(rec), flush=True)
        del engine


def run_checkpoint_bench():
    """Async-checkpoint stall probe: the same training loop with and
    without a two-phase async save in flight. Phase 1 (device->host
    snapshot) runs on the training thread; phase 2 (serialize+fsync+commit)
    on the background writer — the metric is the per-step stall the whole
    mechanism adds, with checkpoint/save_ms + /bytes from the same run
    embedded in the record's telemetry blob. BENCH_CKPT_STEPS overrides the
    window; BENCH_CKPT_EVERY the save cadence (steps per async save)."""
    import shutil
    import tempfile
    import time as _t

    steps = max(4, int(os.environ.get("BENCH_CKPT_STEPS",
                                      os.environ.get("BENCH_STEPS", 10))))
    every = max(1, int(os.environ.get("BENCH_CKPT_EVERY", 2)))
    engine, model, batch, knobs = build_bench_engine()
    # bound the probe's disk footprint: retention keeps the 2 newest tags
    engine._config.checkpoint_config.keep_last = 2
    save_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        float(engine.train_batch(batch()))  # warmup/compile

        def _window(save: bool):
            times = []
            for i in range(steps):
                t0 = _t.perf_counter()
                loss = engine.train_batch(batch())
                if save and i % every == 0:
                    engine.save_checkpoint(save_dir, asynchronous=True)
                float(loss)  # per-step sync: the stall is a step-time delta
                times.append((_t.perf_counter() - t0) * 1e3)
            return sum(times) / len(times)

        base_ms = _window(save=False)
        with_ms = _window(save=True)
        engine.flush_checkpoints()
        stall = with_ms - base_ms
        rec = {
            "metric": _metric_name("BENCH_CKPT"),
            "value": round(stall, 3),
            "unit": f"ms/step added by async save every {every} steps "
                    f"(base {base_ms:.1f} -> {with_ms:.1f} ms/step, "
                    f"{steps}-step windows)",
            # <=1.0 means the async save is (near-)stall-free
            "vs_baseline": round(with_ms / base_ms, 4),
        }
        tel = _telemetry_blob(engine)
        if tel:
            rec["telemetry"] = tel
        print(json.dumps(rec), flush=True)
    finally:
        try:
            engine.destroy()   # stop the writer thread so the engine can GC
        except Exception:
            pass
        shutil.rmtree(save_dir, ignore_errors=True)


def main():
    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    try:
        dev = require_tpu()
    except RuntimeError as e:
        sys.exit(f"bench: {e}")
    print(f"bench: {dev['count']} x {dev['kind']} ({dev['platform']}); "
          f"compile cache at {enable_compile_cache()}", file=sys.stderr)

    STEPS = int(os.environ.get("BENCH_STEPS", 10))
    if STEPS < 1:
        print("bench: BENCH_STEPS must be >= 1", file=sys.stderr)
        sys.exit(1)
    engine = None
    if _metric_enabled("BENCH_GPT2"):
        engine, model, batch, knobs = build_bench_engine()
        _run_metric(_metric_name("BENCH_GPT2"), engine, model,
                    batch, knobs["BATCH"], knobs["SEQ"], STEPS,
                    f"ZeRO-1, remat={knobs['remat_env']}, "
                    f"fused_ce={knobs['FUSED_CE']}, "
                    f"loss_chunk={knobs['LOSS_CHUNK']}")

    if _metric_enabled("BENCH_LLAMA"):
        # free the first engine's device state before the larger model lands
        if engine is not None:
            del engine, model, batch
        import gc
        gc.collect()
        engine, model, batch, knobs = build_llama_bench_engine()
        _run_metric(_metric_name("BENCH_LLAMA"),
                    engine, model, batch, knobs["BATCH"], knobs["SEQ"],
                    STEPS, "GQA 12q/4kv hd128, ZeRO-3, remat=dots")

    if _metric_enabled("BENCH_BERT"):
        if engine is not None:
            del engine, model, batch
        import gc
        gc.collect()
        engine, model, batch, knobs = build_bert_bench_engine()
        _run_metric(_metric_name("BENCH_BERT"),
                    engine, model, batch, knobs["BATCH"], knobs["SEQ"],
                    STEPS, "MLM, ZeRO-2")

    if _metric_enabled("BENCH_CKPT"):
        if engine is not None:
            del engine, model, batch
        import gc
        gc.collect()
        run_checkpoint_bench()
        engine = None

    if any(_metric_enabled(g) for g in
           ("BENCH_DECODE_DENSE", "BENCH_DECODE_PAGED",
            "BENCH_SERVE_PREFIX", "BENCH_KV_TIER", "BENCH_SERVE_CHUNKED",
            "BENCH_SERVE_SPEC", "BENCH_SERVE_ASYNC", "BENCH_SERVE_CHAOS",
            "BENCH_SERVE_DP", "BENCH_CTL", "BENCH_SERVE_TP")):
        # free the last training engine's device state before serving
        if engine is not None:
            del engine, model, batch
        import gc
        gc.collect()
        if _metric_enabled("BENCH_DECODE_DENSE") \
                or _metric_enabled("BENCH_DECODE_PAGED"):
            run_decode_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_PREFIX"):
            run_prefix_cache_bench()
            gc.collect()
        if _metric_enabled("BENCH_KV_TIER"):
            run_kv_tier_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_CHUNKED"):
            run_chunked_prefill_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_SPEC"):
            run_spec_decode_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_ASYNC"):
            run_async_serving_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_CHAOS"):
            run_serve_chaos_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_DP"):
            run_serve_dp_bench()
            gc.collect()
        if _metric_enabled("BENCH_CTL"):
            run_serve_adaptive_bench()
            gc.collect()
        if _metric_enabled("BENCH_SERVE_TP"):
            run_serving_tp_bench()


if __name__ == "__main__":
    main()
