"""Monitor (TensorBoard / W&B / CSV) config.

Reference parity: ``deepspeed/monitor/config.py``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from pydantic import Field

from deepspeed_tpu.config.config_utils import ConfigModel


def get_monitor_config(param_dict: dict) -> "DeepSpeedMonitorConfig":
    monitor_dict = {key: param_dict.get(key, {}) for key in ("tensorboard", "wandb", "csv_monitor")}
    return DeepSpeedMonitorConfig(**monitor_dict)


class HealthConfig(ConfigModel):
    """"telemetry.health" sub-block: the training health observatory
    (``monitor/health.py``). Off by default; enabling it turns on the
    on-device numerics sentinels inside the compiled train step plus the
    host-side anomaly detectors over the per-step ring buffer."""
    enabled: bool = False
    # device-side sentinel collection (non-finite counts, param/update
    # norms, per-layer-group buckets) inside the compiled step. Off keeps
    # only the host-side detectors (loss, grad norm, skips, wall times) —
    # zero in-step overhead beyond the grad-norm reuse telemetry records
    sentinels: bool = True
    # what firing detectors do: "record" = counters only, "warn" = + a
    # rate-limited log line, "dump" = + a debug bundle on disk
    action: str = "warn"
    # ring-buffer length AND the per-detector warning/dump rate limit
    window: int = 50
    # loss spike: robust z-score against an EWMA mean/variance
    loss_spike_zscore: float = 6.0
    loss_ewma_alpha: float = 0.02
    # spike/explosion detectors hold fire for this many steps
    warmup_steps: int = 10
    # grad-norm explosion: fire when norm > factor x its EWMA
    grad_norm_factor: float = 10.0
    # plateau: no relative loss improvement for this many steps (0 = off)
    plateau_steps: int = 0
    plateau_rel_improvement: float = 1e-3
    # sustained fp16 overflow: consecutive skipped steps before the alarm
    # (also the rate limit of the engine's health-off skip warning)
    overflow_window: int = 25
    # repeated checkpoint failure: consecutive failed saves (sync or async)
    # before the ckpt_failure detector fires (0 = off)
    ckpt_failure_consecutive: int = 2
    # data stall: wait/(wait+step) above the fraction for this many
    # consecutive steps means the input pipeline is the bottleneck
    data_stall_fraction: float = 0.5
    data_stall_steps: int = 10
    # debug bundles (action: dump)
    dump_dir: str = "ds_health_dumps"
    dump_limit: int = 3
    keep_last_steps: int = 200
    # per-layer-group grad-norm buckets in the sentinel vector
    max_norm_buckets: int = 8


class EventsConfig(ConfigModel):
    """"telemetry.events" sub-block: the flight recorder
    (``monitor/events.py``) — a bounded ring of structured lifecycle
    events (train step/phase/skip, checkpoint phases, serving request
    lifecycle) with monotonic-ns timestamps. Off by default; when off
    every emit site costs one flag/None check and allocates nothing."""
    enabled: bool = False
    # ring size (events). The recorder keeps the NEWEST `capacity` events
    # and counts evictions — post-mortems want the tail, not the head.
    capacity: int = 16384


class ProfileConfig(ConfigModel):
    """"telemetry.profile" sub-block: an on-demand ``jax.profiler``
    capture window. ``num_steps > 0`` arms it: the capture starts at the
    ``start_step``-th train_batch call of this process and stops
    ``num_steps`` later, writing a TensorBoard/xprof profile under
    ``dir`` (summarize with ``dscli profile <dir>``). The host-side
    ``TraceAnnotation`` names pushed while capturing match the
    StepTracer span names, so host spans line up with the device
    timeline. ``engine.profile(steps=N)`` arms the same window
    programmatically."""
    start_step: int = 0
    num_steps: int = 0      # 0 = no config-armed capture window
    dir: str = "ds_profile"


class SamplerConfig(ConfigModel):
    """"telemetry.sampler" sub-block: the background snapshot daemon
    (``monitor/sampler.py``) — periodic registry snapshots appended to a
    rotated JSONL time series plus an in-memory ring (the SLO engine's
    input and ``dscli top``'s offline source). The sampler thread does
    host-side dict work ONLY: zero device work, zero added compiles
    (pinned by the ``serving_metrics_steady`` contract and dslint
    DS009)."""
    enabled: bool = False
    # seconds between snapshots (the background thread's cadence; tests
    # and trace replay drive tick() directly instead)
    interval_s: float = 1.0
    # JSONL sink (None = ring only). Rotated at max_bytes: path -> path.1
    # -> ... -> path.<keep>, oldest dropped
    path: Optional[str] = None
    max_bytes: int = 16 << 20
    keep: int = 2
    # in-memory snapshot ring length (newest retained)
    ring: int = 512


class SloConfig(ConfigModel):
    """"telemetry.slo" sub-block: service-level objectives evaluated by
    ``monitor/slo.py`` as multi-window burn rates over the sampler's
    ring. Each objective dict declares either a latency target
    (``{"name": "ttft_p99", "metric": "serving/ttft_ms", "kind":
    "latency", "threshold_ms": 500, "objective": 0.99}``: at most 1 % of
    observations above 500 ms) or a ratio (``{"kind": "ratio", "metric":
    "serving/rejected_requests", "total_metric": "serving/requests",
    "objective": 0.999}``). Breaches emit ``slo.breach`` flight-recorder
    events, increment ``slo/breaches{objective=}``, and surface in
    ``health_summary`` / ``dscli top``. Enabling SLOs implies the
    sampler (something must tick the evaluation)."""
    enabled: bool = False
    objectives: List[Dict] = Field(default_factory=list)
    # default evaluation windows in sampler ticks (long, short): a breach
    # needs EVERY window burning — the long window proves sustained
    # budget loss, the short one proves it is still happening now
    windows: List[int] = Field(default_factory=lambda: [60, 5])
    # burn-rate alarm level: 1.0 = budget exhausted exactly at the SLO
    # period's end; paging setups usually alarm well above 1
    burn_rate_threshold: float = 1.0


class CtlConfig(ConfigModel):
    """"telemetry.ctl" sub-block: the deterministic SLO-burn-rate
    autopilot (``monitor/controller.py``). When enabled (and the serving
    front-end runs with ``--adaptive`` / a controller attached), each
    sampler tick folds the burn-rate gauges plus serving pressure
    signals into one observation and may move serving knobs one ladder
    rung (tighten under burn, relax back toward config after sustained
    headroom). Every decision is a typed flight-recorder event — the
    auditable ledger ``replay_decisions`` reproduces exactly. Enabling
    ctl implies the sampler (something must tick the loop); pin a single
    knob static with ``knobs: {"<name>": "off"}``."""
    enabled: bool = False
    # burn rate at/above which a pressure class tightens its knobs
    tighten_threshold: float = 1.0
    # burn rate at/below which a tick counts toward the headroom streak;
    # the (relax_threshold, tighten_threshold) gap is the hysteresis
    # dead band where posture holds
    relax_threshold: float = 0.25
    # minimum ticks between movements of the SAME knob (flap guard)
    cooldown_ticks: int = 5
    # consecutive headroom ticks before knobs start stepping back
    relax_after: int = 10
    # tpot pressure only drops spec k while acceptance sits below this
    spec_accept_floor: float = 0.5
    # KV-block utilization at/above which spill aggressiveness rises
    # (only when the host tier is present and error-free)
    kv_util_high: float = 0.9
    # per-knob overrides: {"prefill_chunk": "off"} pins that knob at its
    # config value — the controller never builds a ladder for it
    knobs: Dict[str, str] = Field(default_factory=dict)


class TelemetryConfig(ConfigModel):
    """"telemetry" section: the cross-layer metrics registry + tracing.

    Accepted as a dict, a bool, or the strings ``"on"``/``"off"`` (see
    :func:`get_telemetry_config`). When enabled, the training engine
    records per-step time/tokens-per-sec/MFU, the inference engine records
    serving stats (TTFT/TPOT, queue depth, KV-block utilization,
    preemptions), and every ``jax.jit`` entry point the engines own runs
    under the compile watchdog. When disabled nothing is instrumented —
    the hot paths gate at one flag check, with no host/device syncs.
    """
    enabled: bool = False
    # append a registry snapshot to this JSONL file every
    # ``steps_per_snapshot`` steps (0 = only on demand / engine exit)
    jsonl_path: Optional[str] = None
    steps_per_snapshot: int = 0
    # also fan snapshots out through the MonitorMaster sinks at the same
    # cadence (TensorBoard / W&B / CSV, "Telemetry/*" series)
    publish_to_monitor: bool = True
    # chrome-trace span export path (written by engine.export_trace())
    chrome_trace_path: Optional[str] = None
    # compile watchdog: warn when one entry point compiles this many times
    # inside its rolling window
    compile_storm_threshold: int = 8
    # hardware peak for the MFU gauge, per chip; 0 = auto (the
    # accelerator's device_kind table; a kind without an entry reads MFU 0)
    peak_tflops_per_chip: float = 0.0
    # health observatory sub-block (sentinels + anomaly detectors +
    # memory gauges + the `dscli health` screen); accepts a dict or a bool
    health: HealthConfig = Field(default_factory=HealthConfig)
    # flight recorder sub-block (event ring + serving trace export);
    # accepts a dict or a bool like `health`
    events: EventsConfig = Field(default_factory=EventsConfig)
    # on-demand jax.profiler capture window
    profile: ProfileConfig = Field(default_factory=ProfileConfig)
    # standalone Prometheus exposition endpoint (monitor/exporter.py):
    # GET /metrics on this port (0 = ephemeral, logged once bound; None =
    # no exporter). `dscli serve` exposes /metrics on its own front-end
    # regardless — this knob is the training-side scrape target.
    metrics_port: Optional[int] = None
    # background snapshot daemon (rotated JSONL + ring); bool shorthand
    sampler: SamplerConfig = Field(default_factory=SamplerConfig)
    # burn-rate SLO engine over the sampler's ring; bool shorthand
    slo: SloConfig = Field(default_factory=SloConfig)
    # adaptive serving controller over the SLO plane; bool shorthand
    ctl: CtlConfig = Field(default_factory=CtlConfig)


def get_telemetry_config(param_dict: dict) -> TelemetryConfig:
    """Parse the ``telemetry`` section: dict, bool/0/1, "on"/"off", or
    null (= defaults). The ``health`` sub-key accepts a bool shorthand,
    and enabling health implies telemetry itself unless the user
    explicitly disabled it (health rides the telemetry substrate)."""
    t = param_dict.get("telemetry", {})
    if t is None:
        t = {}
    elif isinstance(t, str):
        if t not in ("on", "off"):
            raise ValueError(f"telemetry={t!r} (expected 'on', 'off', "
                             "a bool, or a config dict)")
        t = {"enabled": t == "on"}
    elif isinstance(t, (bool, int)):
        t = {"enabled": bool(t)}
    elif not isinstance(t, dict):
        raise ValueError(f"telemetry section must be a dict, bool, or "
                         f"'on'/'off'; got {type(t).__name__}")
    t = dict(t)

    def _sub_shorthand(key):
        """bool / "on"/"off" / null shorthand for a sub-block (shared by
        ``health`` and ``events``)."""
        sub = t.get(key, {})
        if sub is None:
            sub = {}         # null = defaults, like the parent section
        elif isinstance(sub, str):
            if sub not in ("on", "off"):
                raise ValueError(f"telemetry.{key}={sub!r} (expected 'on', "
                                 "'off', a bool, or a config dict)")
            sub = {"enabled": sub == "on"}
        elif isinstance(sub, (bool, int)):
            sub = {"enabled": bool(sub)}
        t[key] = sub
        return sub

    health = _sub_shorthand("health")
    events = _sub_shorthand("events")
    sampler = _sub_shorthand("sampler")
    slo = _sub_shorthand("slo")
    ctl = _sub_shorthand("ctl")
    if t.get("profile") is None and "profile" in t:
        t["profile"] = {}    # null = defaults
    # enabling a sub-block implies the telemetry substrate it rides on,
    # unless the user explicitly disabled telemetry itself
    for sub in (health, events, sampler, slo, ctl):
        if isinstance(sub, dict) and sub.get("enabled") \
                and "enabled" not in t:
            t["enabled"] = True
    # a scrape endpoint with nothing behind it would silently serve an
    # empty registry: asking for /metrics implies telemetry too
    if t.get("metrics_port") is not None and "enabled" not in t:
        t["enabled"] = True
    # SLOs need something ticking the evaluation: enabling slo implies
    # the sampler (ring-only when no path is configured); same for the
    # controller, which ticks on the sampler's cadence
    if isinstance(sampler, dict) and "enabled" not in sampler and (
            (isinstance(slo, dict) and slo.get("enabled"))
            or (isinstance(ctl, dict) and ctl.get("enabled"))):
        sampler["enabled"] = True
    return TelemetryConfig(**t)


class TensorBoardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


class CSVConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class DeepSpeedMonitorConfig(ConfigModel):
    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)
