"""Inference config.

Reference parity: ``deepspeed/inference/config.py`` — ``DeepSpeedInferenceConfig``
(dtype, tensor-parallel degree, MoE, quantization, max_out_tokens,
kernel-injection toggles) plus the quantization sub-configs.

TPU mapping: ``replace_with_kernel_inject`` swaps HF/flax layers for the
fused Pallas inference blocks; ``enable_cuda_graph`` has no TPU analogue —
``jax.jit`` + donated KV-cache buffers already gives a captured graph — so it
is accepted and ignored (warn once).
"""

from __future__ import annotations

from enum import Enum
from typing import Any, Dict, Optional, Union

from pydantic import Field

from deepspeed_tpu.config.config_utils import ConfigModel
from deepspeed_tpu.monitor.config import TelemetryConfig
from deepspeed_tpu.utils.logging import warn_once


class DtypeEnum(str, Enum):
    fp32 = "fp32"
    fp16 = "fp16"
    bf16 = "bf16"
    int8 = "int8"

    @classmethod
    def from_any(cls, value) -> "DtypeEnum":
        if isinstance(value, cls):
            return value
        aliases = {
            "float32": "fp32", "float": "fp32", "fp32": "fp32",
            "float16": "fp16", "half": "fp16", "fp16": "fp16",
            "bfloat16": "bf16", "bf16": "bf16",
            "int8": "int8",
        }
        name = str(value).replace("torch.", "").replace("jnp.", "")
        if name not in aliases:
            raise ValueError(f"Unsupported dtype: {value}")
        return cls(aliases[name])

    @property
    def jnp(self):
        import jax.numpy as jnp
        return {
            DtypeEnum.fp32: jnp.float32,
            DtypeEnum.fp16: jnp.float16,
            DtypeEnum.bf16: jnp.bfloat16,
            DtypeEnum.int8: jnp.int8,
        }[self]


class MoETypeEnum(str, Enum):
    residual = "residual"
    standard = "standard"


class DeepSpeedTPConfig(ConfigModel):
    """Tensor-parallel config ("tensor_parallel" section)."""
    enabled: bool = True
    tp_size: int = 1
    mpu: Optional[Any] = None
    tp_group: Optional[Any] = None


class DeepSpeedMoEConfig(ConfigModel):
    """MoE inference config ("moe" section)."""
    enabled: bool = True
    ep_size: int = 1
    moe_experts: list = Field([1], alias="num_experts")
    type: MoETypeEnum = MoETypeEnum.standard
    ep_mp_group: Optional[Any] = None
    ep_group: Optional[Any] = None


class QuantTypeEnum(str, Enum):
    asym = "asymmetric"
    sym = "symmetric"


class BaseQuantConfig(ConfigModel):
    enabled: bool = True
    num_bits: int = 8
    q_type: QuantTypeEnum = QuantTypeEnum.sym
    q_groups: int = 1


class WeightQuantConfig(BaseQuantConfig):
    enabled: bool = True
    quantized_initialization: Dict = {}
    post_init_quant: Dict = {}


class ActivationQuantConfig(BaseQuantConfig):
    enabled: bool = True


class QKVQuantConfig(ConfigModel):
    enabled: bool = True


class QuantizationConfig(ConfigModel):
    enabled: bool = True
    activation: ActivationQuantConfig = Field(default_factory=ActivationQuantConfig)
    weight: WeightQuantConfig = Field(default_factory=WeightQuantConfig)
    qkv: QKVQuantConfig = Field(default_factory=QKVQuantConfig)


class SpeculativeConfig(ConfigModel):
    """Speculative decoding ("serving.speculative" sub-section).

    ``mode="ngram"`` turns on draft-free self-speculation on the paged
    path: a host-side n-gram proposer (``inference/spec.py``) matches the
    tail of each request's prompt + generated tokens against earlier
    occurrences and proposes up to ``k`` continuation tokens, which one
    fused verify step (``forward_paged_verify``) checks at all ``k + 1``
    positions at once — greedy argmax acceptance keeps speculation
    token-identical to plain greedy decode while emitting (accepted + 1)
    tokens per fused step. Requests with no match fall back to
    single-token decode; sampled generation (``temperature > 0``)
    disables speculation for the call (acceptance is argmax-exact).
    ``mode="auto"`` is RESERVED for a future draft-model speculator and
    resolves to "off" today.
    """
    mode: str = "off"       # off | ngram | auto (auto reserved: off today)
    k: int = 4              # max candidate tokens proposed per request/step
    min_match: int = 2      # shortest tail n-gram the proposer may match
    max_match: int = 4      # longest tail n-gram tried (longest first)


class KvHostConfig(ConfigModel):
    """Tiered KV cache ("serving.kv_host" sub-section).

    ``enabled=True`` attaches a host-memory tier
    (``inference/kv_host_pool.py``) behind the paged block allocator:
    instead of destroying a cold prefix-cache block under allocation
    pressure, the allocator *demotes* it — an async D2H copy of the
    block's ``[L, bs, KV*Hd]`` k/v slices keyed by its blake2b hash
    chain — and a later admission whose prefix walks onto a demoted
    chain re-materializes it H2D into fresh device blocks instead of
    recomputing the prefill. Host RAM is ~10x HBM, so effective cache
    capacity (hence hit rate and TTFT at scale) grows accordingly.
    Requires prefix caching on the paged path; greedy token identity is
    unchanged (a fetched block is a bit-identical copy of what recompute
    would produce).

    ``max_host_blocks`` bounds the tier with its own LRU; 0 = auto (4x
    the device pool's allocatable blocks). ``spill="off"`` keeps the
    tier read-only — existing demoted chains still serve hits, but
    reclaim destroys (no new demotions). Injected D2H/H2D faults
    (``utils/fault_injection``) degrade to destroy-on-reclaim with a
    warning and the ``serving/kv_host_errors`` counter; the serving
    loop never wedges.
    """
    enabled: bool = False
    max_host_blocks: int = 0   # 0 = auto: 4x device pool capacity
    spill: str = "auto"        # auto | off (off = fetch-only, no demotion)


class ReplicaConfig(ConfigModel):
    """Replica scale-out ("serving.replicas" sub-section) — the ``dp``
    serving axis.

    ``dp`` > 1 stands up N engine replicas (one shared weight pytree,
    one shared host KV tier) behind the deterministic
    :class:`~deepspeed_tpu.inference.router.ReplicaRouter`: session-
    affinity hashing pins multi-turn traffic onto the replica holding
    its prefix cache, fresh sessions take a queue-depth/burn-rate-aware
    least-loaded tiebreak, and a replica tripping its crash-loop breaker
    drains in flight to siblings token-identically. ``roles`` tags each
    replica ``any`` | ``prefill`` | ``decode``; any ``prefill`` entry
    enables disaggregated prefill/decode — the prefill replica commits
    prompt blocks and ships them through the content-addressed
    ``KvHostPool`` (the host tier is the KV transport), the decode
    replica re-materializes them H2D instead of re-prefilling.
    ``affinity="off"`` disables session hashing; ``handoff="off"``
    disables the disaggregated path while keeping the role tags for
    routing. Prefer more replicas when throughput-bound with a model
    that fits one slice; prefer larger ``tp`` when the model (or its KV
    working set) does not fit."""
    dp: int = 1                 # serving replicas behind the router
    roles: list = Field(default_factory=list)   # per-replica role tags,
    # padded with "any"; any "prefill" entry enables the handoff path
    affinity: str = "session"   # session | off — session-key hashing
    handoff: str = "auto"       # auto | off — disaggregated prefill path


class ServingFaultConfig(ConfigModel):
    """Serving-plane fault tolerance ("serving.fault" sub-section).

    Governs how the always-on loop (``inference/serve.py``) contains
    engine-step failures — the serving mirror of the training side's
    crash-safe checkpointing:

    - a **per-request** fault (raised before the step's donated pools were
      consumed — e.g. a poison request crashing host-side prep, an injected
      ``fail_step(phase="pre")``) re-queues the faulting action's
      request(s) through the recompute-preemption machinery with
      exponential backoff in LOGICAL scheduler steps
      (``retry_backoff_steps * 2**(retry-1)``); after
      ``max_request_retries`` retries the request **quarantines** — retired
      with ``req.error`` while the loop keeps serving everyone else;
    - an **engine-fatal** fault (anything that died with the donated pools
      already consumed mid-step) triggers a crash-safe engine restart: the
      pool workspace, allocator and fused-step jits are rebuilt and every
      in-flight request is re-admitted from prompt + generated tokens —
      exactly the recovery path recompute-preemption already proves
      correct — at most ``max_engine_restarts`` times (each preceded by
      ``restart_backoff_s * 2**(restart-1)`` of wall backoff); exhausted,
      the **crash-loop breaker** opens: in-flight requests fail, the loop
      parks, ``/healthz`` reads 503, and ``drain()``/``shutdown()`` still
      work;
    - ``shed_queue_depth`` > 0 turns on **load shedding**: whenever the
      waiting queue exceeds the bound the loop sheds the scheduling
      policy's ``select_shed_victim`` picks (lowest priority first, newest
      arrival on ties — deterministic) until it fits, retiring each as
      ``shed`` (HTTP 429).

    Containment is deterministic given a request trace + injection
    schedule; every decision emits flight-recorder events (``serve.fault``
    / ``serve.restart`` / ``req.requeue`` / ``req.timeout`` / ``req.shed``)
    and counts into ``serving/step_faults{kind=}``,
    ``serving/engine_restarts``, ``serving/request_retries``,
    ``serving/timeouts`` and ``serving/shed_requests``.
    """
    max_request_retries: int = 3   # retries before a request quarantines
    retry_backoff_steps: int = 2   # logical-step backoff base (x2 per retry)
    max_engine_restarts: int = 2   # engine rebuilds before the breaker opens
    restart_backoff_s: float = 0.0  # wall backoff base between restarts
    shed_queue_depth: int = 0      # shed waiting requests above this (0=off)


class ServingConfig(ConfigModel):
    """Continuous-batching serving config ("serving" section).

    Governs ``InferenceEngine.generate_batch``: the paged KV cache (block
    pools + per-request block tables) and the iteration-level scheduler.
    ``paged="auto"`` uses the paged path whenever the model supports it
    (zoo causal LMs with a paged forward; weight-streaming and MoE engines
    fall back), ``"on"`` requires it (loud error otherwise), ``"off"``
    serves each request through the static ``generate`` path sequentially.

    ``prefix_caching`` enables vLLM-style automatic prefix caching: full
    KV blocks are content-addressed by a rolling hash chain and shared
    across requests (and across ``generate_batch`` calls) with ref-count
    bumps — a request whose prompt starts with a cached prefix skips that
    prefill compute entirely. ``auto`` = on wherever the paged path is
    active; ``off`` restores the one-owner-per-block behavior.

    ``prefill_chunk_tokens`` > 0 splits prefill into chunks of at most
    that many tokens (compile buckets are 128-aligned, so keep it a
    multiple of 128) and interleaves one chunk with each fused decode
    step — running decodes keep making progress instead of stalling for a
    whole long prompt. 0 = whole-prompt prefill (the default).

    Several serving knobs — the prefill chunk size, speculative ``k``,
    the policy's ``admission_*`` bounds, the shed depth, and host-tier
    spill — double as the adaptive controller's actuation surface
    (``monitor/controller.py``, ``dscli serve --adaptive``): their config
    values are the BASELINE the controller tightens away from under SLO
    burn and steps back to under sustained headroom. Pin one static with
    ``telemetry.ctl.knobs.<name>: off``.

    ``speculative`` configures n-gram self-speculation (verified
    multi-token decode steps) — see :class:`SpeculativeConfig`.

    ``policy`` selects the scheduling policy for the serving loop
    (``inference/policy.py``): ``"fifo"`` (default — the pinned behavior
    every release has had), ``"priority"`` (strict priority classes on
    each request's ``priority``), or ``"sla"`` (TTFT-slack-aware
    admission and preemption). A dict form passes constructor kwargs,
    e.g. ``{"name": "sla", "default_ttft_budget": 64,
    "admission_max_queue": 128, "admission_min_free_blocks": 2}`` — the
    ``admission_*`` knobs are the async front-end's admission control
    (submissions refused under queue/pool pressure instead of queueing
    unboundedly). All policies are deterministic given a request trace.

    ``tp`` > 0 shards the serving engine over a ``tp`` mesh axis (tensor
    parallelism): model params lay out column/row-sharded (the model's
    ``tp_specs`` or the ``auto_tp`` heuristics) and the KV block pools
    split on the KV-head dim, so one model spans ``tp`` chips and pool
    bytes per chip drop to 1/tp. Block tables, the allocator and the
    scheduler stay replicated — per-shard block indices are identical.
    0 (the default) follows ``tensor_parallel.tp_size``; setting both to
    different values is a loud error. KV heads that don't divide ``tp``
    replicate the pools (rate-limited warning, never a crash).
    """
    block_size: int = 128          # tokens per KV block (128 = kernel path;
    # smaller blocks pack tighter but decode through the gather fallback)
    max_num_blocks: int = 0        # pool blocks per layer; 0 = auto-size so
    # max_running requests can reach the model's max_seq (no eviction)
    max_running: int = 8           # fused-decode width / running request cap
    paged: str = "auto"            # auto | on | off
    tp: int = 0                    # serving tensor-parallel degree; 0 =
    # follow tensor_parallel.tp_size
    prefix_caching: str = "auto"   # auto | on | off (auto = on when paged)
    prefill_chunk_tokens: int = 0  # 0 = whole-prompt; else chunk size
    kv_host: KvHostConfig = Field(default_factory=KvHostConfig)
    # tiered KV cache: spill cold prefix-cache blocks to a host-RAM pool
    # (see KvHostConfig)
    replicas: ReplicaConfig = Field(default_factory=ReplicaConfig)
    # dp serving axis: N replicas behind the deterministic affinity
    # router, optional prefill/decode role split (see ReplicaConfig)
    speculative: SpeculativeConfig = Field(
        default_factory=SpeculativeConfig)
    fault: ServingFaultConfig = Field(default_factory=ServingFaultConfig)
    # serving-plane fault tolerance: step-fault containment, crash-safe
    # engine restarts, load shedding (see ServingFaultConfig)
    policy: Union[str, Dict[str, Any]] = "fifo"   # fifo | priority | sla,
    # or {"name": ..., **kwargs} (see inference/policy.py); the serving
    # loop's scheduling policy — generate_batch always runs FIFO


class InferenceCheckpointConfig(ConfigModel):
    checkpoint_dir: Optional[str] = None
    save_mp_checkpoint_path: Optional[str] = None
    base_dir: Optional[str] = None


class DeepSpeedInferenceConfig(ConfigModel):
    """Master inference config (``deepspeed_tpu.init_inference`` kwarg set)."""

    replace_with_kernel_inject: bool = Field(False, alias="kernel_inject")
    dtype: DtypeEnum = DtypeEnum.fp16
    tensor_parallel: DeepSpeedTPConfig = Field(default_factory=DeepSpeedTPConfig, alias="tp")
    enable_cuda_graph: bool = False  # accepted for parity; jit is the TPU analogue
    zero: Dict = {}
    triangular_masking: bool = Field(True, alias="tm")
    moe: Union[bool, DeepSpeedMoEConfig] = Field(default_factory=DeepSpeedMoEConfig)
    quant: QuantizationConfig = Field(default_factory=QuantizationConfig)
    checkpoint: Optional[Union[str, Dict]] = None
    base_dir: str = ""
    set_empty_params: bool = False
    save_mp_checkpoint_path: Optional[str] = None
    checkpoint_config: InferenceCheckpointConfig = Field(default_factory=InferenceCheckpointConfig, alias="ckpt_config")
    serving: ServingConfig = Field(default_factory=ServingConfig)
    # serving telemetry (TTFT/TPOT histograms, queue depth, KV utilization,
    # preemption counters + the compile watchdog); accepts a dict, a bool,
    # or "on"/"off" like the training config's section
    telemetry: TelemetryConfig = Field(default_factory=TelemetryConfig)
    return_tuple: bool = True
    training_mp_size: int = 1
    replace_method: str = Field("auto", json_schema_extra={"deprecated": True})
    injection_policy: Optional[Dict] = Field(None, alias="injection_dict")
    injection_policy_tuple: Optional[tuple] = None
    config: Optional[Dict] = Field(None, alias="args")
    max_out_tokens: int = Field(1024, alias="max_tokens")
    min_out_tokens: int = Field(1, alias="min_tokens")
    transposed_mode: bool = Field(False, alias="transposed_mode")
    mp_size: int = Field(1, json_schema_extra={"deprecated": True, "new_param": "tensor_parallel.tp_size"})
    mpu: Optional[Any] = Field(None, json_schema_extra={"deprecated": True, "new_param": "tensor_parallel.mpu"})
    ep_size: int = Field(1, json_schema_extra={"deprecated": True, "new_param": "moe.ep_size"})
    ep_group: Optional[Any] = Field(None, alias="expert_group",
                                    json_schema_extra={"deprecated": True, "new_param": "moe.ep_group"})
    ep_mp_group: Optional[Any] = Field(None, alias="expert_mp_group",
                                       json_schema_extra={"deprecated": True, "new_param": "moe.ep_mp_group"})
    moe_experts: list = Field([1], json_schema_extra={"deprecated": True, "new_param": "moe.moe_experts"})
    moe_type: MoETypeEnum = Field(MoETypeEnum.standard,
                                  json_schema_extra={"deprecated": True, "new_param": "moe.type"})

    def __init__(self, **data):
        if data.get("enable_cuda_graph"):
            warn_once("enable_cuda_graph has no TPU analogue; jax.jit already captures the graph. Ignoring.")
        if "dtype" in data and data["dtype"] is not None:
            data["dtype"] = DtypeEnum.from_any(data["dtype"])
        if "telemetry" in data and not isinstance(data["telemetry"],
                                                  TelemetryConfig):
            # dicts too: the sub-blocks (health/events) accept bool and
            # "on"/"off" shorthands only get_telemetry_config understands
            from deepspeed_tpu.monitor.config import get_telemetry_config
            data["telemetry"] = get_telemetry_config(
                {"telemetry": data["telemetry"]})
        super().__init__(**data)
