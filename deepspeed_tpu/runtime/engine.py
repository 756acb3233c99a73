"""Core training engine.

Reference parity: ``deepspeed/runtime/engine.py`` — ``DeepSpeedEngine``
wrapping the user model with ``forward``/``backward``/``step``, gradient
accumulation, mixed precision, ZeRO dispatch, LR scheduling, monitoring,
and checkpoint save/load.

TPU-native architecture (not a port):

- The hot path is ONE compiled function per engine:
  ``_train_batch_fn(state, batch, step)`` — a ``lax.scan`` over
  gradient-accumulation micro-steps followed by the optimizer update, all
  under ``jit`` with NamedSharding annotations. The reference's grad-hook /
  bucket / side-stream machinery (stage_1_and_2.py:792-1249, stage3.py
  coordinator) collapses into XLA's SPMD partitioner + latency-hiding
  scheduler: annotating grads/master/opt-state with ZeRO shardings makes XLA
  emit the same reduce-scatter/all-gather overlap those 4k lines implement
  by hand.

- The reference's ``forward()/backward()/step()`` trio
  (engine.py:1652,1794,1990) is kept as a compatibility surface: forward
  caches the micro-batch and returns the loss; backward computes+accumulates
  grads (compiled); step applies the update at the accumulation boundary
  (``is_gradient_accumulation_boundary`` semantics preserved).

- fp16 dynamic loss scaling runs *inside* the compiled step via
  ``lax.cond`` skip-update (SURVEY §7 "hard part": no host round-trip).

Model contract: ``model`` is a loss callable ``loss_fn(params, batch)`` or
``loss_fn(params, batch, rng)`` returning a scalar loss (optionally
``(loss, aux_dict)``), or an object exposing ``.loss`` with that signature
(every class in ``deepspeed_tpu.models`` does). ``model_parameters`` is the
parameter pytree.
"""

from __future__ import annotations

import contextlib
import inspect
import os
import time
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu.config.core import DeepSpeedConfig
from deepspeed_tpu.runtime import lr_schedules
from deepspeed_tpu.runtime.loss_scaler import LossScaleState, has_overflow, make_loss_scale_state
from deepspeed_tpu.runtime.loss_scaler import update as scaler_update
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.runtime.utils import clip_grad_norm_, global_norm
from deepspeed_tpu.runtime.zero.partition import ZeroShardingRules
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import NoopTimer, SynchronizedWallClockTimer, ThroughputTimer


class TrainState(NamedTuple):
    """Everything the compiled step reads/writes. All leaves are jax arrays
    carrying NamedShardings chosen by the ZeRO rules."""
    params: Any          # compute-dtype params (bf16/fp16/fp32)
    master: Any          # fp32 master params (None when compute is fp32)
    opt_state: Any       # optax state, sharded like master
    acc_grads: Any       # fp32 (or configured dtype) accumulation buffers
    scaler: LossScaleState
    micro_steps: jnp.ndarray   # i32
    global_steps: jnp.ndarray  # i32
    skipped_steps: jnp.ndarray # i32 (fp16 overflow skips)


def _loss_fn_of(model) -> Callable:
    if callable(model) and not hasattr(model, "loss"):
        fn = model
    elif hasattr(model, "loss"):
        fn = model.loss
    else:
        raise TypeError("model must be a loss callable loss_fn(params, batch[, rng]) or expose .loss")
    try:
        n_args = len(inspect.signature(fn).parameters)
    except (TypeError, ValueError):
        n_args = 2
    if n_args >= 3:
        return fn
    return lambda params, batch, rng: fn(params, batch)


class DeepSpeedEngine:

    def __init__(self,
                 model,
                 config: Optional[Any] = None,
                 model_parameters=None,
                 optimizer=None,
                 lr_scheduler=None,
                 mesh=None,
                 mpu=None,
                 training_data=None,
                 collate_fn=None,
                 config_class: Optional[DeepSpeedConfig] = None,
                 dont_change_device: bool = False):
        self.client_model = model
        self.loss_fn = _loss_fn_of(model)
        self.mpu = mpu

        dist.init_distributed(verbose=False)

        # ---- mesh ----
        if mesh is None:
            if config_class is None:
                tmp_axes = (config or {}).get("mesh", None) if isinstance(config, dict) else None
                mesh = dist.init_mesh(tmp_axes) if not dist.has_mesh() else dist.get_mesh()
            else:
                mesh = dist.init_mesh(config_class.mesh_axes)
        else:
            dist.set_mesh(mesh)
        self.mesh = mesh

        # ---- config ----
        self._config = config_class or DeepSpeedConfig(config, mpu=mpu, mesh=mesh)
        dist.configure(self._config)
        # vocab-head kernel override: a JSON-level "fused_cross_entropy"
        # knob beats the model config's default (the same engine-pushes-into-
        # model pattern the autotuner's model_overrides use), so bench/serve
        # configs can flip the CE path without rebuilding the model
        fce = self._config.fused_cross_entropy
        mcfg = getattr(model, "config", None)
        if fce is not None and mcfg is not None \
                and hasattr(mcfg, "fused_cross_entropy"):
            import dataclasses
            model.config = dataclasses.replace(mcfg, fused_cross_entropy=fce)
            if hasattr(model, "zoo_cfg"):
                # BertModel caches a derived zoo config; keep it coherent
                model.zoo_cfg = model.config.zoo()
        self.zero_rules = ZeroShardingRules(mesh, self._config.zero_config)
        log_dist(self.zero_rules.describe(), ranks=[0])

        # ---- precision ----
        if self.bfloat16_enabled():
            self.compute_dtype = jnp.bfloat16
        elif self.fp16_enabled():
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32
        self.mixed_precision = self.compute_dtype != jnp.float32
        acc_dtype_name = self._config.gradient_accumulation_dtype
        # default: with no accumulation (gas=1) grads pass straight through to
        # the update, so keep them in compute dtype — the persistent fp32
        # accumulator would cost 4 bytes/param for nothing; with gas>1 the
        # reference accumulates in fp32 (bf16_optimizer.py) and so do we
        self._acc_dtype_name = acc_dtype_name
        if acc_dtype_name is None and self.gradient_accumulation_steps() == 1:
            self.grad_acc_dtype = self.compute_dtype
        else:
            self.grad_acc_dtype = {None: jnp.float32, "fp32": jnp.float32, "fp16": jnp.float16,
                                   "bf16": jnp.bfloat16}[acc_dtype_name]

        # ---- optimizer ----
        # 1-bit family: functional optimizers whose COMPRESSED collectives
        # run inside the compiled step (reference fp16/onebit/adam.py:11 —
        # the optimizer owns gradient communication after freeze_step)
        self._onebit = None
        ob_name = (self._config.optimizer_name or "").lower() if optimizer is None else ""
        if ob_name in ("onebitadam", "zerooneadam", "onebitlamb"):
            self._onebit = self._build_onebit_optimizer(ob_name)

        self.client_optimizer = optimizer
        if self._onebit is not None:
            self.tx = None
            self._client_tx_full = False
            self._optimizer_name = ob_name
            if float(self._config.gradient_clipping or 0.0) > 0.0:
                raise NotImplementedError(
                    f"{ob_name}: gradient_clipping does not compose with the compressed "
                    "momentum exchange (the optimizer owns communication); disable it")
        elif optimizer is not None:
            # A user-supplied optax transformation follows standard optax
            # conventions: updates are final (lr and sign already applied),
            # consumed as params + updates. The engine's LR schedule then
            # does NOT rescale them — the client optimizer owns its LR.
            self.tx = optimizer
            self._client_tx_full = True
            self._optimizer_name = "client"
            if self._config.scheduler_name is not None:
                logger.warning("A client optax optimizer was passed together with a scheduler config; "
                               "the engine cannot inject the schedule into a finalized optax chain. "
                               "Use optimizer config {'type': ...} or bake the schedule into the client chain.")
        else:
            self.tx = build_optimizer(self._config.optimizer_name, self._config.optimizer_params)
            self._client_tx_full = False
            self._optimizer_name = self._config.optimizer_name or "adamw"

        # ---- lr schedule ----
        self.client_lr_scheduler = lr_scheduler
        self.lr_scheduler = None
        base_lr = (self._config.optimizer_params or {}).get("lr", 1e-3)
        if lr_scheduler is not None and hasattr(lr_scheduler, "schedule_fn"):
            self._lr_fn = lr_scheduler.schedule_fn
            self.lr_scheduler = lr_scheduler
        elif callable(lr_scheduler):
            self._lr_fn = lr_scheduler
        elif self._config.scheduler_name is not None:
            self._lr_fn = lr_schedules.get_lr_schedule_fn(self._config.scheduler_name,
                                                          self._config.scheduler_params or {})
            sched_cls = getattr(lr_schedules, self._config.scheduler_name)
            self.lr_scheduler = sched_cls(**(self._config.scheduler_params or {}))
        else:
            self._lr_fn = lambda step: jnp.asarray(base_lr, jnp.float32)

        # ---- timers / monitor ----
        self.wall_clock_breakdown_enabled = self._config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown_enabled else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=self.train_batch_size(),
                                          steps_per_output=self._config.steps_per_print)
        from deepspeed_tpu.monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(self._config.monitor_config)

        # ---- telemetry (metrics registry + compile watchdog) ----
        # when off, _telemetry is None and every hot-path hook is a single
        # attribute check — no timers, no syncs, no registry traffic
        tcfg = self._config.telemetry_config
        self._telemetry = tcfg if tcfg.enabled else None
        self._tel_flops_per_token_v = None
        # health observatory: None when off, so every hot-path hook gates
        # at one attribute check (sentinel collection in the compiled step
        # is likewise a trace-time constant — no runtime branch at all)
        self._health = None
        self._sentinels_on = False
        self._sentinel_layout = None     # (leaf->bucket assignment, names)
        self._t_prev_step_end = None     # data-stall wait-time base
        self._trio_busy_s = 0.0          # per-cycle fwd+bwd+step phase time
        self._tel_wait_total = 0.0
        self._tel_busy_total = 0.0
        self._tel_skip_consec = 0        # health-off sustained-skip warning
        self._tel_skip_seen = 0
        self._tel_skipped_prev = None    # health skip detection base
        self._tel_skipped_cached = None  # per-step skipped_steps fetch
        # flight recorder: None when off — every hot-path emit site is one
        # None check and allocates nothing
        self._tel_events = None
        self._ev_skip_prev = None        # fp16-skip event detection base
        # on-demand jax.profiler capture window; armed by config
        # (telemetry.profile) or engine.profile(steps=N). One None check
        # per train_batch when absent.
        self._profiler = None
        # metrics exposition plane (monitor/exporter.py, monitor/
        # sampler.py): a standalone /metrics endpoint + the background
        # snapshot/SLO sampler — both config-driven, both host-only
        # daemon threads, stopped in destroy()
        self._tel_exporter = None
        self._tel_sampler = None
        pcfg = tcfg.profile
        if pcfg.num_steps > 0:
            from deepspeed_tpu.monitor.trace import ProfileWindow
            self._profiler = ProfileWindow(pcfg.dir, pcfg.start_step,
                                           pcfg.num_steps)
        if self._telemetry is not None:
            from deepspeed_tpu.monitor.health import sample_memory_gauges
            from deepspeed_tpu.monitor.metrics import get_registry
            from deepspeed_tpu.monitor.trace import (get_compile_watchdog,
                                                     get_tracer)
            reg = get_registry()
            reg.set_enabled(True)
            self._tel_reg = reg
            self._tel_watchdog = get_compile_watchdog()
            self._tel_watchdog.storm_threshold = tcfg.compile_storm_threshold
            self._tel_tracer = get_tracer()
            self._tel_sample_memory = sample_memory_gauges
            self._tel_step_hist = reg.histogram(
                "train/step_time_ms", "whole train_batch wall time")
            self._tel_phase_hist = reg.histogram(
                "train/phase_time_ms",
                "fwd/bwd/step breakdown (forward()/backward()/step() trio; "
                "fwd = value_and_grad, bwd = accumulate)",
                labelnames=("phase",))
            self._tel_tokens_gauge = reg.gauge(
                "train/tokens_per_sec", "tokens through the last step")
            self._tel_tflops_gauge = reg.gauge(
                "train/achieved_tflops_per_chip",
                "model flops per token x token rate / chips")
            self._tel_mfu_gauge = reg.gauge(
                "train/mfu", "achieved / peak flops per chip (PaLM-style)")
            self._tel_steps_counter = reg.counter("train/steps")
            self._tel_tokens_counter = reg.counter("train/tokens")
            self._tel_loss_gauge = reg.gauge(
                "train/loss", "last recorded training loss")
            self._tel_grad_norm_hist = reg.histogram(
                "train/grad_norm",
                "pre-clip global gradient norm (reused from the norm "
                "clip_grad_norm_ computes; recorded even with clipping off)")
            self._tel_wait_hist = reg.histogram(
                "train/data_wait_ms",
                "host time between compiled steps (data loading + host prep)")
            self._tel_stall_gauge = reg.gauge(
                "train/data_stall_fraction",
                "cumulative wait / (wait + device step) time")
            if self.fp16_enabled():
                self._tel_skipped_gauge = reg.gauge(
                    "train/skipped_steps",
                    "fp16 overflow skip-update steps so far")
                self._tel_scale_gauge = reg.gauge(
                    "train/loss_scale", "current dynamic loss scale")
            if tcfg.events.enabled:
                from deepspeed_tpu.monitor.events import get_flight_recorder
                self._tel_events = get_flight_recorder().enable(
                    capacity=tcfg.events.capacity)
            hcfg = tcfg.health
            if hcfg.enabled:
                from deepspeed_tpu.monitor.health import HealthMonitor
                self._health = HealthMonitor(
                    hcfg, registry=reg,
                    snapshot_fn=self.telemetry_snapshot,
                    trace_export_fn=self._tel_tracer.export_chrome_trace)
                self._sentinels_on = bool(hcfg.sentinels)
            if tcfg.metrics_port is not None:
                from deepspeed_tpu.monitor.exporter import MetricsExporter
                self._tel_exporter = MetricsExporter(
                    reg, port=tcfg.metrics_port)
                ehost, eport = self._tel_exporter.start()
                logger.info(f"telemetry: /metrics exposition on "
                            f"http://{ehost}:{eport}/metrics")
            from deepspeed_tpu.monitor.sampler import sampler_from_config
            sampler = sampler_from_config(tcfg, reg, self._tel_events)
            if sampler is not None:
                self._tel_sampler = sampler.start()

        # ---- curriculum learning (reference engine.py:1691 legacy path +
        # data_efficiency data_sampling.curriculum_learning) ----
        self.curriculum_scheduler = None
        self._curriculum_metric = None
        raw = self._config._param_dict
        legacy = raw.get("curriculum_learning", {})
        from deepspeed_tpu.runtime.data_pipeline.config import get_data_efficiency_config
        de = get_data_efficiency_config(raw)
        sampling = de["data_sampling"]
        de_curr = sampling["curriculum_learning"]
        curr_cfg = None
        if isinstance(legacy, dict) and legacy.get("enabled", False):
            curr_cfg = legacy
        elif de["enabled"] and sampling["enabled"] and de_curr.get("enabled", False):
            # the parent data_efficiency/data_sampling switches gate the
            # feature (reference runtime/data_pipeline/config.py semantics)
            curr_cfg = de_curr
        if curr_cfg is not None:
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import (
                CurriculumScheduler)
            self.curriculum_scheduler = CurriculumScheduler(dict(curr_cfg))
            self._curriculum_metric = curr_cfg.get("curriculum_type", "seqlen")
        # host-side step counter for curriculum (avoids a device sync per
        # train_batch just to read state.global_steps)
        self._host_global_steps = 0

        # ---- fault tolerance: data-pipeline progress + async checkpoint
        # writer + preemption grace handler (runtime/checkpoint_engine) ----
        # consumed_samples/iterations are recorded in every checkpoint's
        # meta.json so auto_resume can fast-forward the data pipeline
        self._data_progress = {"consumed_samples": 0, "iterations": 0}
        # True only for a user-provided set_dataiterator stream: resume
        # fast-forwards it in place; loader-derived iterators are instead
        # re-created by the epoch-aware resume_loader_iterator path
        self._data_iter_external = False
        self._ckpt_writer = None
        self._preemption = None

        # ---- dataloader ----
        self.training_dataloader = None
        if training_data is not None:
            from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
            # single-controller: this process feeds every dp shard it owns
            dp = dist.get_world_size(dist.data_parallel_axes(self.mesh))
            self.training_dataloader = DeepSpeedDataLoader(
                training_data, batch_size=self.train_micro_batch_size_per_gpu() * dp, collate_fn=collate_fn,
                drop_last=self._config.dataloader_drop_last)

        # ---- state ----
        if model_parameters is None and hasattr(model, "init_params"):
            # key(0): decorrelated from the training rng stream (key(DS_SEED))
            # and unchanged vs earlier releases
            seed_key = jax.random.key(0)
            if self.zero_optimization_stage() >= 3:
                # zero.Init-equivalent abstract construction (reference
                # partition_parameters.py:516): params materialise directly
                # into their ZeRO-3 shards — the full tree never exists in
                # one memory, so > single-device-memory models construct
                from deepspeed_tpu.runtime.zero import Init
                with Init(mesh=self.mesh, config=self._config.zero_config):
                    model_parameters = model.init_params(seed_key)
            else:
                model_parameters = model.init_params(seed_key)
        if model_parameters is None:
            raise ValueError("model_parameters is required (or model must expose init_params(rng))")
        self.state = self._init_state(model_parameters)
        self._rng = jax.random.key(int(os.environ.get("DS_SEED", 42)))

        # compiled functions, built lazily on first use
        self._train_batch_jit: Dict[Tuple, Callable] = {}
        self._accum_batch_jit: Dict[Tuple, Callable] = {}
        self._grad_jit = None
        self._acc_jit = None
        self._apply_jit = None
        self._reset_acc_jit = None
        self._eval_jit = None
        self._cached_grads = None
        self._losses = 0.0

        self.progressive_layer_drop = None
        if self._config.pld_enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
            theta = self._config.pld_params.get("theta", 1.0)
            gamma = self._config.pld_params.get("gamma", 0.001)
            self.progressive_layer_drop = ProgressiveLayerDrop(theta=theta, gamma=gamma)

        # MoQ progressive quantization (reference runtime/quantize.py wired
        # via the "quantize_training" config section; eigenvalue-guided
        # schedule per runtime/eigenvalue.py)
        self.quantizer = None
        self.eigenvalue = None
        qt = getattr(self._config, "quantize_training", {})
        if getattr(self._config, "quantize_training_enabled", False):
            from deepspeed_tpu.runtime.quantize import Quantizer
            bits = qt.get("quantize_bits", {})
            sched = qt.get("quantize_schedule", {})
            algo = qt.get("quantize_algo", {})
            mixed = qt.get("fp16_mixed_quantize", {})
            # only config-present keys: Quantizer's own defaults govern
            kw = {k: v for k, v in dict(
                q_groups=qt.get("quantize_groups"),
                q_mixed_fp16=mixed.get("enabled"),
                q_change_ratio=mixed.get("quantize_change_ratio"),
                q_type=algo.get("q_type"),
                q_rounding=algo.get("q_rounding"),
                q_verbose=qt.get("quantize_verbose"),
                q_eigenvalue=qt.get("eigenvalue", {}).get("enabled"),
                start_bits=bits.get("start_bits"),
                target_bits=bits.get("target_bits"),
                q_period=sched.get("quantize_period"),
            ).items() if v is not None}
            self.quantizer = Quantizer(**kw)
            self._moq_seen_skipped = 0
            ev = qt.get("eigenvalue", {})
            if ev.get("enabled", False):
                from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
                self.eigenvalue = Eigenvalue(
                    verbose=ev.get("verbose", False),
                    max_iter=ev.get("max_iter", 100),
                    tol=ev.get("tol", 1e-2),
                    stability=ev.get("stability", 1e-6),
                    gas_boundary_resolution=ev.get("gas_boundary_resolution", 1))
                self._ev_layer_name = ev.get("layer_name", "layers")
                self._ev_layer_num = ev.get("layer_num", 0)

        ccfg = getattr(self._config, "checkpoint_config", None)
        if ccfg is not None and ccfg.preemption_save and ccfg.save_dir:
            self.enable_preemption_handler(ccfg.save_dir)

        log_dist(f"DeepSpeedEngine ready: optimizer={self._optimizer_name}, "
                 f"dtype={self.compute_dtype.__name__}, mesh={dict(mesh.shape)}, "
                 f"micro_bs={self.train_micro_batch_size_per_gpu()} x gas={self.gradient_accumulation_steps()}",
                 ranks=[0])

    # ------------------------------------------------------------------ #
    # state initialization

    def _init_state(self, model_parameters) -> TrainState:
        rules = self.zero_rules
        tp_specs = getattr(self.client_model, "tp_specs", None)
        if callable(tp_specs):
            tp_specs = tp_specs()

        param_sh = rules.param_shardings(model_parameters, tp_specs)
        master_sh = rules.master_shardings(model_parameters, tp_specs)
        grad_sh = rules.grad_shardings(model_parameters, tp_specs)
        self._param_shardings = param_sh
        self._grad_shardings = grad_sh
        self._master_shardings = master_sh
        self._params_treedef = jax.tree.structure(model_parameters)

        # ---- ZeRO-Offload: fp32 master + optimizer state live on host
        # (or NVMe), stepped by the native cpu_adam; the device program only
        # accumulates grads (reference stage_1_and_2.py:1030-1155, stage3
        # PartitionedOptimizerSwapper) ----
        self._offload = None
        ocfg = self._config.zero_config.offload_optimizer
        if ocfg is not None and ocfg.device != "none":
            if self.client_optimizer is not None:
                raise ValueError("offload_optimizer is incompatible with a client optax optimizer; "
                                 "configure the optimizer via the config instead")
            from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
            self._offload = HostOffloadOptimizer(
                model_parameters,
                optimizer_name=self._optimizer_name,
                optimizer_params=self._config.optimizer_params,
                device=str(ocfg.device.value if hasattr(ocfg.device, "value") else ocfg.device),
                nvme_path=ocfg.nvme_path,
                grad_clip=float(self.gradient_clipping() or 0.0))
            log_dist(f"ZeRO-Offload: optimizer on {self._offload.device} "
                     f"({len(self._offload.order)} tensors, native cpu_{self._optimizer_name})", ranks=[0])

        params = jax.tree.map(
            lambda a, s: jax.device_put(jnp.asarray(a, self.compute_dtype), s), model_parameters, param_sh)
        if self.mixed_precision and self._offload is None:
            master = jax.tree.map(
                lambda a, s: jax.device_put(jnp.asarray(a, jnp.float32), s), model_parameters, master_sh)
        else:
            master = None
        if self._onebit is not None:
            opt_target = master if master is not None else params
            opt_state = self._onebit_init_state(opt_target)
        elif self._offload is None:
            opt_target = master if master is not None else params
            opt_state = self.tx.init(opt_target)
            opt_sh = rules.opt_state_shardings(opt_state, model_parameters, tp_specs)
            opt_state = jax.tree.map(lambda a, s: jax.device_put(a, s) if hasattr(a, "shape") else a,
                                     opt_state, opt_sh)
        else:
            opt_state = ()
        if not self._uses_acc_grad_buffers():
            # the fused step feeds grads straight into the update — no
            # accumulation buffers; the forward/backward/step trio lazily
            # allocates them on first use (_ensure_acc_grads)
            acc_grads = ()
        else:
            acc_grads = jax.tree.map(
                lambda a, s: jax.device_put(jnp.zeros(a.shape, self.grad_acc_dtype), s),
                model_parameters, grad_sh)

        if self.fp16_enabled() and self._config.fp16_config.dynamic_loss_scale:
            args = self._config.dynamic_loss_scale_args
            scaler = make_loss_scale_state(init_scale=args["init_scale"], scale_window=args["scale_window"],
                                           min_scale=args["min_scale"], delayed_shift=args["delayed_shift"])
        elif self.fp16_enabled():
            scaler = make_loss_scale_state(init_scale=self._config.loss_scale or 1.0, dynamic=False)
        else:
            scaler = make_loss_scale_state(init_scale=1.0, dynamic=False)

        # scalars live replicated on the mesh so they compose with sharded
        # leaves in one program; counters must be distinct buffers (the state
        # is donated, and XLA rejects donating one buffer twice)
        rep = NamedSharding(self.mesh, P())
        scaler = jax.tree.map(lambda x: jax.device_put(x, rep), scaler)
        return TrainState(params=params, master=master, opt_state=opt_state, acc_grads=acc_grads,
                          scaler=scaler,
                          micro_steps=jax.device_put(jnp.zeros((), jnp.int32), rep),
                          global_steps=jax.device_put(jnp.zeros((), jnp.int32), rep),
                          skipped_steps=jax.device_put(jnp.zeros((), jnp.int32), rep))

    # ------------------------------------------------------------------ #
    # compiled step builders

    def _micro_grads(self, params, batch, rng, scale):
        """Loss + scaled grads for one micro-batch (compute dtype)."""

        def scaled_loss(p):
            out = self.loss_fn(p, batch, rng)
            loss = out[0] if isinstance(out, tuple) else out
            return loss.astype(jnp.float32) * scale, loss

        grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
        return loss, grads

    def _accumulate(self, acc, grads):
        acc = jax.tree.map(lambda a, g: (a + g.astype(self.grad_acc_dtype)), acc, grads)
        # constrain to ZeRO grad shardings: stage>=2 => XLA reduce-scatters
        return jax.lax.with_sharding_constraint(acc, self._grad_shardings)

    def _apply_update(self, state: TrainState, gas: int, acc=None):
        """Unscale, clip, (maybe skip on overflow), optimizer update.
        Returns ``(new_state, aux)`` where ``aux`` is a (possibly empty)
        dict of health/telemetry scalars computed inside this same
        program: ``grad_norm`` (pre-clip, telemetry on) and ``sentinels``
        (the numerics summary vector, health sentinels on). The gating is
        a trace-time constant — telemetry off compiles the exact same
        program as before.

        ``acc``: gradient tree to consume; defaults to ``state.acc_grads``
        (the GAS-scan buffers). The gas==1 fast path passes the micro-step
        grads directly so no accumulation buffers are read, written, or
        re-zeroed — and with no scan barrier XLA's scheduler is free to
        overlap per-param optimizer updates with the rest of the backward."""
        if self._onebit is not None:
            raise NotImplementedError(
                "1-bit optimizers run their compressed update inside train_batch(); "
                "the forward()/backward()/step() trio is not supported with them")
        from_buffers = acc is None
        if from_buffers:
            acc = state.acc_grads
        scale = state.scaler.loss_scale
        denom = scale * gas
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / denom, acc)

        overflow = has_overflow(grads) if self.fp16_enabled() else jnp.asarray(False)

        aux: Dict[str, Any] = {}
        raw_grads = grads
        clip = float(self.gradient_clipping() or 0.0)
        # pre-clip global norm computed ONCE and shared: the clip consumes
        # it via its norm= parameter and telemetry records it (even with
        # clipping disabled, the satellite contract)
        norm = None
        if clip > 0.0 or self._telemetry is not None:
            norm = global_norm(grads)
        if clip > 0.0:
            grads, _ = clip_grad_norm_(grads, clip, norm=norm)
        if self._telemetry is not None:
            aux["grad_norm"] = norm

        lr = self._lr_fn(state.global_steps)
        opt_target = state.master if state.master is not None else state.params

        def do_update(_):
            updates, new_opt = self.tx.update(grads, state.opt_state, opt_target)
            if self._client_tx_full:
                # standard optax semantics: updates are final (incl. -lr)
                new_target = jax.tree.map(lambda p, u: p + u.astype(p.dtype), opt_target, updates)
            else:
                # engine-built chains end before lr scaling so the schedule
                # stays inside jit: direction u is descent, applied as p - lr*u
                new_target = jax.tree.map(lambda p, u: p - lr * u.astype(p.dtype), opt_target, updates)
            # sentinel update norm from the update VECTOR, not new - old:
            # a (new - old) subtraction would keep the whole pre-update
            # tree live past the update and defeat donation aliasing (one
            # extra fp32 master copy of peak HBM). ||delta|| = ||u|| for a
            # client chain, lr*||u|| for engine-built chains.
            if self._sentinels_on:
                up_norm = global_norm(updates)
                if not self._client_tx_full:
                    up_norm = lr * up_norm
            else:
                up_norm = jnp.float32(0.0)
            return new_target, new_opt, up_norm

        def skip_update(_):
            return opt_target, state.opt_state, jnp.float32(0.0)

        if self.fp16_enabled():
            new_target, new_opt, up_norm = jax.lax.cond(
                overflow, skip_update, do_update, operand=None)
        else:
            new_target, new_opt, up_norm = do_update(None)

        if state.master is not None:
            new_master = new_target
            new_params = jax.lax.with_sharding_constraint(
                jax.tree.map(lambda m: m.astype(self.compute_dtype), new_master), self._param_shardings)
        else:
            new_master = None
            new_params = jax.lax.with_sharding_constraint(new_target, self._param_shardings)

        if self._sentinels_on:
            # numerics sentinels ride THIS program (no extra compiles or
            # host round-trips): non-finite counts over the raw unscaled
            # grads + post-update params, param/update norms, per-group
            # norm buckets — all cheap reductions XLA fuses into the step
            from deepspeed_tpu.monitor.health import compute_sentinels
            assignment, names = self._sentinel_buckets(raw_grads)
            aux["sentinels"] = compute_sentinels(
                raw_grads, new_target, up_norm, norm, assignment, names)

        new_scaler = scaler_update(state.scaler, overflow)
        # donation aliases the untouched buffers through at zero cost
        zero_acc = (jax.tree.map(jnp.zeros_like, state.acc_grads) if from_buffers
                    else state.acc_grads)
        return state._replace(
            params=new_params, master=new_master, opt_state=new_opt, acc_grads=zero_acc, scaler=new_scaler,
            global_steps=state.global_steps + 1,
            skipped_steps=state.skipped_steps + overflow.astype(jnp.int32)), aux

    def _sentinel_buckets(self, grads_tree):
        """Leaf→layer-group bucket layout for the sentinel vector,
        computed once (at trace time of the first compiled step) and
        cached — the structure is fixed for the engine's lifetime."""
        if self._sentinel_layout is None:
            from deepspeed_tpu.monitor.health import make_bucket_assignment
            assignment, names = make_bucket_assignment(
                grads_tree, self._health.cfg.max_norm_buckets)
            self._sentinel_layout = (assignment, names)
            self._health.set_bucket_names(names)
        return self._sentinel_layout

    def _build_accum_batch_fn(self, gas: int) -> Callable:
        """GAS-scan only (offload path): grads accumulate on device, the
        optimizer update happens on host in :meth:`_host_step`."""

        def accum_batch_fn(state: TrainState, batch, rng):
            scale = state.scaler.loss_scale

            def micro(carry, mb):
                acc, i = carry
                mb_rng = jax.random.fold_in(rng, i)
                loss, grads = self._micro_grads(state.params, mb, mb_rng, scale)
                acc = self._accumulate(acc, grads)
                return (acc, i + 1), loss

            (acc, _), losses = jax.lax.scan(micro, (state.acc_grads, jnp.asarray(0, jnp.int32)), batch, length=gas)
            state = state._replace(acc_grads=acc, micro_steps=state.micro_steps + gas)
            return state, jnp.mean(losses)

        return jax.jit(accum_batch_fn, donate_argnums=(0,))

    def _host_step(self):
        """Offload optimizer boundary: grads → host, native cpu_adam step,
        updated bf16 params → device. Returns metrics."""
        import ml_dtypes

        gas = self.gradient_accumulation_steps()
        scale = float(self.state.scaler.loss_scale) if self.fp16_enabled() else 1.0
        denom = scale * gas
        lr = float(self._lr_fn(self.state.global_steps))

        from deepspeed_tpu.runtime.zero.offload import _leaf_key

        # one tree-level D2H transfer (JAX batches/overlaps the copies)
        host_grads_tree = jax.device_get(self.state.acc_grads)
        grads_host: Dict[str, np.ndarray] = {}
        # offload-path health/telemetry ride the SAME host pass the grads
        # already make (one extra reduction per leaf, no device work)
        grad_sq = 0.0
        nonfinite = 0.0
        for path, leaf in jax.tree_util.tree_flatten_with_path(host_grads_tree)[0]:
            arr = np.asarray(leaf).ravel()
            # one conversion, one divide: .astype copies, then /= is in-place
            arr = arr.astype(np.float32)
            arr /= denom
            if self._telemetry is not None:
                grad_sq += float(np.dot(arr, arr))
            if self._health is not None:
                nonfinite += float(arr.size - np.isfinite(arr).sum())
            grads_host[_leaf_key(path)] = np.ascontiguousarray(arr)

        out_dtype = ml_dtypes.bfloat16 if self.compute_dtype == jnp.bfloat16 else np.float32
        staged, overflow = self._offload.step(grads_host, lr, out_dtype=out_dtype)

        if not overflow:
            np_dtype = {jnp.bfloat16: ml_dtypes.bfloat16, jnp.float16: np.float16,
                        jnp.float32: np.float32}[self.compute_dtype]
            leaves = []
            for key in self._offload.order:
                flat = staged[key]
                if flat.dtype == np.uint16:
                    flat = flat.view(ml_dtypes.bfloat16)
                leaves.append(flat.reshape(self._offload.shape(key)).astype(np_dtype, copy=False))
            host_params = jax.tree.unflatten(self._params_treedef, leaves)
            # one tree-level H2D transfer against the sharding tree
            new_params = jax.device_put(host_params, self._param_shardings)
        else:
            new_params = self.state.params

        zero_acc = self._zeroed_acc(self.state.acc_grads)
        overflow_arr = jnp.asarray(overflow)
        new_scaler = scaler_update(self.state.scaler, overflow_arr) if self.fp16_enabled() else self.state.scaler
        self.state = self.state._replace(
            params=new_params, acc_grads=zero_acc, scaler=new_scaler,
            global_steps=self.state.global_steps + 1,
            skipped_steps=self.state.skipped_steps + int(overflow))
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        metrics = {"loss": self._losses, "lr": lr, "loss_scale": float(new_scaler.loss_scale)}
        if self._telemetry is not None:
            metrics["grad_norm"] = float(np.sqrt(grad_sq))
        if self._health is not None:
            metrics["nonfinite_grads"] = nonfinite
        return metrics

    # ------------------------------------------------------------------ #
    # 1-bit optimizer path (reference runtime/fp16/onebit/*: the optimizer
    # owns gradient communication — full-precision psum during warmup,
    # error-compensated 1-bit compressed allreduce after freeze_step)

    def _build_onebit_optimizer(self, name: str):
        p = dict(self._config.optimizer_params or {})
        mesh = self.mesh
        dp_axes = [ax for ax in ("dp", "fsdp") if mesh.shape.get(ax, 1) > 1]
        other = [ax for ax, sz in mesh.shape.items()
                 if sz > 1 and ax not in ("dp", "fsdp")]
        if other or len(dp_axes) > 1:
            raise NotImplementedError(
                f"{name} supports a single data-parallel mesh axis (got {dict(mesh.shape)}); "
                "the compressed allreduce composes with dp only (reference parity: "
                "1-bit optimizers are incompatible with model parallelism + ZeRO>=2)")
        if self._config.zero_config.stage >= 2:
            raise NotImplementedError(f"{name} is incompatible with ZeRO stage >= 2 "
                                      "(gradients must stay whole for the compressed allreduce)")
        if self.fp16_enabled():
            raise NotImplementedError(f"{name}: use bf16/fp32 (dynamic loss scaling does not "
                                      "compose with the compressed momentum exchange)")
        self._onebit_axis = dp_axes[0] if dp_axes else "dp"
        n = mesh.shape.get(self._onebit_axis, 1)
        common = dict(lr=p.get("lr", 1e-3), betas=tuple(p.get("betas", (0.9, 0.999))),
                      eps=p.get("eps", 1e-8), weight_decay=p.get("weight_decay", 0.0),
                      axis=self._onebit_axis, comm_group_size=n)
        if name == "onebitadam":
            from deepspeed_tpu.runtime.fp16.onebit import OnebitAdam
            return OnebitAdam(freeze_step=p.get("freeze_step", 100), **common)
        if name == "onebitlamb":
            from deepspeed_tpu.runtime.fp16.onebit import OnebitLamb
            return OnebitLamb(freeze_step=p.get("freeze_step", 100), **common)
        from deepspeed_tpu.runtime.fp16.onebit import ZeroOneAdam
        return ZeroOneAdam(var_freeze_step=p.get("var_freeze_step", 100),
                           local_step_clipper=p.get("local_step_clipper", 16), **common)

    _ONEBIT_ERR_FIELDS = ("worker_error", "server_error")

    def _ob_map_errors(self, st, fn):
        """Apply ``fn`` leaf-wise to the worker/server error subtrees,
        wherever they live (OnebitLambState nests an adam state)."""
        if hasattr(st, "adam"):
            return st._replace(adam=self._ob_map_errors(st.adam, fn))
        return st._replace(worker_error=jax.tree.map(fn, st.worker_error),
                           server_error=jax.tree.map(fn, st.server_error))

    def _ob_is_error_path(self, path) -> bool:
        return any(getattr(k, "name", None) in self._ONEBIT_ERR_FIELDS for k in path)

    def _onebit_init_state(self, target):
        """Global optimizer state: per-rank error feedback gets a leading dp
        dim sharded over the dp axis; everything else replicates."""
        n = self.mesh.shape.get(self._onebit_axis, 1)
        st = self._onebit.init(target)
        st = self._ob_map_errors(st, lambda e: jnp.zeros((n,) + e.shape, e.dtype))
        rep = NamedSharding(self.mesh, P())
        shd = NamedSharding(self.mesh, P(self._onebit_axis))

        def put(path, a):
            return jax.device_put(a, shd if self._ob_is_error_path(path) else rep)

        from jax.tree_util import tree_map_with_path
        return tree_map_with_path(put, st)

    def _build_onebit_batch_fn(self, gas: int) -> Callable:
        """Whole step inside shard_map over dp: per-rank LOCAL grads feed the
        1-bit optimizer, which performs the (compressed) communication."""
        from deepspeed_tpu.utils.jax_compat import shard_map

        opt = self._onebit
        axis = self._onebit_axis
        mesh = self.mesh
        has_axis = mesh.shape.get(axis, 1) > 1

        def step(state: TrainState, batch, rng, lr):
            params, master, opt_state = state.params, state.master, state.opt_state

            def per_rank(params, master, opt_state, batch, rng):
                local = self._ob_map_errors(opt_state, lambda e: e[0])

                def micro_grad(carry, mb):
                    acc, i = carry
                    def lf(p):
                        out = self.loss_fn(p, mb, jax.random.fold_in(rng, i))
                        return (out[0] if isinstance(out, tuple) else out).astype(jnp.float32)
                    loss, grads = jax.value_and_grad(lf)(params)
                    acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
                    return (acc, i + 1), loss

                zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (gsum, _), losses = jax.lax.scan(micro_grad, (zero, jnp.int32(0)), batch)
                grads = jax.tree.map(lambda g: g / gas, gsum)  # LOCAL mean

                target = master if master is not None else params
                new_target, new_local = opt.update(grads, local, target, lr=lr)
                new_opt = self._ob_map_errors(new_local, lambda e: e[None])
                loss = jnp.mean(losses)
                if has_axis:
                    loss = jax.lax.pmean(loss, axis)
                return new_target, new_opt, loss

            rep = P()
            specs = lambda tree, s: jax.tree.map(lambda _: s, tree,
                                                 is_leaf=lambda x: x is None)
            opt_in = jax.tree_util.tree_map_with_path(
                lambda path, _: P(axis) if self._ob_is_error_path(path) else rep,
                opt_state)
            batch_spec = jax.tree.map(lambda _: P(None, axis) if has_axis else P(None), batch)

            wrapped = shard_map(
                per_rank, mesh=mesh,
                in_specs=(specs(params, rep), specs(master, rep), opt_in, batch_spec, rep),
                out_specs=(specs(params, rep), opt_in, rep),
                check_vma=False)
            new_target, new_opt, loss = wrapped(params, master, opt_state, batch, rng)

            if master is not None:
                new_master = new_target
                new_params = jax.lax.with_sharding_constraint(
                    jax.tree.map(lambda m: m.astype(self.compute_dtype), new_master),
                    self._param_shardings)
            else:
                new_master, new_params = None, new_target
            state = state._replace(params=new_params, master=new_master, opt_state=new_opt,
                                   micro_steps=state.micro_steps + gas,
                                   global_steps=state.global_steps + 1)
            return state, loss

        def train_batch_fn(state: TrainState, batch, rng):
            lr = self._lr_fn(state.global_steps)
            state, loss = step(state, batch, rng, lr)
            return state, {"loss": loss, "lr": lr, "loss_scale": state.scaler.loss_scale}

        return jax.jit(train_batch_fn, donate_argnums=(0,))

    def _build_train_batch_fn(self, gas: int) -> Callable:
        """Fused GAS-scan + update, one XLA program. gas == 1 skips the scan
        and the accumulation buffers entirely: the micro-step grads feed the
        optimizer update directly (no acc read/write/re-zero, no scan
        barrier between backward and update)."""
        if self._onebit is not None:
            return self._build_onebit_batch_fn(gas)

        if gas == 1:
            def train_batch_fn(state: TrainState, batch, rng):
                mb = jax.tree.map(lambda x: x[0], batch)
                # fold_in(rng, 0) matches the scan path's micro-step-0 stream
                loss, grads = self._micro_grads(state.params, mb,
                                                jax.random.fold_in(rng, 0),
                                                state.scaler.loss_scale)
                grads = jax.lax.with_sharding_constraint(
                    jax.tree.map(lambda g: g.astype(self.grad_acc_dtype), grads),
                    self._grad_shardings)
                state = state._replace(micro_steps=state.micro_steps + 1)
                state, aux = self._apply_update(state, 1, acc=grads)
                return state, {"loss": loss, "lr": self._lr_fn(state.global_steps - 1),
                               "loss_scale": state.scaler.loss_scale, **aux}

            return jax.jit(train_batch_fn, donate_argnums=(0,))

        def train_batch_fn(state: TrainState, batch, rng):
            scale = state.scaler.loss_scale

            def micro(carry, mb):
                acc, i = carry
                mb_rng = jax.random.fold_in(rng, i)
                loss, grads = self._micro_grads(state.params, mb, mb_rng, scale)
                acc = self._accumulate(acc, grads)
                return (acc, i + 1), loss

            (acc, _), losses = jax.lax.scan(micro, (state.acc_grads, jnp.asarray(0, jnp.int32)), batch, length=gas)
            state = state._replace(acc_grads=acc, micro_steps=state.micro_steps + gas)
            state, aux = self._apply_update(state, gas)
            mean_loss = jnp.mean(losses)
            return state, {"loss": mean_loss, "lr": self._lr_fn(state.global_steps - 1),
                           "loss_scale": state.scaler.loss_scale, **aux}

        return jax.jit(train_batch_fn, donate_argnums=(0,))

    # ------------------------------------------------------------------ #
    # public API

    def train_batch(self, batch=None, data_iter=None):
        """Run one full training batch (gas micro-steps + update) as a single
        compiled program. ``batch`` leaves have leading dim
        ``gas * micro_bs * dp_size`` (this process's share of the global
        batch), or pass ``data_iter`` yielding ``gas`` micro-batches of
        ``micro_bs * dp_size`` samples each."""
        self._check_compression_epoch()
        # snapshot for was_step_applied: +0 makes a fresh buffer so the
        # donated state array's invalidation can't reach it (no host sync)
        self._skipped_before_step = self.state.skipped_steps + 0
        gas = self.gradient_accumulation_steps()
        micro_bs = self.train_micro_batch_size_per_gpu()
        dp = dist.get_world_size(dist.data_parallel_axes(self.mesh))
        expected = gas * micro_bs * dp
        if batch is not None and getattr(self, "_batch_fn", None) is not None:
            # reference semantics: batch_fn normalizes the raw batch BEFORE
            # any shape validation or splitting
            batch = self._batch_fn(batch)
        if batch is not None:
            lead = jax.tree.leaves(batch)[0].shape[0]
            if lead != expected:
                raise ValueError(
                    f"train_batch leading dim {lead} != gas({gas}) * micro_bs({micro_bs}) * dp({dp}) = {expected}")

        if batch is None:
            if data_iter is None:
                data_iter = getattr(self, "_data_iterator", None)
            if data_iter is None:
                if self.training_dataloader is None:
                    raise ValueError("train_batch needs a batch, a data_iter, or engine training_data")
                # standing sequential stream rolling over epochs — the same
                # stream auto_resume's fast-forward reconstructs, so resume
                # stays step-identical on the engine-owned dataloader (a
                # fresh iter() per call would replay the epoch head forever)
                from deepspeed_tpu.runtime.dataloader import RepeatingLoader
                self._data_iterator = iter(RepeatingLoader(self.training_dataloader))
                self._data_iter_external = False
                data_iter = self._data_iterator
            micros = [next(data_iter) for _ in range(gas)]
            if getattr(self, "_batch_fn", None) is not None:
                micros = [self._batch_fn(m) for m in micros]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *micros)
        else:
            batch = jax.tree.map(lambda x: jnp.reshape(jnp.asarray(x), (gas, -1) + tuple(x.shape[1:])), batch)

        self._host_global_steps += 1

        # flops profiler (reference engine.py:1664,2060): one-shot profile of
        # the loss computation at the configured step
        fp_cfg = self._config.flops_profiler_config
        if fp_cfg.enabled and self._host_global_steps == fp_cfg.profile_step:
            from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
            prof = FlopsProfiler(model=self.module, ds_engine=self,
                                 recompute_fwd_factor=fp_cfg.recompute_fwd_factor)
            micro = jax.tree.map(lambda x: x[0][:self.train_micro_batch_size_per_gpu()], batch)
            prof.profile_fn(lambda p, b: self.loss_fn(p, b, jax.random.key(0)),
                            self.state.params, micro)
            prof.print_model_profile(profile_step=self._host_global_steps,
                                     output_file=fp_cfg.output_file)
            self.flops_profiler = prof

        # curriculum learning: truncate the sequence dim to the scheduled
        # difficulty (reference engine.py:1691-1694 legacy seqlen curriculum).
        # Only dims equal to the batch's sequence length are sliced, so 2-D
        # masks [.., S, S] truncate on BOTH key/query dims and non-sequence
        # feature dims stay intact.
        if self.curriculum_scheduler is not None and self._curriculum_metric == "seqlen":
            difficulty = self.curriculum_scheduler.update_difficulty(self._host_global_steps)
            leaves = jax.tree.leaves(batch)
            seq = max((x.shape[2] for x in leaves if x.ndim >= 3), default=0)
            if difficulty < seq:
                def trunc(x):
                    # leaves are [gas, B, S, ...]: the sequence dim is dim 2;
                    # dim 3 is sliced ONLY for square [.., S, S] attention
                    # masks — a feature dim that merely equals S (e.g.
                    # one-hot labels with vocab == S) must stay intact
                    if x.ndim >= 3 and x.shape[2] == seq:
                        x = jax.lax.slice_in_dim(x, 0, difficulty, axis=2)
                        if x.ndim == 4 and x.shape[3] == seq:
                            x = jax.lax.slice_in_dim(x, 0, difficulty, axis=3)
                    return x
                batch = jax.tree.map(trunc, batch)

        # shard the batch over the data axes
        dp_axes = tuple(dist.data_parallel_axes(self.mesh))
        if dp_axes:
            bat = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            sp = "sp" if ("sp" in self.mesh.shape and self.mesh.shape["sp"] > 1) else None

            sp_size = self.mesh.shape["sp"] if sp else 0

            def shard_leaf(x):
                # [gas, B, ...]; seq dim (2) additionally sharded over sp
                # when it divides evenly (non-sequence leaves fall back to dp-only)
                if sp and x.ndim >= 3 and x.shape[2] % sp_size == 0:
                    spec = P(None, bat, sp)
                else:
                    spec = P(None, bat)
                return jax.device_put(x, NamedSharding(self.mesh, spec))

            batch = jax.tree.map(shard_leaf, batch)

        self.tput_timer.start()
        prof = self._profiler
        if prof is not None:
            # profile-window boundary: starts/stops the jax.profiler
            # capture when an armed window begins/ends at this step
            prof.tick()
        t0 = time.perf_counter() if self._telemetry is not None else 0.0
        self._rng, step_rng = jax.random.split(self._rng)
        with (prof.annotate("train_batch") if prof is not None
              and prof.active else contextlib.nullcontext()):
            if self._offload is not None:
                fn = self._accum_batch_jit.get(gas)
                if fn is None:
                    fn = self._watched(self._build_accum_batch_fn(gas),
                                       f"engine.accum_batch[gas={gas}]")
                    self._accum_batch_jit[gas] = fn
                self.state, mean_loss = fn(self.state, batch, step_rng)
                self._losses = mean_loss
                metrics = self._host_step()
            else:
                fn = self._train_batch_jit.get(gas)
                if fn is None:
                    fn = self._watched(self._build_train_batch_fn(gas),
                                       f"engine.train_batch[gas={gas}]")
                    self._train_batch_jit[gas] = fn
                self.state, metrics = fn(self.state, batch, step_rng)
        self.tput_timer.stop(global_step=True)
        self._data_progress["iterations"] += 1
        self._data_progress["consumed_samples"] += self.train_batch_size()
        if self._telemetry is not None:
            # telemetry-on accepts one host sync per step: the wall clock
            # must bracket the device work for step time / MFU to mean
            # anything (off-mode never reaches this branch)
            jax.block_until_ready(metrics["loss"])
            dt_s = time.perf_counter() - t0
            # wait = host time since the previous step's end up to this
            # step's dispatch: data loading + host-side prep — the
            # input-bound signal the data-stall detector compares against
            # the bracketed device time
            wait_s = (t0 - self._t_prev_step_end
                      if self._t_prev_step_end is not None else 0.0)
            self._tel_record_step(batch, dt_s, metrics, wait_s)
            if self._health is not None:
                # observe BEFORE the flush so a flush-step anomaly is in
                # the very snapshot it fired on (matches the step() order)
                self._observe_health(metrics, dt_s, wait_s)
            self._tel_maybe_flush()
            self._t_prev_step_end = time.perf_counter()
        if self.quantizer is not None:
            self._quantize_step(batch)
        self._write_monitor_events(metrics)
        self._report_progress(metrics)
        return metrics["loss"]

    def _quantize_step(self, batch):
        """MoQ post-step hook (reference fp16 optimizers calling
        ``quantizer.quantize`` after each step, runtime/quantize.py): walks
        the per-leaf bit schedule and fake-quantizes the live params. With
        eigenvalue enabled, per-block curvature is re-estimated at gas
        boundaries while a precision switch is pending, and the MEAN across
        blocks scales the stacked-layers leaves' periods (deviation from the
        reference's per-block factor, forced by the stacked-layers leaf
        layout; max is useless here because post_process normalizes the
        largest eigenvalue to 1.0)."""
        # fp16 overflow steps skipped their update: don't advance the bit
        # schedule on them either (reference defers quantize on overflow)
        overflow = False
        if self.fp16_enabled():
            cur = int(self.state.skipped_steps)
            overflow = cur > self._moq_seen_skipped
            self._moq_seen_skipped = cur

        block_ev = None
        if self.eigenvalue is not None and \
                self._host_global_steps % self.eigenvalue.gas_boundary_resolution == 0 \
                and self.quantizer.any_precision_switch():
            micro = jax.tree.map(lambda x: x[0], batch)
            params = self.state.params
            name = self._ev_layer_name
            n_blocks = self._ev_layer_num or 0
            if n_blocks > 0 and name in params:
                masks = self.eigenvalue.layer_masks(params, name, n_blocks)
            else:
                masks = [jax.tree.map(lambda a: jnp.ones(a.shape, jnp.float32), params)]
            self._rng, ev_rng = jax.random.split(self._rng)

            def scalar_loss(p):
                out = self.loss_fn(p, micro, ev_rng)
                return out[0] if isinstance(out, tuple) else out

            vals = self.eigenvalue.compute_eigenvalue(
                scalar_loss, params, masks, rng=ev_rng)
            # post_process normalizes to [0,1] with max==1; the zoo stacks
            # all layers in one leaf, so aggregate with the MEAN (a
            # max would be the constant 1.0 and carry no information)
            block_ev = {name: sum(vals) / len(vals)} if vals else None
        new_params = self.quantizer.quantize_tree(self.state.params,
                                                  overflow=overflow,
                                                  block_eigenvalue=block_ev)
        # quantize ops run eagerly: pin the results back onto the param
        # shardings so the donated train-step jit sees identical layouts
        new_params = jax.device_put(new_params, self._param_shardings)
        self.state = self.state._replace(params=new_params)

    def _check_compression_epoch(self) -> None:
        """A CompressionScheduler transition changes what the model
        computes; compiled programs captured the OLD trace, so drop them
        when the wrapped model's epoch moved. Consulted on every public
        entry that traces the model (train_batch / forward / backward /
        eval_batch); step() needs no check — _apply_jit only runs the
        optimizer update, never the model."""
        epoch = getattr(self.client_model, "compression_epoch", None)
        if epoch is not None and epoch != getattr(self, "_compression_epoch_seen", None):
            if getattr(self, "_compression_epoch_seen", None) is not None:
                self._train_batch_jit.clear()
                self._grad_jit = self._apply_jit = self._eval_jit = None
            self._compression_epoch_seen = epoch

    # ---- reference-shaped trio ---- #

    def forward(self, batch):
        """Compute loss AND grads for a micro-batch in one pass (value_and_grad
        costs the same as grad alone); grads are cached so ``backward()`` just
        accumulates them — the reference's fwd/bwd split without running the
        model twice."""
        self._check_compression_epoch()
        if self._grad_jit is None:
            def vg_fn(state: TrainState, b, rng):
                return self._micro_grads(state.params, b, rng, state.scaler.loss_scale)
            self._grad_jit = self._watched(jax.jit(vg_fn), "engine.forward")
        batch = jax.tree.map(jnp.asarray, batch)
        self._rng, rng = jax.random.split(self._rng)
        t0 = time.perf_counter()
        loss, grads = self._grad_jit(self.state, batch, rng)
        self._tel_phase("fwd", t0, loss)
        self._cached_grads = grads
        self._losses = loss
        return loss

    __call__ = forward

    def backward(self, loss=None, batch=None, allreduce_gradients=True, release_loss=False):
        """Accumulate the grads computed by ``forward()`` (or compute them for
        an explicitly given micro-batch)."""
        if batch is not None:
            # forward() owns the whole micro-grad path (compression-epoch
            # check, batch conversion, rng split, jit build) — delegating
            # keeps the rng stream identical to the forward()+backward() style
            self.forward(batch)
        if getattr(self, "_cached_grads", None) is None:
            raise RuntimeError("backward() called before forward(); pass batch= explicitly if needed")
        self._ensure_acc_grads()

        if self._acc_jit is None:
            def acc_fn(state: TrainState, grads):
                acc = self._accumulate(state.acc_grads, grads)
                return state._replace(acc_grads=acc, micro_steps=state.micro_steps + 1)
            self._acc_jit = self._watched(jax.jit(acc_fn, donate_argnums=(0,)),
                                          "engine.backward")

        t0 = time.perf_counter()
        self.state = self._acc_jit(self.state, self._cached_grads)
        self._tel_phase("bwd", t0, self.state.micro_steps)
        self._cached_grads = None
        return self._losses

    def _uses_acc_grad_buffers(self) -> bool:
        """Whether the compiled step reads/writes state.acc_grads (the
        gas==1 fused path, the 1-bit path, and 1F1B pipelines do not)."""
        if self._onebit is not None:
            return False
        return not (self.gradient_accumulation_steps() == 1 and self._offload is None)

    def _ensure_acc_grads(self) -> None:
        """Materialize the accumulation buffers the gas==1 fused path skips
        (only the forward/backward/step trio needs them)."""
        if self.state.acc_grads == ():
            acc = jax.tree.map(
                lambda p, s: jax.device_put(jnp.zeros(p.shape, self.grad_acc_dtype), s),
                self.state.params, self._grad_shardings)
            self.state = self.state._replace(acc_grads=acc)

    def _zeroed_acc(self, acc):
        """Zero the accumulation buffers through the donated reset jit —
        reuses the buffers in place (no transient second tree)."""
        if self._reset_acc_jit is None:
            self._reset_acc_jit = jax.jit(
                lambda a: jax.tree.map(jnp.zeros_like, a), donate_argnums=(0,))
        return self._reset_acc_jit(acc)

    def is_gradient_accumulation_boundary(self) -> bool:
        return int(self.state.micro_steps) % self.gradient_accumulation_steps() == 0

    def step(self, lr_kwargs=None):
        """Apply the optimizer update at the accumulation boundary
        (no-op otherwise, matching reference engine.py:1990)."""
        if not self.is_gradient_accumulation_boundary():
            return
        self._skipped_before_step = self.state.skipped_steps + 0
        if self._offload is not None:
            t0 = time.perf_counter()
            metrics = self._host_step()
            if self._telemetry is not None:
                self._host_global_steps += 1
                self._tel_record_update(metrics)
                # wait/stall series record under plain telemetry, exactly
                # like the train_batch path (health only adds detectors)
                busy, wait = self._trio_wait_busy(
                    self._trio_busy_s + time.perf_counter() - t0)
                if self._health is not None:
                    self._observe_health(metrics, busy, wait)
                self._tel_maybe_flush()
            self._write_monitor_events(metrics)
            self._report_progress(metrics)
            return
        if self._apply_jit is None:
            gas = self.gradient_accumulation_steps()
            self._apply_jit = self._watched(
                jax.jit(partial(self._apply_update, gas=gas), donate_argnums=(0,)),
                "engine.step")
        t0 = time.perf_counter()
        self.state, aux = self._apply_jit(self.state)
        self._tel_phase("step", t0, self.state.global_steps)
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        metrics = {"loss": self._losses, "lr": self.get_lr()[0],
                   "loss_scale": self.state.scaler.loss_scale, **aux}
        if self._telemetry is not None:
            # keep the host step counter moving on the trio path too, so
            # the flush cadence (and snapshot step stamps) work without a
            # device fetch; train_batch and step() are alternative
            # boundaries, never both for one update
            self._host_global_steps += 1
            self._tel_record_update(metrics)
            # _tel_phase("step") above already folded this apply into the
            # cycle's busy accumulator; wait/stall series record under
            # plain telemetry (health only adds the detectors on top)
            busy, wait = self._trio_wait_busy(self._trio_busy_s)
            if self._health is not None:
                self._observe_health(metrics, busy, wait)
            self._tel_maybe_flush()
        self._write_monitor_events(metrics)
        self._report_progress(metrics)

    def eval_batch(self, batch):
        """Evaluation loss — DETERMINISTIC: the loss is called with rng=None,
        which the model zoo's convention reads as "no stochasticity" (dropout
        off, no MoE routing jitter/RTS draw), matching the reference's
        module.eval() semantics."""
        self._check_compression_epoch()
        if self._eval_jit is None:
            def eval_fn(params, b):
                out = self.loss_fn(params, b, None)
                return out[0] if isinstance(out, tuple) else out
            self._eval_jit = self._watched(jax.jit(eval_fn), "engine.eval_batch")
        return self._eval_jit(self.state.params, jax.tree.map(jnp.asarray, batch))

    # ------------------------------------------------------------------ #
    # accessors (reference engine.py:479-858 config properties)

    def train_batch_size(self) -> int:
        return self._config.train_batch_size

    def set_train_batch_size(self, train_batch_size: int) -> None:
        """Adjust the global batch by changing gradient-accumulation steps;
        the micro-batch size is unchanged (reference ``engine.py:426`` —
        elastic/curriculum batch scaling). The compiled step cache is keyed
        by gas, so a new gas compiles once and is then hot."""
        micro = self.train_micro_batch_size_per_gpu()
        dp = dist.get_world_size(dist.data_parallel_axes(self.mesh))
        if train_batch_size % (micro * dp):
            raise ValueError(
                f"Train batch size ({train_batch_size}) must be divisible by "
                f"micro-batch ({micro}) x data parallelism ({dp})")
        new_gas = train_batch_size // (micro * dp)
        if new_gas < 1:
            raise ValueError(f"Train batch size ({train_batch_size}) must cover "
                             f"at least one micro-batch per dp rank ({micro * dp})")
        self._config.train_batch_size = train_batch_size
        self._config.gradient_accumulation_steps = new_gas
        # the trio's cached apply step froze the OLD gas (grad divisor):
        # rebuild it at the new one
        self._apply_jit = None
        self.tput_timer.batch_size = train_batch_size
        if new_gas > 1 and self._acc_dtype_name is None and \
                self.grad_acc_dtype != jnp.float32:
            # engines born at gas==1 pinned accumulation to the compute dtype
            # (no buffers existed); gas>1 accumulates in fp32 per the
            # init-time rule, so restore it before (re)allocating buffers
            self.grad_acc_dtype = jnp.float32
            if self.state is not None and self.state.acc_grads != ():
                self.state = self.state._replace(acc_grads=())
        if self._uses_acc_grad_buffers():
            # the gas>1 scan path reads state.acc_grads; 1-bit/offload-free
            # gas==1 engines skip them entirely
            self._ensure_acc_grads()

    def train_micro_batch_size_per_gpu(self) -> int:
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self._config.gradient_accumulation_steps

    def gradient_clipping(self) -> float:
        return self._config.gradient_clipping

    def zero_optimization_stage(self) -> int:
        return self._config.zero_optimization_stage

    def fp16_enabled(self) -> bool:
        return self._config.fp16_enabled

    def bfloat16_enabled(self) -> bool:
        return self._config.bfloat16_enabled

    def steps_per_print(self) -> int:
        return self._config.steps_per_print

    def zero_enabled(self) -> bool:
        return self._config.zero_enabled

    # -- reference surface conveniences (engine.py:479-858, 2168-2510) -- #

    def zero_optimization(self) -> bool:
        return self._config.zero_optimization_stage > 0

    def optimizer_name(self):
        return self._optimizer_name

    def scheduler_name(self):
        return self._config.scheduler_name

    def scheduler_params(self):
        return self._config.scheduler_params

    def dynamic_loss_scale(self) -> bool:
        # static fp16 (loss_scale != 0) reports False, like the reference
        return self.fp16_enabled() and self._config.fp16_config.dynamic_loss_scale

    def wall_clock_breakdown(self) -> bool:
        return bool(self._config.wall_clock_breakdown)

    def pld_enabled(self) -> bool:
        return bool(self._config.pld_enabled)

    def curriculum_enabled_legacy(self) -> bool:
        return bool(self._config.curriculum_enabled_legacy)

    def random_ltd_enabled(self) -> bool:
        cfg = getattr(self._config, "data_efficiency_config", {}) or {}
        return bool(cfg.get("data_routing", {}).get("random_ltd",
                                                    {}).get("enabled", False))

    def get_batch_info(self):
        """(train_batch_size, micro_batch_size, gradient_accumulation_steps)
        — reference engine.py get_batch_info."""
        return (self.train_batch_size(),
                self.train_micro_batch_size_per_gpu(),
                self.gradient_accumulation_steps())

    def train(self, mode: bool = True):
        """torch-style mode toggle kept for port compatibility. The zoo is
        functional — train/eval behavior is chosen per call (e.g. MoE
        forward(train=...), eval_batch) — so this records intent only."""
        self._training_mode = bool(mode)
        return self

    def eval(self):
        return self.train(False)

    def was_step_applied(self) -> bool:
        """True when the most recent boundary step updated params (i.e. was
        not an fp16 overflow skip) — reference engine.py was_step_applied."""
        before = getattr(self, "_skipped_before_step", None)
        if before is None:
            return False
        return int(self.state.skipped_steps) == int(before)

    def module_state_dict(self):
        """The module parameters (reference module_state_dict: the
        checkpoint-shaped weights view)."""
        return self.state.params

    def load_module_state_dict(self, state_dict, strict: bool = True):
        """Replace the module parameters with ``state_dict``, resharded
        onto the engine's param shardings; fp32 masters (device or
        host-offloaded) follow so the optimizer continues from the new
        weights (reference load_module_state_dict). ``strict=False``
        overlays only the leaves present in ``state_dict`` (by path),
        keeping the rest."""
        from deepspeed_tpu.utils.pytree import leaf_key, leaf_paths

        if strict:
            import jax.tree_util as jtu
            if jtu.tree_structure(state_dict) != jtu.tree_structure(self.state.params):
                raise ValueError("state_dict structure does not match module "
                                 "parameters (pass strict=False to overlay "
                                 "matching leaves only)")
            new_params = jax.tree.map(
                lambda a, p: jax.device_put(jnp.asarray(a, p.dtype), p.sharding),
                state_dict, self.state.params)
        else:
            # pair by PATH KEY, never by flatten order (dict flattening is
            # key-sorted while leaf_paths preserves insertion order)
            overlay = leaf_paths(state_dict)
            leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(
                self.state.params)
            new_leaves = [
                jax.device_put(jnp.asarray(overlay.get(leaf_key(path), p),
                                           p.dtype), p.sharding)
                for path, p in leaves_with_path]
            new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        replace = {"params": new_params}
        if self.state.master is not None:
            # cast on device: no host round-trip for model-sized trees
            replace["master"] = jax.tree.map(
                lambda a, m: jax.device_put(a.astype(jnp.float32), m.sharding),
                new_params, self.state.master)
        self.state = self.state._replace(**replace)
        if self._offload is not None:
            # host/NVMe fp32 masters are the authoritative weights for the
            # next step — refresh them or the load is silently reverted
            from deepspeed_tpu.utils.pytree import leaf_key
            flat_new = jax.tree_util.tree_flatten_with_path(new_params)[0]
            self._offload.load_masters(
                {leaf_key(path): np.asarray(jax.device_get(leaf), np.float32).ravel()
                 for path, leaf in flat_new})

    def set_dataloader(self, loader) -> None:
        """Reference pipe-engine surface: replace the training dataloader
        and start a STANDING iterator over it (successive batchless
        train_batch calls consume successive micro-batches, not the first
        gas items forever)."""
        self.training_dataloader = loader
        self._data_iterator = iter(loader) if loader is not None else None
        self._data_iter_external = False
        # progress describes the data pipeline; a new pipeline starts at 0
        self._data_progress = {"consumed_samples": 0, "iterations": 0}

    def set_dataiterator(self, iterator) -> None:
        """Reference pipe-engine surface: a standing iterator yielding
        micro-batches for batchless train_batch calls."""
        self._data_iterator = iterator
        self._data_iter_external = iterator is not None
        # progress describes the data pipeline; a new pipeline starts at 0
        self._data_progress = {"consumed_samples": 0, "iterations": 0}

    def set_batch_fn(self, fn) -> None:
        """Post-process every batch (or micro-batch from an iterator)
        before it enters the compiled step (reference set_batch_fn)."""
        self._batch_fn = fn

    def zero_grad(self) -> None:
        """Zero the gradient-accumulation buffers (reference zero_grad /
        optimizer.zero_grad between trio steps)."""
        if self.state.acc_grads != ():
            self.state = self.state._replace(
                acc_grads=self._zeroed_acc(self.state.acc_grads))
        self._cached_grads = None

    def empty_partition_cache(self) -> None:
        """Reference frees gathered ZeRO-3 params here; gathers live inside
        the compiled step under XLA's allocator, so there is no persistent
        partition cache to free. Kept as an explicit no-op."""

    def memory_breakdown(self):
        """Live-buffer breakdown per device (reference memory_breakdown /
        see_memory_usage)."""
        out = {}
        for d in jax.local_devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:  # backend without memory stats (CPU)
                stats = {}
            out[str(d)] = {k: stats[k] for k in ("bytes_in_use",
                                                 "peak_bytes_in_use",
                                                 "bytes_limit") if k in stats}
        return out

    def dump_state(self) -> None:
        """Log a one-shot engine state summary (reference dump_state)."""
        log_dist(
            f"DeepSpeedEngine state: optimizer={self._optimizer_name}, "
            f"dtype={self.compute_dtype.__name__}, mesh={dict(self.mesh.shape)}, "
            f"batch={self.get_batch_info()}, zero_stage={self.zero_optimization_stage()}, "
            f"global_steps={self.global_steps}, skipped={self.skipped_steps}, "
            f"loss_scale={self.loss_scale}", ranks=[0])

    def save_16bit_model(self, save_dir, save_filename: str = "model_16bit.npz",
                         exclude_frozen_parameters: bool = False):
        """Write the module weights as a single 16-bit flat-key .npz
        (reference save_16bit_model / zero3 consolidated fp16 save — params
        here are full logical arrays, so no cross-rank gather is needed).
        Returns the written path."""
        import os

        from deepspeed_tpu.utils.pytree import leaf_paths

        if exclude_frozen_parameters:
            raise NotImplementedError(
                "exclude_frozen_parameters: the functional engine has no "
                "frozen-parameter registry; filter the tree before saving")

        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, save_filename)
        flat = {}
        for key, leaf in leaf_paths(self.state.params).items():
            a = np.asarray(leaf)
            if a.dtype == np.float32:
                import ml_dtypes
                a = a.astype(ml_dtypes.bfloat16)
            # npz has no bf16: store raw bits + dtype tag
            if a.dtype.name == "bfloat16":
                flat[key + "::bf16"] = a.view(np.uint16)
            else:
                flat[key] = a
        np.savez(path, **flat)
        log_dist(f"saved 16-bit model weights to {path}", ranks=[0])
        return path

    def save_fp16_model(self, save_dir, save_filename: str = "model_16bit.npz"):
        """Reference alias for save_16bit_model."""
        return self.save_16bit_model(save_dir, save_filename)

    def destroy(self) -> None:
        """Drop compiled executables and large state references (reference
        engine.destroy): the engine is unusable afterwards."""
        self.disable_preemption_handler()
        if self._profiler is not None:
            self._profiler.stop()   # a dangling capture wedges the profiler
        if self._tel_sampler is not None:
            self._tel_sampler.stop()
            self._tel_sampler = None
        if self._tel_exporter is not None:
            self._tel_exporter.stop()
            self._tel_exporter = None
        if self._ckpt_writer is not None:
            self._ckpt_writer.stop()
            self._ckpt_writer = None
        self._train_batch_jit = {}
        self._grad_jit = None
        self._apply_jit = None
        self._eval_jit = None
        self._acc_jit = None
        self._reset_acc_jit = None
        self._cached_grads = None
        self._offload = None
        self.state = None

    @property
    def global_steps(self) -> int:
        return int(self.state.global_steps)

    @property
    def micro_steps(self) -> int:
        return int(self.state.micro_steps)

    @property
    def skipped_steps(self) -> int:
        return int(self.state.skipped_steps)

    def get_lr(self):
        return [float(self._lr_fn(self.state.global_steps))]

    def get_type(self):
        """Optimizer type per param group (reference engine.py:2171)."""
        return [self._optimizer_name]

    def get_mom(self):
        """Momentum per param group (reference engine.py:2174): SGD-family
        reports ``momentum``, Adam-family ``betas``; a client-supplied optax
        chain reports [None] (its momenta are not introspectable)."""
        from deepspeed_tpu.runtime.optimizers import optimizer_momenta
        return [optimizer_momenta(self._optimizer_name,
                                  self._config.optimizer_params)]

    def get_pld_theta(self):
        """Current progressive-layer-drop theta, or None when PLD is off
        (reference engine.py:2180)."""
        if self.progressive_layer_drop is not None:
            return self.progressive_layer_drop.get_theta()
        return None

    def get_global_grad_norm(self) -> float:
        if self.state.acc_grads == ():  # gas==1 fused path keeps no buffers
            return 0.0
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), self.state.acc_grads)
        return float(global_norm(grads))

    @property
    def loss_scale(self) -> float:
        return float(self.state.scaler.loss_scale)

    @property
    def module(self):
        return self.client_model

    @property
    def optimizer(self):
        return self.tx

    def __getattr__(self, name):
        # delegate unknown attributes to the client model (reference :464)
        client = self.__dict__.get("client_model")
        if client is not None and hasattr(client, name):
            return getattr(client, name)
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    # ------------------------------------------------------------------ #
    # monitoring / reporting

    def _write_monitor_events(self, metrics) -> None:
        if not self.monitor.enabled:
            return
        step = self.global_steps
        events = [("Train/Samples/train_loss", float(metrics["loss"]), step),
                  ("Train/Samples/lr", float(metrics["lr"]), step)]
        if self.fp16_enabled():
            events.append(("Train/Samples/loss_scale", float(metrics["loss_scale"]), step))
        self.monitor.write_events(events)

    def _report_progress(self, metrics) -> None:
        if not self.steps_per_print():
            return  # no host-device sync when printing is off (keeps dispatch async)
        step = self.global_steps
        if step % self.steps_per_print() == 0:
            log_dist(f"step={step}, skipped={self.skipped_steps}, lr={float(metrics['lr']):.3e}, "
                     f"loss={float(metrics['loss']):.4f}", ranks=[0])

    # ------------------------------------------------------------------ #
    # telemetry

    def _watched(self, fn, name: str):
        """Route a compiled entry point through the compile watchdog when
        telemetry is on (counts compilations, records compile wall time +
        input shapes, flags recompilation storms)."""
        if self._telemetry is None:
            return fn
        return self._tel_watchdog.watch(fn, name)

    def _tel_phase(self, phase: str, t0: float, sync_on) -> None:
        """Record one trio-phase duration (blocks on ``sync_on`` so the
        wall clock brackets the device work)."""
        if self._telemetry is None:
            return
        jax.block_until_ready(sync_on)
        dur = time.perf_counter() - t0
        self._tel_phase_hist.labels(phase=phase).observe(dur * 1e3)
        if self._tel_events is not None:
            now = time.monotonic_ns()
            dur_ns = int(dur * 1e9)
            self._tel_events.emit("train.phase",
                                  step=self._host_global_steps,
                                  t_ns=now - dur_ns, dur_ns=dur_ns,
                                  phase=phase)
        # accumulated per update cycle: the trio path's device-busy time
        # (fwd + bwd + step), consumed by _trio_wait_busy at the boundary
        self._trio_busy_s += dur

    def _trio_wait_busy(self, busy_s: float):
        """Trio/offload boundary wait accounting: ``busy_s`` is the
        compiled/host work this cycle actually measured (accumulated phase
        durations); the wait is the REST of the boundary-to-boundary wall
        time — data loading and host prep between the timed calls — so the
        data-stall detector sees input-bound trio runs too, not just
        train_batch ones. Resets the cycle accumulators and feeds the
        cumulative train/data_stall_fraction gauge."""
        now = time.perf_counter()
        wall = (now - self._t_prev_step_end
                if self._t_prev_step_end is not None else busy_s)
        self._t_prev_step_end = now
        self._trio_busy_s = 0.0
        wait_s = max(wall - busy_s, 0.0)
        self._tel_account_wait(wait_s, busy_s)
        return busy_s, wait_s

    def _tel_account_wait(self, wait_s: float, busy_s: float) -> None:
        """The single home of wait/stall accounting (train_batch and the
        trio boundary both feed it): the data-wait histogram plus the
        cumulative wait/(wait+busy) stall gauge."""
        wait_s = max(wait_s, 0.0)
        self._tel_wait_hist.observe(wait_s * 1e3)
        self._tel_wait_total += wait_s
        self._tel_busy_total += max(busy_s, 0.0)
        tot = self._tel_wait_total + self._tel_busy_total
        if tot > 0:
            self._tel_stall_gauge.set(self._tel_wait_total / tot)

    def _tel_record_step(self, batch, dt_s: float, metrics=None,
                         wait_s: float = 0.0) -> None:
        """Per-step series: step time, tokens/sec, achieved TFLOPs + MFU
        (PaLM-style: model flops/token x token rate / peak), data-wait
        time, loss/grad-norm/fp16 gauges, plus the periodic JSONL /
        MonitorMaster flush (memory gauges sampled on the same cadence)."""
        self._tel_step_hist.observe(dt_s * 1e3)
        self._tel_steps_counter.inc()
        self._tel_tracer.add_event("train_batch",
                                   time.perf_counter() - dt_s, dt_s)
        if self._tel_events is not None:
            now = time.monotonic_ns()
            dur = int(dt_s * 1e9)
            self._tel_events.emit("train.step", step=self._host_global_steps,
                                  t_ns=now - dur, dur_ns=dur)
        lead = jax.tree.leaves(batch)[0]
        dims = lead.shape[:3] if lead.ndim >= 3 else lead.shape[:2]
        tokens = 1
        for d in dims:
            tokens *= int(d)
        tps = tokens / max(dt_s, 1e-9)
        self._tel_tokens_gauge.set(tps)
        self._tel_tokens_counter.inc(tokens)
        fpt = self._tel_flops_per_token(batch)
        n_chips = max(1, int(np.prod(list(self.mesh.shape.values()))))
        achieved = tps * fpt / 1e12 / n_chips
        self._tel_tflops_gauge.set(achieved)
        peak = self._tel_peak_tflops()
        self._tel_mfu_gauge.set(achieved / peak if peak > 0 else 0.0)
        self._tel_account_wait(wait_s, dt_s)
        if metrics is not None:
            self._tel_record_update(metrics)

    def _tel_maybe_flush(self) -> None:
        """JSONL/MonitorMaster flush on the ``steps_per_snapshot`` cadence,
        with memory gauges sampled just before (every step under health —
        host-side dict reads, ~µs). Shared by the train_batch path and the
        trio/offload step() boundary so a trio run feeds the sink (and the
        ``dscli health`` screen) too."""
        tcfg = self._telemetry
        n = tcfg.steps_per_snapshot
        flush = bool(n) and self._host_global_steps % n == 0
        if flush or self._health is not None:
            self._tel_sample_memory(self._tel_reg)
        if flush:
            if tcfg.jsonl_path:
                self._tel_reg.write_jsonl(tcfg.jsonl_path,
                                          step=self._host_global_steps)
            if tcfg.publish_to_monitor:
                self._tel_reg.publish(self.monitor, self._host_global_steps)

    def _tel_record_update(self, metrics) -> None:
        """Optimizer-update series shared by every path that applies an
        update (fused train_batch, the trio's step(), the offload host
        step): loss gauge, the pre-clip grad-norm histogram, and — fp16 —
        the skipped-steps / loss-scale gauges with a rate-limited warning
        when overflow skips persist (today's `lax.cond` skip is otherwise
        invisible unless you read the state object)."""
        import math as _math
        self._tel_loss_gauge.set(float(metrics["loss"]))
        gn = metrics.get("grad_norm")
        if gn is not None:
            gn = float(gn)
            if _math.isfinite(gn):
                self._tel_grad_norm_hist.observe(gn)
        if self.fp16_enabled():
            skipped = int(self.state.skipped_steps)
            # one blocking scalar fetch per step, shared with
            # _observe_health (which runs right after on every boundary)
            self._tel_skipped_cached = skipped
            self._tel_skipped_gauge.set(skipped)
            self._tel_scale_gauge.set(float(metrics["loss_scale"]))
            if self._tel_events is not None:
                if self._ev_skip_prev is not None \
                        and skipped > self._ev_skip_prev:
                    self._tel_events.emit(
                        "train.fp16_skip", step=self._host_global_steps,
                        skipped_total=skipped,
                        loss_scale=float(metrics["loss_scale"]))
                self._ev_skip_prev = skipped
            if self._health is None:
                # the HealthMonitor's sustained-overflow detector owns
                # this when enabled; health-off still surfaces it
                self._warn_sustained_skips(skipped)

    def _warn_sustained_skips(self, skipped_total: int) -> None:
        window = self._telemetry.health.overflow_window
        delta = skipped_total - self._tel_skip_seen
        self._tel_skip_seen = skipped_total
        self._tel_skip_consec = self._tel_skip_consec + 1 if delta > 0 else 0
        if window and self._tel_skip_consec and \
                self._tel_skip_consec % window == 0:
            logger.warning(
                f"fp16 overflow skipped the last {self._tel_skip_consec} "
                f"consecutive optimizer updates (total skipped "
                f"{skipped_total}, loss scale {self.loss_scale:.4g}). The "
                "run is making no progress — check for numerics issues or "
                "lower the initial loss scale.")

    def _observe_health(self, metrics, dt_s: float, wait_s: float) -> None:
        """Feed one step's record through the health detectors (host side;
        sentinel values were computed inside the compiled step and arrive
        as one small vector — fetching them costs no extra device sync
        beyond the one telemetry-on already performs)."""
        from deepspeed_tpu.monitor.health import StepHealth, sentinel_to_dict
        # global_steps, not _host_global_steps: the trio/offload step()
        # paths never bump the latter, and a constant step number would
        # permanently mute the per-detector warn/dump rate limiting
        rec = StepHealth(step=int(self.state.global_steps),
                         loss=float(metrics["loss"]),
                         loss_scale=float(metrics.get("loss_scale", 1.0)),
                         step_time_s=dt_s, wait_time_s=wait_s)
        gn = metrics.get("grad_norm")
        if gn is not None:
            rec.grad_norm = float(gn)
        sen = metrics.get("sentinels")
        if sen is not None:
            d = sentinel_to_dict(sen, self._health.bucket_names)
            rec.grad_norm = d["grad_norm"]
            rec.nonfinite_grads = d["nonfinite_grads"]
            rec.nonfinite_params = d["nonfinite_params"]
            rec.update_ratio = d["update_ratio"]
            rec.bucket_norms = tuple(d["bucket_norms"].values())
        elif "nonfinite_grads" in metrics:  # offload host path
            rec.nonfinite_grads = float(metrics["nonfinite_grads"])
        if self.fp16_enabled():
            # reuse _tel_record_update's single skipped_steps fetch;
            # detect this boundary's skip against the previous total
            after = getattr(self, "_tel_skipped_cached", None)
            if after is None:
                after = int(self.state.skipped_steps)
            prev = self._tel_skipped_prev
            if prev is None:
                before = getattr(self, "_skipped_before_step", None)
                prev = int(before) if before is not None else after
            rec.skipped = after > prev
            self._tel_skipped_prev = after
        self._health.observe_step(rec)

    def health_report(self) -> Dict:
        """The health observatory's one-call summary: anomaly counts,
        loss/grad-norm EWMAs, consecutive-skip and data-stall state, the
        last step record, and a fresh memory sample. ``{"enabled": False}``
        when ``telemetry.health`` is off."""
        if self._health is None:
            return {"enabled": False}
        return self._health.report()

    def _tel_flops_per_token(self, batch) -> float:
        """Training flops per token, computed once per engine: the flops
        profiler's ``cost_analysis()`` path on the loss forward for ONE
        sample (x3 for fwd+bwd, plus the configured recompute factor),
        falling back to the model's analytic ``flops_per_token``."""
        if self._tel_flops_per_token_v is not None:
            return self._tel_flops_per_token_v
        fpt = 0.0
        try:
            from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
            prof = FlopsProfiler(model=self.module, ds_engine=self)
            micro = jax.tree.map(lambda x: x[0][:1], batch)
            # rng=None: the zoo's deterministic eval convention — dropout
            # off changes flops negligibly and avoids threading an rng
            prof.profile_fn(lambda p, b: self.loss_fn(p, b, None),
                            self.state.params, micro)
            lead = jax.tree.leaves(micro)[0]
            micro_tokens = int(np.prod(lead.shape))
            fwd = float(prof.get_total_flops())
            if fwd > 0 and micro_tokens > 0:
                fac = 3.0 + float(getattr(self._config.flops_profiler_config,
                                          "recompute_fwd_factor", 0.0) or 0.0)
                fpt = fwd * fac / micro_tokens
        except Exception as e:  # profiling must never break the step
            logger.warning(f"telemetry: flops profile failed ({e}); "
                           "falling back to analytic flops_per_token")
        if not fpt:
            try:
                fpt = float(self.module.flops_per_token())
            except Exception:
                fpt = 0.0
        self._tel_flops_per_token_v = fpt
        return fpt

    def _tel_peak_tflops(self) -> float:
        """MFU denominator: config > accelerator device-kind table; a kind
        with no published peak gives 0 (no peak, no gauge)."""
        p = float(self._telemetry.peak_tflops_per_chip or 0.0)
        if p > 0:
            return p
        from deepspeed_tpu.accelerator import get_accelerator
        try:
            return get_accelerator().peak_tflops()
        except LookupError:
            return 0.0

    def telemetry_snapshot(self) -> Dict:
        """Whole-process registry snapshot plus the compile watchdog's
        summary. Empty dict when telemetry is off."""
        if self._telemetry is None:
            return {}
        if self._health is not None:
            # refresh the memory gauges so on-demand snapshots (and the
            # debug bundles that embed them) carry current HBM numbers
            self._tel_sample_memory(self._tel_reg)
        snap = self._tel_reg.snapshot()
        snap["compile"] = self._tel_watchdog.summary()
        return snap

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write recorded host spans as chrome-trace JSON (view in
        Perfetto / chrome://tracing); returns the path, or None when
        telemetry is off."""
        if self._telemetry is None:
            return None
        path = path or self._telemetry.chrome_trace_path
        if not path:
            raise ValueError("no trace path: pass one or set "
                             "telemetry.chrome_trace_path")
        return self._tel_tracer.export_chrome_trace(path)

    def profile(self, steps: int, log_dir: Optional[str] = None):
        """Arm an on-demand device-profile capture: the next ``steps``
        ``train_batch`` calls run under ``jax.profiler`` and the trace
        lands in ``log_dir`` (default ``telemetry.profile.dir``) —
        summarize with ``dscli profile <log_dir>``. Works with telemetry
        off (it is a profiler window, not a metrics feature); raises if a
        capture is already running. Returns the armed window."""
        if self._profiler is None:
            from deepspeed_tpu.monitor.trace import ProfileWindow
            pcfg = self._config.telemetry_config.profile
            self._profiler = ProfileWindow(log_dir or pcfg.dir)
        self._profiler.arm(steps, log_dir=log_dir)
        return self._profiler

    # ------------------------------------------------------------------ #
    # checkpointing

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        asynchronous=None):
        """Two-phase crash-safe save. ``asynchronous`` overrides the config's
        ``checkpoint.async_save``: True snapshots device state to host and
        returns while the background writer persists/commits; False blocks
        until the tag is durably on disk."""
        from deepspeed_tpu.runtime.checkpoint_engine.engine import save_engine_checkpoint
        return save_engine_checkpoint(self, save_dir, tag=tag, client_state=client_state,
                                      save_latest=save_latest, asynchronous=asynchronous)

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False, strict=False, load_data_progress=False):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import load_engine_checkpoint
        result = load_engine_checkpoint(self, load_dir, tag=tag,
                                        load_optimizer_states=load_optimizer_states,
                                        load_module_only=load_module_only,
                                        strict=strict,
                                        load_data_progress=load_data_progress)
        # resync the host-side curriculum counter with the restored step
        self._host_global_steps = int(self.global_steps)
        return result

    def auto_resume(self, save_dir, tag=None, strict=False):
        """Verified auto-resume: restore params/optimizer/loss-scaler/RNG/
        counters from the newest INTACT checkpoint under ``save_dir``
        (walking back past corrupt/partial tags) and fast-forward the data
        pipeline to the recorded progress, so the resumed loss curve is
        step-identical to an uninterrupted run. Returns ``(path,
        client_state)``; ``(None, {})`` when nothing is there to resume
        (fresh start) unless ``strict``."""
        return self.load_checkpoint(save_dir, tag=tag, strict=strict,
                                    load_data_progress=True)

    def flush_checkpoints(self, timeout=None):
        """Block until every queued async checkpoint is durably committed.
        Raises the writer's error if a queued save failed."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.drain(timeout=timeout, raise_on_error=True)

    def emergency_save(self, save_dir):
        """Preemption-grace save: drain in-flight async saves (best effort,
        bounded — preemption grace windows are short and the synchronous
        save below captures newer state anyway), then take one synchronous
        verified save of the current state."""
        if self._ckpt_writer is not None:
            try:
                self._ckpt_writer.drain(timeout=30)
            except Exception as e:
                logger.warning(f"emergency save: drain failed ({e}); "
                               f"taking the synchronous save anyway")
        result = self.save_checkpoint(save_dir, asynchronous=False)
        # ship the flight-recorder tail next to the emergency tag: the
        # post-mortem gets the event timeline leading into the signal
        # (no-op when the recorder is off; never fails the save)
        from deepspeed_tpu.monitor.events import dump_events_jsonl
        dump_events_jsonl(save_dir)
        return result

    def enable_preemption_handler(self, save_dir, signals=None,
                                  exit_on_signal=True):
        """Install the SIGTERM/SIGINT grace handler: on signal, drain the
        checkpoint writer, emergency-save to ``save_dir``, exit
        ``128+signum`` (TPU preemption / maintenance SIGTERMs become clean
        resumable exits)."""
        from deepspeed_tpu.runtime.checkpoint_engine.safe_engine import PreemptionHandler
        if self._preemption is not None:
            self._preemption.uninstall()
        kwargs = {} if signals is None else {"signals": tuple(signals)}
        self._preemption = PreemptionHandler(
            self, save_dir, exit_on_signal=exit_on_signal, **kwargs).install()
        return self._preemption

    def disable_preemption_handler(self):
        if self._preemption is not None:
            self._preemption.uninstall()
            self._preemption = None

    def _checkpoint_writer(self):
        """Lazy per-engine async writer; failures feed checkpoint metrics
        and the health observatory's ckpt_failure detector."""
        if self._ckpt_writer is None:
            from deepspeed_tpu.runtime.checkpoint_engine.engine import (
                _checkpoint_cfg, _notify_ckpt_result)
            from deepspeed_tpu.runtime.checkpoint_engine.safe_engine import AsyncCheckpointWriter
            ccfg = _checkpoint_cfg(self)
            self._ckpt_writer = AsyncCheckpointWriter(
                max_pending=ccfg.max_pending,
                retries=ccfg.retries,
                retry_backoff_s=ccfg.retry_backoff_s,
                keep_last=ccfg.keep_last,
                on_result=lambda ok, steps: _notify_ckpt_result(self, ok, steps))
        return self._ckpt_writer

    def _fast_forward_data(self, iterations):
        """Advance the data pipeline past ``iterations`` already-consumed
        train_batch calls (``iterations * gas`` micro-batches) so resume
        neither replays nor skips batches. Works on the engine's standing
        ``set_dataiterator`` iterator (advanced in place — re-create it
        fresh before auto_resume) or on ``training_dataloader`` (epoch
        seed + in-epoch position recomputed, then a standing iterator that
        rolls over epochs is installed)."""
        micro = int(iterations) * self.gradient_accumulation_steps()
        if micro <= 0:
            return
        it = getattr(self, "_data_iterator", None)
        # a loader-derived standing iterator (set_dataloader / train_batch's
        # auto-install) is NOT advanced in place: it is a plain single-epoch
        # iter that StopIterations past the first epoch and knows nothing of
        # shuffle-seed replay — the loader path below re-creates it at the
        # right position instead
        if it is not None and (getattr(self, "_data_iter_external", False)
                               or self.training_dataloader is None):
            for _ in range(micro):
                next(it)
            log_dist(f"auto_resume: fast-forwarded data iterator by "
                     f"{micro} micro-batches", ranks=[0])
            return
        if self.training_dataloader is None:
            logger.warning(
                f"auto_resume: {micro} micro-batches of recorded progress "
                f"but no engine-owned data pipeline to fast-forward; pass a "
                f"freshly-created iterator via set_dataiterator BEFORE "
                f"auto_resume, or expect replayed batches")
            return
        from deepspeed_tpu.runtime.dataloader import resume_loader_iterator
        self._data_iterator = resume_loader_iterator(
            self.training_dataloader, micro)
        self._data_iter_external = False
        log_dist(f"auto_resume: dataloader fast-forwarded by {micro} "
                 f"micro-batches", ranks=[0])
