"""Inference engine.

Reference parity: ``deepspeed/inference/engine.py:35`` — ``InferenceEngine``
wraps a model for serving: dtype conversion, tensor-parallel sharding of the
weights, checkpoint loading, and a ``generate`` loop. The reference's three
injection modes (user policy / kernel injection / AutoTP,
``inference/engine.py:120-144``) map here to:

- models from ``deepspeed_tpu.models``: TP sharding comes from the model's
  own ``tp_specs()`` (policy equivalent);
- arbitrary param pytrees: ``AutoShard`` heuristics
  (``deepspeed_tpu.inference.auto_tp``) pick specs by name/shape, the AutoTP
  analogue;
- kernel injection = swapping the attention op for the Pallas decode kernel
  with KV cache (``deepspeed_tpu.ops``), enabled when available.

CUDA-graph capture/replay (reference ``:435-463``) is subsumed by ``jit``:
the decode step is one compiled program with a donated KV cache.
"""

from __future__ import annotations

import os
import time
from contextlib import nullcontext
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference import blockgen
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.monitor.trace import LoopTime, span
from deepspeed_tpu.utils.fault_injection import step_fault as _step_fault
from deepspeed_tpu.utils.logging import log_dist, logger, warn_once


class InferenceEngine:

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None, params=None):
        self.module = model
        self._config = config or DeepSpeedInferenceConfig()
        # int8 = weight-only quantisation (reference GroupQuantizer,
        # module_inject/replace_module.py:135): activations run bf16, weight
        # matrices are stored int8 + per-group scales (see ops/quant.py)
        dt = str(getattr(self._config.dtype, "value", self._config.dtype))
        self._weight_quant = dt == "int8"
        # use_enum_values stores the plain string — map it explicitly (a
        # hasattr(.jnp) probe silently turned every requested dtype into bf16)
        self.dtype = {"fp32": jnp.float32, "fp16": jnp.float16,
                      "bf16": jnp.bfloat16, "int8": jnp.bfloat16}[dt]

        tp_size = self._config.tensor_parallel.tp_size
        # serving.tp: the paged serving engine's tensor-parallel degree —
        # one knob that implies the whole sharded-serving layout (params
        # via tp_specs/auto_tp, KV pools head-sharded, shard_map'd paged
        # kernel). 0 follows tensor_parallel.tp_size; both set and
        # disagreeing is a config contradiction, not a tie to break
        srv_tp = int(getattr(self._config.serving, "tp", 0) or 0)
        if srv_tp > 0:
            if tp_size > 1 and srv_tp != tp_size:
                raise ValueError(
                    f"serving.tp={srv_tp} conflicts with "
                    f"tensor_parallel.tp_size={tp_size}; set one (serving.tp"
                    " alone is enough for the serving engine)")
            tp_size = srv_tp
        # MoE serving (reference inference/engine.py:209-216 _create_ep_parallel_group):
        # the ep axis shards the expert dimension at serve time; gating and
        # attention replicate over it
        moe_cfg = self._config.moe
        if isinstance(moe_cfg, bool):
            moe_enabled, ep_size = moe_cfg, max(1, int(self._config.ep_size))
            moe_type = str(getattr(self._config.moe_type, "value", self._config.moe_type))
        else:
            moe_enabled = moe_cfg.enabled
            ep_size = max(int(moe_cfg.ep_size), int(self._config.ep_size), 1)
            moe_type = str(getattr(moe_cfg.type, "value", moe_cfg.type))
        self._ep_size = ep_size if moe_enabled else 1
        if moe_type not in ("standard", "residual"):
            raise NotImplementedError(
                f"MoE inference type {moe_type!r} is not implemented; "
                "'standard' and 'residual' (PR-MoE) are supported")
        self._moe_type = moe_type
        axes = {}
        if self._ep_size > 1:
            axes["ep"] = self._ep_size
        if tp_size > 1:
            axes["tp"] = tp_size
        axes["dp"] = -1
        if not dist.has_mesh():
            dist.init_mesh(axes)
            self.mesh = dist.get_mesh()
        else:
            mesh = dist.get_mesh()
            need = {a: s for a, s in axes.items() if a != "dp"}
            if all(mesh.shape.get(a, 1) == s for a, s in need.items()):
                self.mesh = mesh
            else:
                # the live mesh (a training run's, or another engine's)
                # does not carry this engine's tp/ep axes: silently
                # adopting it would serve UNSHARDED despite the explicit
                # config (every spec would sanitize to replicated). Build
                # a private mesh instead — the global one is left alone
                # (a training engine may own it) and ``_mesh_scope`` pins
                # ours around every forward/serve trace.
                from deepspeed_tpu.comm.mesh import build_mesh
                self.mesh = build_mesh(axes)
                log_dist(
                    f"InferenceEngine: existing mesh "
                    f"{dict(mesh.shape)} lacks the configured axes "
                    f"{need}; serving on a private mesh "
                    f"{dict(self.mesh.shape)}", ranks=[0])

        # checkpoint loading (reference inference/engine.py:354-419
        # _load_checkpoint): an HF checkpoint dir/file (or a model given as a
        # path string) loads through the per-architecture policies
        ckpt = self._config.checkpoint
        if isinstance(model, str) and ckpt is None:
            ckpt, model = model, None
        if params is None and isinstance(ckpt, str) and not ckpt.endswith(".json"):
            from deepspeed_tpu.module_inject import load_hf_checkpoint
            loaded_model, params = load_hf_checkpoint(ckpt)
            if model is None:
                model = loaded_model
            self.module = model = model if not isinstance(model, str) else loaded_model
            n_params = sum(int(np.prod(a.shape))
                           for a in jax.tree.leaves(params))
            log_dist(f"InferenceEngine: loaded HF checkpoint {ckpt} "
                     f"({n_params / 1e6:.1f}M params)", ranks=[0])
        elif params is None and isinstance(ckpt, (dict,)) or \
                (params is None and isinstance(ckpt, str) and ckpt.endswith(".json")):
            # ds_inference meta json (reference engine.py:354-419 sharded
            # "tp/pp" checkpoints): per-TP-rank Megatron files merged by the
            # SD loader, then mapped to the zoo layout for model.config
            from deepspeed_tpu.module_inject.megatron import load_megatron_checkpoint
            if model is None or not hasattr(model, "config"):
                raise ValueError("Megatron meta-json checkpoints need the model "
                                 "(with .config) passed to init_inference")
            params = load_megatron_checkpoint(ckpt, model.config)
            log_dist("InferenceEngine: loaded Megatron ds_inference checkpoint "
                     f"({len(jax.tree.leaves(params))} tensors)", ranks=[0])

        if params is None and hasattr(model, "init_params"):
            params = model.init_params(jax.random.key(0))
        if params is None:
            raise ValueError("InferenceEngine needs params (or a model with init_params, "
                             "or config.checkpoint pointing at an HF checkpoint)")

        # MoE models (zoo MoECausalLM shape: .moe config + aux-loss forward):
        # wire the serve mesh into the model so dispatch_combine constrains
        # the dispatched tensor to the ep axis (all-to-all over ICI), and
        # drop the aux loss from the served logits
        self._is_moe = hasattr(model, "moe") and hasattr(model, "_moe_mlp")
        if self._ep_size > 1 and not self._is_moe:
            raise ValueError(
                f"config.moe.ep_size={self._ep_size} but the model has no MoE "
                "layers; remove the moe section or serve an MoE model")
        if self._is_moe:
            n_experts = int(getattr(model.moe, "num_experts", 0))
            if self._ep_size > 1 and n_experts % self._ep_size:
                raise ValueError(
                    f"moe.ep_size={self._ep_size} must divide the model's "
                    f"num_experts={n_experts}")
            # the config's moe type and the model's architecture must agree:
            # serving a PR-MoE with standard routing (or vice versa) would be
            # silently wrong (reference moe_inference moe_type dispatch)
            model_residual = bool(getattr(model.moe, "use_residual", False))
            if model_residual != (self._moe_type == "residual"):
                raise ValueError(
                    f"config moe.type={self._moe_type!r} but the model "
                    f"{'IS' if model_residual else 'is NOT'} a residual "
                    "(PR-)MoE; set moe.type accordingly")
            # serve on a shallow copy bound to the serve mesh — mutating the
            # caller's model would clobber a training mesh (or an earlier
            # engine's) and put stale sharding constraints inside their jit
            import copy
            self.module = model = copy.copy(model)
            model.mesh = self.mesh

        tp_specs = None
        if hasattr(model, "tp_specs"):
            tp_specs = model.tp_specs() if callable(model.tp_specs) else model.tp_specs
        elif tp_size > 1:
            from deepspeed_tpu.inference.auto_tp import auto_tp_specs
            tp_specs = auto_tp_specs(params, tp=tp_size)
        if tp_specs is not None and tp_size > 1:
            # one divisibility gate for EVERY param layout (model-provided
            # and auto): a dim tp does not divide replicates with a warning
            # instead of relying on each placement path's silent drop
            from deepspeed_tpu.inference.auto_tp import validate_tp_specs
            tp_specs = validate_tp_specs(params, tp_specs, self.mesh)

        if self._weight_quant:
            from deepspeed_tpu.ops.quant import quantize_params, tree_nbytes
            groups = max(1, int(self._config.quant.weight.q_groups))
            dense_bytes = sum(a.size * 2 for a in jax.tree.leaves(params))
            params = quantize_params(params, groups=groups,
                                     include_embed=not getattr(getattr(model, "config", None),
                                                               "tie_embeddings", True))
            log_dist(f"int8 weight-only quantisation: q_groups={groups}, "
                     f"{dense_bytes / 2**20:.0f} MiB (bf16) -> "
                     f"{tree_nbytes(params) / 2**20:.0f} MiB at rest", ranks=[0])

        # ZeRO-Inference: layer weights stay in HOST memory and stream to the
        # device one layer at a time during forward/decode (reference
        # zero.stage3 + offload_param powering ZeRO-Inference; the BLOOM-176B
        # serving recipe). Device residency = one layer + activations + KV.
        off = dict(self._config.zero or {}).get("offload_param", {})
        off_dev = str(off.get("device", "none")).lower()
        # nvme: layer weights live on fast local storage and stream through
        # the native aio engine (reference partitioned_param_swapper.py:35
        # powering NVMe ZeRO-Inference); cpu: host RAM
        self._stream_weights = off_dev in ("cpu", "nvme")
        self._stream_nvme = off_dev == "nvme"
        if self._stream_nvme and not off.get("nvme_path"):
            raise ValueError("offload_param device='nvme' requires nvme_path")
        if self._stream_weights and not (hasattr(model, "config")
                                         and "layers" in params):
            raise ValueError("weight streaming needs a zoo-layout model "
                             "(.config + params['layers'] stacked per layer)")
        if self._stream_weights and getattr(model.config, "norm_position", "pre") == "post":
            # the streamed path is built from the pre-LN cached_* blocks
            raise ValueError("weight streaming supports pre-LN models only "
                             "(norm_position='post' has no cached path)")
        if self._stream_weights and (hasattr(model, "moe") or self._ep_size > 1):
            raise NotImplementedError(
                "ZeRO-Inference weight streaming of MoE models is not "
                "implemented (the streamed block is the dense cached path)")

        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.tree_util import GetAttrKey, tree_map_with_path

        def _is_qscale(path):
            # Quantized8.scale leaves (reached via a dataclass attr, unlike
            # dict-keyed layernorm "scale") stay f32
            return any(isinstance(k, GetAttrKey) and k.name == "scale" for k in path)

        if self._stream_weights:
            import numpy as _np
            import ml_dtypes
            np_dtype = {jnp.bfloat16: ml_dtypes.bfloat16,
                        jnp.float16: _np.float16,
                        jnp.float32: _np.float32}[self.dtype]

            def host_leaf(path, a):
                a = _np.asarray(a)
                if not _is_qscale(path) and _np.issubdtype(a.dtype, _np.floating):
                    a = a.astype(np_dtype)
                return a

            L = model.config.n_layer
            host_stack = tree_map_with_path(host_leaf, params["layers"])
            self._host_layers = [jax.tree.map(lambda a: a[i], host_stack)
                                 for i in range(L)]
            params = {k: v for k, v in params.items() if k != "layers"}
            host_bytes = sum(a.nbytes for lp in self._host_layers
                             for a in jax.tree.leaves(lp))
            self._n_stream_layers = L
            self._swapper = None
            # streaming x TP: the per-layer H2D copy lands SHARDED (each chip
            # receives its slice of the layer; XLA partitions the block step
            # and inserts the TP collectives). Non-layer params (embed/head)
            # stay replicated — they are small next to the layer stack.
            self._layer_put_shardings = None
            if tp_size > 1 and tp_specs is not None and "layers" in tp_specs:
                from deepspeed_tpu.ops.quant import (align_quant_groups,
                                                     quantized_shardings)
                drop_lead = lambda s: P(*list(s)[1:])  # unstack the layer dim
                per_layer = jax.tree.map(drop_lead, tp_specs["layers"],
                                         is_leaf=lambda x: isinstance(x, P))
                # regroup int8 scales (lossless subdivision) so the quant
                # axis stays sharded even when q_groups % tp != 0
                self._host_layers = [align_quant_groups(lp, per_layer, self.mesh)
                                     for lp in self._host_layers]
                self._layer_put_shardings = quantized_shardings(
                    self._host_layers[0], per_layer, self.mesh)
            elif tp_size > 1:
                logger.warning(
                    "weight streaming with tp_size>1 but no per-layer TP "
                    "specs: layers stream REPLICATED (no memory split or "
                    "speedup from the tp axis)")
            if self._stream_nvme:
                # leaves ride as raw bytes (dtype restored from in-memory
                # metadata — bf16 has no stable numpy dtype_str round-trip).
                # A unique per-engine subdir: engines sharing an nvme_path
                # must not overwrite each other's same-keyed swap files.
                import tempfile

                from deepspeed_tpu.runtime.swap_tensor.async_swapper import \
                    AsyncTensorSwapper
                os.makedirs(str(off.get("nvme_path")), exist_ok=True)
                self._sweep_stale_swap_dirs(str(off.get("nvme_path")))
                swap_dir = tempfile.mkdtemp(dir=str(off.get("nvme_path")),
                                            prefix="zero_inference_")
                # ownership marker: lets a future engine init reclaim this
                # model-sized footprint if we die without running finalizers
                with open(os.path.join(swap_dir, "owner.pid"), "w") as f:
                    f.write(self._owner_marker())
                self._swapper = AsyncTensorSwapper(swap_dir)
                # swap files are engine-lifetime caches of a model-sized
                # footprint: reclaim them on engine GC / interpreter exit
                import shutil
                import weakref
                self._swap_cleanup = weakref.finalize(
                    self, shutil.rmtree, swap_dir, True)
                self._layer_meta = []
                for i, lp in enumerate(self._host_layers):
                    leaves, treedef = jax.tree.flatten(lp)
                    metas = []
                    for j, a in enumerate(leaves):
                        a = _np.ascontiguousarray(a)
                        key = f"L{i}_{j}"
                        self._swapper.swap_out(key, a.view(_np.uint8).ravel(),
                                               async_op=True)
                        metas.append((key, a.shape, a.dtype))
                    # per-layer barrier: bounds staged aligned buffers to one
                    # layer (async across the whole model would transiently
                    # double the model's host footprint)
                    self._swapper.wait()
                    self._host_layers[i] = None  # free as we go
                    self._layer_meta.append((treedef, metas))
                self._host_layers = None  # host copy dropped; NVMe holds it
            where = (f"on NVMe at {off.get('nvme_path')}" if self._stream_nvme
                     else "resident on host")
            log_dist(f"ZeRO-Inference streaming: {L} layers "
                     f"({host_bytes / 2**20:.0f} MiB) {where}; device "
                     "holds two layers at a time (double-buffered)", ranks=[0])

        # quantized param trees (int8 config or quantize-on-load) carry
        # Quantized8 nodes: their payload+scale shardings are derived
        # together so group boundaries align with TP shard boundaries
        # (reference GroupQuantizer x TP slicing, replace_module.py:42-135)
        from deepspeed_tpu.ops.quant import (Quantized8, align_quant_groups,
                                             quantized_shardings)
        has_quant_nodes = any(isinstance(l, Quantized8) for l in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, Quantized8)))
        if tp_specs is not None and not self._stream_weights \
                and (self._weight_quant or has_quant_nodes):
            params = align_quant_groups(params, tp_specs, self.mesh)
            shardings = quantized_shardings(params, tp_specs, self.mesh)
        elif tp_specs is not None and not self._stream_weights:
            from deepspeed_tpu.runtime.zero.partition import ZeroShardingRules
            rules = ZeroShardingRules(self.mesh)  # stage 0: replicate except TP dims
            shardings = rules.param_shardings(params, tp_specs)
        else:
            shardings = jax.tree.map(lambda _: NamedSharding(self.mesh, P()), params)

        def put(path, a, s):
            a = jnp.asarray(a)
            # int8 payloads stay int8
            if _is_qscale(path) or not jnp.issubdtype(a.dtype, jnp.floating):
                return jax.device_put(a, s)
            return jax.device_put(a.astype(self.dtype), s)

        self.params = tree_map_with_path(put, params, shardings)

        self._fwd_jit = None
        self._prefill_jit = None
        self._decode_jit = None
        self._stream_jits = None
        self._paged_jits = None
        self._paged_alloc = None   # persistent prefix-cache allocator
        self._kv_host_pool = None  # persistent host-RAM KV tier (tiered
        # KV cache: cold prefix-cache blocks demote here instead of being
        # destroyed; content-addressed, so it outlives pool workspaces and
        # cache-off serves — only a geometry/dtype change rebuilds it)

        # ---- telemetry (serving stats + compile watchdog) ----
        tcfg = getattr(self._config, "telemetry", None)
        self._telemetry = tcfg if tcfg is not None and tcfg.enabled else None
        self._serving_tel = None
        # flight recorder: None when off, so every hot-path emit site in
        # generate_batch (and the scheduler it constructs) gates at one
        # None check and allocates nothing
        self._events = None
        self._serve_rid_base = 0   # rids unique across generate_batch calls
        self._active_session = None  # at most ONE paged serving session
        # owns the pools/jits at a time (generate_batch drain or an
        # AsyncServingEngine loop)
        if self._telemetry is not None:
            from deepspeed_tpu.inference.scheduler import ServingTelemetry
            from deepspeed_tpu.monitor.metrics import get_registry
            from deepspeed_tpu.monitor.trace import get_compile_watchdog
            reg = get_registry()
            reg.set_enabled(True)
            self._tel_reg = reg
            self._tel_watchdog = get_compile_watchdog()
            self._tel_watchdog.storm_threshold = tcfg.compile_storm_threshold
            self._serving_tel = ServingTelemetry(reg)
            if tcfg.events.enabled:
                from deepspeed_tpu.monitor.events import (TaggedRecorder,
                                                          get_flight_recorder)
                # every replica shares the ONE global ring; the per-engine
                # wrapper stamps replica= so the fleet renderer can group
                self._events = TaggedRecorder(get_flight_recorder().enable(
                    capacity=tcfg.events.capacity))

        log_dist(f"InferenceEngine ready: dtype={self.dtype.__name__}, tp={tp_size}, "
                 f"mesh={dict(self.mesh.shape)}"
                 + (", weight-streaming" if self._stream_weights else ""), ranks=[0])

    # ------------------------------------------------------------------ #

    def _watched(self, fn, name: str):
        """Route a compiled entry point through the compile watchdog when
        telemetry is on."""
        if self._telemetry is None:
            return fn
        return self._tel_watchdog.watch(fn, name)

    def telemetry_snapshot(self) -> Dict:
        """Whole-process registry snapshot plus the compile watchdog's
        summary. Empty dict when telemetry is off."""
        if self._telemetry is None:
            return {}
        from deepspeed_tpu.monitor.health import sample_memory_gauges
        sample_memory_gauges(self._tel_reg)
        snap = self._tel_reg.snapshot()
        snap["compile"] = self._tel_watchdog.summary()
        return snap

    def export_serving_trace(self, path: str) -> str:
        """Render the flight recorder's serving events as chrome-trace
        JSON (open in Perfetto / chrome://tracing): one track per request
        — its admission→retire span with prefill-chunk / decode-tick /
        COW child slices and preemption instants — plus queue-depth and
        KV-block counter tracks, so a whole ``generate_batch`` (or
        several: rids are unique across calls) is replayable. Requires
        ``telemetry.events`` on; validate the output with
        ``dscli trace --validate <path>``."""
        if self._events is None:
            raise ValueError(
                "serving trace export needs the flight recorder: set "
                "telemetry.events (e.g. telemetry={'events': True}) on "
                "init_inference")
        from deepspeed_tpu.monitor.events import export_serving_trace
        return export_serving_trace(self._events.snapshot(), path)

    def set_replica(self, name: str) -> None:
        """Name this engine's replica for observability: the tag lands on
        every flight-recorder event it emits (the fleet trace's track
        grouping) and on its ``serving/phase_ms`` / ``wasted_tokens``
        label sets. The router calls this at construction; a standalone
        engine stays ``r0``."""
        name = str(name)
        if self._events is not None:
            self._events.replica = name
        if self._serving_tel is not None:
            self._serving_tel.replica = name

    # ------------------------------------------------------------------ #

    def profile_model_time(self, use_cuda_events: bool = True) -> None:
        """Start recording per-forward model latency (reference
        profile_model_time; ``use_cuda_events`` accepted for parity — the
        timing here is a device-synchronized wall clock). Calling it again
        while already enabled is a no-op (a second enable must not silently
        drop the latencies recorded since the first)."""
        if getattr(self, "_model_profile_enabled", False):
            logger.warning("profile_model_time() called twice; model-time "
                           "profiling is already enabled — keeping the "
                           "recorded latencies (read them with model_times())")
            return
        self._model_profile_enabled = True
        self._model_times = []

    def model_times(self):
        """Drain the recorded per-forward latencies in seconds (reference
        model_times: asserts profiling was enabled first)."""
        if not getattr(self, "_model_profile_enabled", False):
            raise RuntimeError(
                "model profiling is not enabled; call profile_model_time() "
                "before forward")
        times = self._model_times
        self._model_times = []
        return times

    def forward(self, input_ids, attention_mask=None):
        """Full-sequence forward → logits."""
        if getattr(self, "_model_profile_enabled", False):
            import time as _t
            t0 = _t.perf_counter()
            out = self._forward_impl(input_ids, attention_mask)
            jax.block_until_ready(out)
            self._model_times.append(_t.perf_counter() - t0)
            return out
        return self._forward_impl(input_ids, attention_mask)

    def _forward_impl(self, input_ids, attention_mask=None):
        with self._mesh_scope():
            return self._forward_on_mesh(input_ids, attention_mask)

    def _forward_on_mesh(self, input_ids, attention_mask=None):
        input_ids = jnp.asarray(input_ids, jnp.int32)
        if self._stream_weights:
            if input_ids.ndim == 1:
                input_ids = input_ids[None, :]
            pad_bias = None
            if attention_mask is not None:
                # [B, S] 1=keep mask → additive key-side bias over the cache
                # slots (the streamed blocks' pad_bias contract); the single
                # mask→bias producer shared by every attention path
                from deepspeed_tpu.models.transformer import key_mask_bias
                mask = jnp.asarray(attention_mask)
                if mask.ndim == 1:
                    mask = mask[None, :]
                pad_bias = key_mask_bias(mask)
            caches = self._stream_caches(input_ids.shape[0], input_ids.shape[1])
            logits, _ = self._streamed_step(input_ids, caches, jnp.int32(0),
                                            pad_bias=pad_bias)
            return logits
        if self._fwd_jit is None:
            fwd = self.module.forward if hasattr(self.module, "forward") else self.module
            if self._is_moe:
                # eval routing (eval_capacity_factor, no jitter/RTS) and the
                # aux loss dropped — serving returns logits only (reference
                # DeepSpeedMoEInference forward, moe_inference.py:300-364)
                self._fwd_jit = jax.jit(lambda p, t, m: fwd(p, t, m, train=False)[0])
            else:
                self._fwd_jit = jax.jit(lambda p, t, m: fwd(p, t, m))
            self._fwd_jit = self._watched(self._fwd_jit, "inference.forward")
        return self._fwd_jit(self.params, input_ids, attention_mask)

    # ------------------------------------------------------------------ #
    # ZeRO-Inference weight streaming: one layer on device at a time

    @staticmethod
    def _owner_marker() -> str:
        """``hostname:boot_id:pid_ns:pid`` — a pid is only meaningful inside
        its own host + boot + pid namespace (two containers can share a
        mount, a hostname, AND a boot id), so the liveness probe below
        refuses to judge markers from any other scope."""
        try:
            boot = open("/proc/sys/kernel/random/boot_id").read().strip()
        except OSError:  # non-Linux: no boot id, host scoping still applies
            boot = "-"
        try:
            pidns = os.readlink("/proc/self/ns/pid")  # e.g. pid:[4026531836]
        except OSError:
            pidns = "-"
        import socket
        return f"{socket.gethostname()}:{boot}:{pidns}:{os.getpid()}"

    @classmethod
    def _sweep_stale_swap_dirs(cls, nvme_path: str) -> None:
        """Reclaim zero_inference_* dirs whose owning process is gone. The
        weakref finalizer cleans up on normal exit, but a SIGKILLed process
        leaks a model-sized footprint; each dir carries an ``owner.pid``
        marker so the next engine init under the same nvme_path can sweep.
        Dirs owned by another host/boot/pid-namespace scope are never
        touched — os.kill(pid, 0) can't see across pid namespaces, so 'not
        found' outside our exact scope proves nothing."""
        import shutil
        me_scope, _ = cls._owner_marker().rsplit(":", 1)
        for name in os.listdir(nvme_path):
            d = os.path.join(nvme_path, name)
            if not (name.startswith("zero_inference_") and os.path.isdir(d)):
                continue
            try:
                marker = open(os.path.join(d, "owner.pid")).read().strip()
                scope, pid = marker.rsplit(":", 1)
                pid = int(pid)
            except (OSError, ValueError):
                continue  # pre-marker dir or mid-creation: leave it alone
            if scope != me_scope or pid == os.getpid():
                continue
            try:
                os.kill(pid, 0)  # signal 0: existence probe only
            except ProcessLookupError:
                logger.warning(f"sweeping stale ZeRO-Inference swap dir {d} "
                               f"(owner pid {pid} is dead)")
                shutil.rmtree(d, ignore_errors=True)
            except OSError:
                pass  # pid alive but not ours (EPERM): leave it alone

    def _put_layer(self, lp):
        """H2D copy of one layer's weights — TP-sharded when serving tp>1
        (each chip receives its slice), replicated otherwise."""
        if self._layer_put_shardings is None:
            return jax.device_put(lp)
        return jax.device_put(lp, self._layer_put_shardings)

    def _fetch_submit(self, i: int):
        """Kick off layer i's NVMe reads on the aio thread pool and return a
        handle; the data is NOT ready until :meth:`_fetch_finish`. RAM mode
        has nothing to overlap, so the handle is just the index."""
        if self._swapper is None:
            return i
        treedef, metas = self._layer_meta[i]
        # submit ALL of the layer's reads, then one barrier (in finish) —
        # per-leaf blocking swap_in would serialize the aio thread pool
        bufs = [self._swapper.swap_in(key, async_op=True)
                for key, _, _ in metas]
        return (treedef, metas, bufs)

    def _fetch_finish(self, handle):
        """Barrier the reads submitted by :meth:`_fetch_submit` and build the
        layer's weight tree. The swapper's wait() is global, so the caller
        must finish one submit before issuing the next."""
        if self._swapper is None:
            return self._host_layers[handle]
        treedef, metas, bufs = handle
        self._swapper.wait()
        leaves = []
        for buf, (key, shape, dtype) in zip(bufs, metas):
            nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            leaves.append(buf[:nbytes].copy().view(dtype).reshape(shape))
            self._swapper.release_buffer(buf)
        return jax.tree.unflatten(treedef, leaves)

    def _stream_caches(self, B: int, Smax: int):
        cfg = self.module.config
        shape = (B, Smax, cfg.kv_heads, cfg.head_dim)
        return [{"k": jnp.zeros(shape, self.dtype), "v": jnp.zeros(shape, self.dtype)}
                for _ in range(cfg.n_layer)]

    def _streamed_step(self, tokens, caches, pos, pad_bias=None):
        """tokens [B, T] against per-layer caches at offset pos: embed on
        device, then per layer H2D-copy the layer weights and run one jitted
        block (same compiled program for every layer — shapes match), then
        the head. The reference analogue is stage3 param fetch/release per
        module during inference forward."""
        from deepspeed_tpu.models import transformer as T
        cfg = self.module.config
        if self._stream_jits is None:
            emb = jax.jit(lambda p, t, pos: T.cached_embed(cfg, p, t, pos, self.dtype))
            blk = jax.jit(
                lambda h, lp, ck, cv, positions, pos, pb:
                T.cached_block(cfg, h, lp, ck, cv, positions, pos, pb),
                donate_argnums=(2, 3))
            head = jax.jit(lambda p, x: T.cached_head(cfg, p, x))
            self._stream_jits = (emb, blk, head)
        emb, blk, head = self._stream_jits
        x, positions = emb(self.params, tokens, pos)
        # double-buffered layer pipeline (reference analogue:
        # pipelined_optimizer_swapper.py's read-ahead): while blk(i) runs on
        # device, layer i+1's H2D copy is in flight (device_put is async) and
        # layer i+2's NVMe reads ride the aio thread pool — I/O, H2D and
        # compute all overlap at the cost of two layers resident on device.
        n = self._n_stream_layers
        pending = self._fetch_submit(0)
        host0 = self._fetch_finish(pending)
        pending = self._fetch_submit(1) if n > 1 else None
        nxt = self._put_layer(host0)
        for i in range(n):
            lp, nxt = nxt, None
            if i + 1 < n:
                # finish i+1's NVMe reads (hidden behind blk(i-1)), queue
                # i+2's, and start i+1's H2D — all before dispatching blk(i)
                host = self._fetch_finish(pending)
                pending = self._fetch_submit(i + 2) if i + 2 < n else None
                nxt = self._put_layer(host)
            x, nk, nv = blk(x, lp, caches[i]["k"], caches[i]["v"],
                            positions, pos, pad_bias)
            caches[i] = {"k": nk, "v": nv}
        return head(self.params, x), caches

    def _generate_streamed(self, input_ids, max_new, temperature, top_k, rng,
                           eos_token_id):
        B, prompt_len = input_ids.shape
        cfg = self.module.config
        Smax = self._bucket(prompt_len + max_new, cfg.max_seq)
        bucket = self._bucket(prompt_len, Smax)
        caches = self._stream_caches(B, Smax)

        if max_new <= 0:
            return input_ids
        pad = bucket - prompt_len
        toks = jnp.pad(input_ids, ((0, 0), (0, pad))) if pad else input_ids
        logits, caches = self._streamed_step(toks, caches, jnp.int32(0))
        rng, sub = jax.random.split(rng)
        nxt = self._sample_host(logits[:, prompt_len - 1].astype(jnp.float32),
                                temperature, top_k, sub)
        eos = eos_token_id
        done = (nxt == eos) if eos is not None else None
        generated = [np.asarray(nxt, np.int32)]
        for step in range(1, max_new):
            if eos is not None and bool(done.all()):
                break
            pos = prompt_len + step - 1
            logits, caches = self._streamed_step(
                nxt[:, None].astype(jnp.int32), caches, jnp.int32(pos))
            rng, sub = jax.random.split(rng)
            nxt = self._sample_host(logits[:, -1].astype(jnp.float32),
                                    temperature, top_k, sub)
            if eos is not None:
                # rows already done keep emitting eos (stable batched output,
                # same invariant as the compiled decode loop)
                nxt = jnp.where(done, eos, nxt)
                done = done | (nxt == eos)
            generated.append(np.asarray(nxt, np.int32))
        gen = jnp.asarray(np.stack(generated, axis=1), jnp.int32)
        return jnp.concatenate([input_ids, gen], axis=1)

    __call__ = forward

    def _reject_encoders(self, what: str) -> None:
        """Encoders run autoregressively emit nonsense (bidirectional
        attention, or hidden states instead of vocab logits) — reject
        loudly (the reference's engine.generate delegates to
        module.generate, which encoder models don't have either)."""
        from deepspeed_tpu.models.bert import BertModel
        from deepspeed_tpu.models.clip import (CLIPTextEncoder,
                                               CLIPVisionEncoder,
                                               DSClipEncoder)
        zoo_cfg = getattr(self.module, "zoo_cfg",
                          getattr(self.module, "config", None))
        if (isinstance(self.module, (BertModel, CLIPTextEncoder,
                                     CLIPVisionEncoder, DSClipEncoder))
                or getattr(zoo_cfg, "causal", True) is False):
            raise ValueError(
                f"{type(self.module).__name__} is an encoder; {what} "
                "requires a causal LM — use engine.forward for hidden "
                "states / MLM logits")

    def generate(self, input_ids, max_new_tokens: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, eos_token_id: Optional[int] = None):
        """Autoregressive generation (greedy or sampled).

        This baseline path recomputes the full prefix per step (correct for
        every model in the zoo); the Pallas KV-cache decode path replaces it
        when kernel injection is enabled. ``max_out_tokens`` semantics follow
        the reference (inference/engine.py:523 token-length check).
        """
        with self._mesh_scope():
            return self._generate(input_ids, max_new_tokens, temperature,
                                  top_k, seed, eos_token_id)

    def _generate(self, input_ids, max_new_tokens, temperature, top_k, seed,
                  eos_token_id):
        input_ids = jnp.asarray(input_ids, jnp.int32)
        if input_ids.ndim == 1:
            input_ids = input_ids[None, :]
        self._reject_encoders("generate()")
        if getattr(getattr(self.module, "config", None), "generation",
                   None) is not None:
            raise ValueError(
                "generate() decodes a token a step; this model generates by "
                "diffusion over blocks (config.generation): use "
                "generate_batch() or the serving engine")
        max_new = max_new_tokens if max_new_tokens is not None else self._config.max_out_tokens
        max_len = input_ids.shape[1] + max_new
        cfg = getattr(self.module, "config", None)
        if cfg is not None and hasattr(cfg, "max_seq") and max_len > cfg.max_seq:
            raise ValueError(f"Input+generated length {max_len} exceeds model max_seq {cfg.max_seq}; "
                             f"reduce max_new_tokens (reference max_out_tokens check)")

        rng = jax.random.key(seed)
        if self._stream_weights:
            return self._generate_streamed(input_ids, max_new, temperature,
                                           top_k, rng, eos_token_id)
        if hasattr(self.module, "forward_cached") and hasattr(self.module, "init_cache"):
            return self._generate_cached(input_ids, max_new, temperature, top_k, rng, eos_token_id)

        # fallback for models without a cached forward: full-prefix recompute
        tokens = input_ids
        for _ in range(max_new):
            logits = self.forward(tokens)[:, -1, :].astype(jnp.float32)
            # split first, consume the child: sampling with `rng` and then
            # splitting the SAME consumed key correlates the next step's
            # stream with the draw already made (DS002; every other
            # generate path uses this split-then-sample order)
            rng, sub = jax.random.split(rng)
            nxt = self._sample_host(logits, temperature, top_k, sub)
            tokens = jnp.concatenate([tokens, nxt[:, None].astype(jnp.int32)], axis=1)
            if eos_token_id is not None and bool((nxt == eos_token_id).all()):
                break
        return tokens

    @staticmethod
    def _sample_host(logits, temperature, top_k, rng):
        if temperature > 0.0:
            return InferenceEngine._draw(logits, temperature, top_k, rng)
        return jnp.argmax(logits, axis=-1)

    @staticmethod
    def _draw(logits, temperature, top_k, rng):
        """One draw a row at ``temperature`` (positive; may be traced)
        from the ``top_k`` largest logits (0: all)."""
        logits = logits / temperature
        if top_k > 0:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return jax.random.categorical(rng, logits, axis=-1)

    # ------------------------------------------------------------------ #
    # KV-cache generation: prefill + fixed-shape decode, no per-token
    # recompilation (reference workspace/KV design: inference_context.h:49,
    # softmax_context pt_binding.cpp:1668-1793)

    def _mesh_scope(self):
        """Pin the framework mesh VIEW to THIS engine's mesh for the
        duration of a serve. The transformer-level kernel dispatch
        (``_flash_mesh`` / ``_bare_pallas_legal``) reads ``dist.get_mesh``
        at trace time, so two engines with different tp degrees serving
        from one process must not trace against each other's mesh. The pin
        is a THREAD-LOCAL override (``dist.mesh_override``), never a write
        to the process-global mesh: the always-on serving loop traces from
        its own thread, and toggling the global there would race a
        training engine (or another serving engine) tracing concurrently
        on another thread."""
        return dist.mesh_override(self.mesh)

    def _kv_head_sharding(self):
        """NamedSharding for the KV workspaces — the dense cache
        [L, B, S, KV, Hd] and the paged pools [L, blocks, bs, KV*Hd] both
        carry the KV heads at axis 3 (the pools merged with Hd, so ``tp``
        splits their rows into whole heads): head-sharded over ``tp``
        when the model's KV heads divide the axis (per-chip KV bytes drop
        to 1/tp; block tables stay replicated because per-shard block
        indices are identical), replicated with a rate-limited warning
        otherwise — serving stays correct, just without the memory split."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        tp = self.mesh.shape.get("tp", 1)
        if tp > 1 and self._latent_rows():
            raise ValueError(
                f"serving tp={tp} but the model keeps latent rows "
                "(cache_spec['latent']): a latent row has no head axis to "
                "split over tp, and no form shards the query heads over one "
                "replicated row yet")
        if tp > 1:
            kvh = getattr(getattr(self.module, "config", None),
                          "kv_heads", None)
            if kvh is not None and kvh % tp == 0:
                return NamedSharding(self.mesh, P(None, None, None, "tp"))
            warn_once(f"serving tp={tp} does not divide the model's "
                      f"kv_heads={kvh}: KV caches/pools replicate over the "
                      "tp axis (params still shard, but there is no KV "
                      "memory split)")
        return NamedSharding(self.mesh, P())

    def _kv_slice_sharding(self):
        """NamedSharding for ONE block's per-layer k/v slice
        ``[L, bs, KV*Hd]`` — the tiered KV cache's D2H/H2D unit. Under
        ``serving.tp`` the slice lands head-sharded exactly like the
        pools themselves (axis 2 here = axis 3 of the pool), so a
        spill gathers each shard's local heads and a fetch scatters them
        back without ever gathering the pool."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        pool_sh = self._kv_head_sharding()
        if any(s is not None for s in pool_sh.spec):
            return NamedSharding(self.mesh, P(None, None, "tp"))
        return NamedSharding(self.mesh, P())

    def _cache_spec(self) -> dict:
        """The model's cache spec, the one place what it keeps is declared
        ({} for a model without one)."""
        return getattr(getattr(self.module, "config", None), "cache_spec",
                       None) or {}

    def _state_slots(self) -> int:
        """Slots a model needs whose layers keep a recurrent or conv STATE
        a request beside the shared KV pool (one a running request and the
        dummy), 0 for a model without. A window layer takes none: its
        blocks are the allocator's second free list."""
        return int(self._config.serving.max_running) + 1 \
            if self._cache_spec().get("state") else 0

    def _window_blocks(self, num_blocks: int, block_size: int) -> int:
        """Blocks of the window layers' pool beside a full pool of
        ``num_blocks`` (``BlockAllocator.window_pool_blocks``), 0 for a
        model without window layers."""
        from deepspeed_tpu.inference.block_allocator import BlockAllocator
        if not self._cache_spec().get("window"):
            return 0
        return BlockAllocator.window_pool_blocks(
            num_blocks, self._config.serving.max_running,
            self.module.config.ring_blocks(block_size))

    def _keeps_beside_kv(self) -> bool:
        """Whether the model keeps something a request that no KV block
        holds (a state slot, a window layer's blocks): what a prefix hit,
        a verify window's rewind, the host tier and a head-sharded pool
        are refused beside."""
        spec = self._cache_spec()
        return bool(spec.get("state") or spec.get("window"))

    def _latent_rows(self) -> int:
        """The model's layers that keep latent rows (its cache spec)."""
        return int(self._cache_spec().get("latent", 0))

    def _slot_kept(self) -> str:
        """What the model keeps a request beside its KV blocks, for a
        refusal's message."""
        return "a recurrent state" if self._cache_spec().get("state") \
            else "a window layer's ring of blocks"

    def _kv_host_pool_for(self, num_blocks: int, block_size: int,
                          caching: bool):
        """The persistent host-RAM KV tier for the current serving
        geometry, or None when ``serving.kv_host`` is off (or prefix
        caching is — the tier is keyed by the cache's hash chains).
        Content addressing makes entries valid across serves and even
        fresh pool workspaces; only a geometry/dtype change rebuilds."""
        kh = getattr(self._config.serving, "kv_host", None)
        if kh is not None and kh.enabled and self._latent_rows():
            raise ValueError(
                "serving.kv_host is on but the model keeps latent rows "
                "(cache_spec['latent']): the host tier's block slice is "
                "[layers, block_size, kv_heads * head_dim] of a k and a v "
                "pool, and a latent block is neither")
        if kh is not None and kh.enabled and self._keeps_beside_kv():
            raise ValueError(
                f"serving.kv_host is on but the model keeps {self._slot_kept()} "
                "beside its KV: a block on the host says nothing of the "
                "state at its end or of what the ring held there (no state "
                "snapshot is built)")
        if kh is None or not kh.enabled or not caching:
            return None
        if str(kh.spill) not in ("auto", "off"):
            raise ValueError(
                f"serving.kv_host.spill={kh.spill!r} (expected auto|off)")
        cfg = self.module.config
        shape = (cfg.n_layer, block_size, cfg.kv_heads * cfg.head_dim)
        dtype = self.dtype.__name__
        cap = int(kh.max_host_blocks) or 4 * max(num_blocks - 1, 1)
        pool = self._kv_host_pool
        if pool is not None and pool.matches_geometry(shape, dtype) \
                and pool.max_blocks == cap:
            return pool
        from deepspeed_tpu.inference.kv_host_pool import KvHostPool
        pool = KvHostPool(cap, shape, dtype, telemetry=self._serving_tel)
        self._kv_host_pool = pool
        log_dist(f"tiered KV cache: host pool of {cap} blocks "
                 f"({shape}, {dtype}) attached behind the block allocator",
                 ranks=[0])
        return pool

    def ensure_host_kv_pool(self):
        """Materialize (or return) the persistent host-RAM KV tier for
        this engine's CURRENT serving geometry without opening a session
        — the replica-router builder uses it to stand the shared pool up
        on the first replica before any session exists. None when
        ``serving.kv_host`` is off or the model cannot prefix-cache."""
        srv = self._config.serving
        bs = int(srv.block_size)
        cfg = self.module.config
        n_max = -(-cfg.max_seq // bs)
        num_blocks = int(srv.max_num_blocks) or \
            (int(srv.max_running) * n_max + 1)
        caching = (hasattr(self.module, "forward_paged_prefill_chunk")
                   and str(srv.prefix_caching) != "off")
        return self._kv_host_pool_for(num_blocks, bs, caching)

    def adopt_host_kv_pool(self, pool) -> None:
        """Share another engine's host KV tier — the dp serving axis's KV
        transport (``inference/router.py``): a prefill replica demotes a
        prompt's committed blocks into the SHARED content-addressed pool
        and a decode replica's tiered admission fetches them H2D, so
        disaggregated prefill/decode needs no new wire format. The pool
        must match this engine's serving geometry (same block slice shape
        + dtype — content addresses are only portable between identical
        layouts); subsequent serve sessions then reuse it instead of
        building a private tier."""
        if pool is None:
            self._kv_host_pool = None
            return
        if self._latent_rows():
            raise ValueError(
                "a host KV pool holds [layers, block_size, kv_heads * "
                "head_dim] slices of a k and a v pool: a model that keeps "
                "latent rows (cache_spec['latent']) has neither")
        cfg = self.module.config
        shape = (cfg.n_layer, int(self._config.serving.block_size),
                 cfg.kv_heads * cfg.head_dim)
        if not pool.matches_geometry(shape, self.dtype.__name__):
            raise ValueError(
                f"host KV pool geometry {pool.block_shape}/{pool.dtype} "
                f"does not match this engine's {shape}/"
                f"{self.dtype.__name__} — replicas can only share a tier "
                "when their serving geometry is identical")
        self._kv_host_pool = pool

    def _kv_workspace(self, B: int, need_len: int):
        """Persistent KV workspace (reference ``inference_context.h:49``:
        one workspace allocated once and reused across calls). Grows
        monotonically in length AND batch: a call with ``B`` smaller than
        the allocated batch runs on a sliced copy instead of reallocating
        (the larger workspace is kept for future calls — ``owned=False``
        tells the caller not to store the sliced copy back). Reuse is safe
        because the causal mask hides slots beyond the current position.
        Returns ``(cache, Smax, owned)``."""
        ws = getattr(self, "_workspace", None)
        if ws is not None and ws[0] >= B and ws[1] >= need_len:
            leaves = jax.tree.leaves(ws[2])
            if not any(getattr(a, "is_deleted", lambda: False)() for a in leaves):
                if ws[0] == B:
                    return ws[2], ws[1], True
                # smaller batch: slice rows [0, B) of the [L, B0, S, KV, Hd]
                # cache — a copy, so donating it through prefill/decode
                # leaves the full workspace intact
                return jax.tree.map(lambda a: a[:, :B], ws[2]), ws[1], False
        cfg = self.module.config
        Smax = min(cfg.max_seq, max(need_len, int(self._config.max_out_tokens)))
        cache = self.module.init_cache(B, Smax, dtype=self.dtype)
        kv_sh = self._kv_head_sharding()
        cache = jax.tree.map(lambda a: jax.device_put(a, kv_sh), cache)
        self._workspace = (B, Smax, cache)
        return cache, Smax, True

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        """Pad prompt lengths up to multiples of 128 (one compile per bucket,
        MXU-aligned) as far as 2,048 and to multiples of 1,024 beyond (a
        long prompt's padding is a small share of it, and a program a 128
        tokens would be 128 programs for a 16k context), clamped to the
        model's max."""
        step = 128 if n <= 2048 else 1024
        return min(-(-max(n, 1) // step) * step, cap)

    def _generate_cached(self, input_ids, max_new, temperature, top_k, rng, eos_token_id):
        if max_new <= 0:
            return input_ids
        B, prompt_len = input_ids.shape
        cfg = self.module.config
        cache, Smax, ws_owned = self._kv_workspace(
            B, min(cfg.max_seq, prompt_len + max_new))
        bucket = self._bucket(prompt_len, Smax)

        if self._decode_jit is None:
            def prefill(params, toks, cache, last_idx):
                # toks are RIGHT-padded to the bucket; junk cache slots are
                # overwritten by decode or masked by causality. MoE modules
                # additionally get a validity mask so bucket padding never
                # competes for expert capacity (top1 used_token)
                kw = {}
                if self._is_moe:
                    kw["valid"] = (jnp.arange(toks.shape[1])[None, :]
                                   <= last_idx).astype(jnp.float32)
                    kw["valid"] = jnp.broadcast_to(kw["valid"], toks.shape)
                logits, cache = self.module.forward_cached(
                    params, toks, cache, jnp.int32(0), **kw)
                return logits[:, last_idx, :].astype(jnp.float32), cache

            def sample(logits, rng, temperature, top_k):
                return jax.lax.cond(
                    temperature > 0.0,
                    lambda: self._sample_jit(logits, temperature, top_k, rng),
                    lambda: jnp.argmax(logits, axis=-1))

            def decode_loop(params, cache, first, pos0, max_new, rng, temperature,
                            top_k, eos, out_cap):
                """Whole decode loop on device: one host transfer per call,
                early exit when every row has emitted eos (eos < 0 = never).
                ``out_cap`` (static, the 128-bucketed max_new) bounds the
                output buffer — sizing it to the cache capacity wasted HBM
                and host-transfer bytes on every short generation."""
                Bd = first.shape[0]
                out0 = jnp.zeros((Bd, out_cap), jnp.int32)
                out0 = out0.at[:, 0].set(first)
                done0 = (first == eos) & (eos >= 0)

                def cond(st):
                    step, _, _, _, done, _ = st
                    return (step < max_new) & ~jnp.all(done)

                def body(st):
                    step, tok, pos, r, done, (cache, out) = st
                    logits, cache = self.module.forward_cached(
                        params, tok[:, None].astype(jnp.int32), cache, pos)
                    r, sub = jax.random.split(r)
                    nxt = sample(logits[:, -1, :].astype(jnp.float32), sub,
                                 temperature, top_k)
                    # rows already done keep emitting eos (stable output)
                    nxt = jnp.where(done & (eos >= 0), eos, nxt)
                    out = jax.lax.dynamic_update_slice(out, nxt[:, None].astype(jnp.int32),
                                                       (0, step))
                    done = done | ((nxt == eos) & (eos >= 0))
                    return step + 1, nxt, pos + 1, r, done, (cache, out)

                st = (jnp.int32(1), first, pos0, rng, done0, (cache, out0))
                step, _, _, _, _, (cache, out) = jax.lax.while_loop(cond, body, st)
                return out, step, cache

            self._prefill_jit = self._watched(
                jax.jit(prefill, donate_argnums=(2,)), "inference.prefill")
            self._decode_jit = self._watched(
                jax.jit(decode_loop, donate_argnums=(1,), static_argnums=(9,)),
                "inference.decode_loop")

        pad = bucket - prompt_len
        toks = jnp.pad(input_ids, ((0, 0), (0, pad))) if pad else input_ids
        logits0, cache = self._prefill_jit(self.params, toks, cache,
                                           jnp.int32(prompt_len - 1))
        rng, sub = jax.random.split(rng)
        first = jnp.asarray(self._sample_host(logits0, temperature, top_k, sub))

        eos = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        # one compile per 128-bucket of max_new (max_new itself stays traced)
        out_cap = min(Smax, self._bucket(max_new, Smax))
        out, n, cache = self._decode_jit(self.params, cache, first,
                                         jnp.int32(prompt_len), jnp.int32(max_new),
                                         rng, jnp.float32(temperature),
                                         jnp.int32(top_k), eos, out_cap)
        if ws_owned:
            self._workspace = (B, Smax, cache)  # keep the donated-through workspace
        n = int(n)
        gen = np.asarray(out)[:, :n]
        return jnp.concatenate([input_ids, jnp.asarray(gen, jnp.int32)], axis=1)

    # ------------------------------------------------------------------ #
    # Paged KV cache + continuous batching (vLLM PagedAttention / Orca
    # iteration-level scheduling): KV block pools shared by every in-flight
    # request, per-request block tables, one fused decode step over ALL
    # running requests per engine step, finished rows retired and queued
    # requests admitted in their place. Memory is bounded by tokens in
    # flight (not B × Smax) and a slow request never convoys the batch.

    def _paged_supported(self) -> bool:
        # an MoE model pages like any other on one replica of its experts;
        # expert parallelism (ep > 1) and weight streaming stay on the
        # static path
        return (not self._stream_weights and self._ep_size == 1
                and hasattr(self.module, "forward_paged_decode")
                and hasattr(self.module, "forward_paged_prefill")
                and hasattr(self.module, "init_paged_cache")
                and hasattr(self.module, "config"))

    def _paged_pools(self, num_blocks: int, block_size: int):
        """Persistent paged-pool workspace: same lifecycle contract as
        :meth:`_kv_workspace` (reuse is safe — every slot a request reads
        was written by that request, or by the request that REGISTERED the
        block in the prefix cache). Returns ``(pools, reused)`` — a fresh
        workspace has no valid cached content, so the caller must drop any
        persisted prefix-cache state alongside it."""
        pw = getattr(self, "_paged_workspace", None)
        # what sizes the pools beside the KV blocks' geometry, the
        # workspace's key with it
        slots, wblocks = kept = (self._state_slots(),
                                 self._window_blocks(num_blocks, block_size))
        if pw is not None and pw[0] == num_blocks and pw[1] == block_size \
                and pw[3] == kept:
            leaves = jax.tree.leaves(pw[2])
            if not any(getattr(a, "is_deleted", lambda: False)() for a in leaves):
                return pw[2], True
        pools = self.module.init_paged_cache(
            num_blocks, block_size, dtype=self.dtype,
            **({"state_slots": slots} if slots else {}),
            **({"window_blocks": wblocks} if wblocks else {}))
        kv_sh = self._kv_head_sharding()
        pools = jax.tree.map(lambda a: jax.device_put(a, kv_sh), pools)
        self._paged_workspace = (num_blocks, block_size, pools, kept)
        return pools, False

    def _paged_allocator(self, num_blocks: int, block_size: int,
                         caching: bool, pools_reused: bool):
        """Block allocator for one serve call. With prefix caching the
        allocator PERSISTS across ``generate_batch`` calls — its
        content-addressed table describes the persistent pool workspace, so
        later calls hit earlier calls' prefixes — as long as the workspace
        itself was reused, geometry matches, and every request of the
        previous call retired cleanly (no leaked references). A cache-off
        call writes blocks the persisted table still describes, so it also
        invalidates the persisted allocator."""
        from deepspeed_tpu.inference.block_allocator import BlockAllocator

        if not caching:
            self._paged_alloc = None
            wblocks = self._window_blocks(num_blocks, block_size)
            return BlockAllocator(
                num_blocks, block_size, state_slots=self._state_slots(),
                window_blocks=wblocks,
                ring_blocks=self.module.config.ring_blocks(block_size)
                if wblocks else 0)
        pa = self._paged_alloc
        if (pools_reused and pa is not None
                and pa.num_blocks == num_blocks
                and pa.block_size == block_size
                and not pa.leak_report()):
            return pa
        alloc = BlockAllocator(num_blocks, block_size, prefix_cache=True)
        self._paged_alloc = alloc
        return alloc

    def _ensure_paged_jits(self):
        if self._paged_jits is None:
            from deepspeed_tpu.models.transformer import copy_paged_block
            mod = self.module
            kv_sh = self._kv_head_sharding()
            pin_sh = kv_sh if any(s is not None for s in kv_sh.spec) else None

            def _pin(pools):
                # NamedSharding-constrained workspaces: under tp the pools
                # must come OUT of every fused step still head-sharded
                # (donation pairs the constrained output with the sharded
                # input buffer), so the row-projection psum is each layer's
                # only collective — unconstrained, the partitioner is free
                # to gather the pool on the way out
                if pin_sh is None:
                    return pools
                return jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(a, pin_sh),
                    pools)

            def _pinned(fn):
                def run(*args, **state):
                    # an MoE model's decode step returns its [L, E + 1]
                    # assignment counts third
                    logits, pools, *aux = fn(*args, **state)
                    return (logits, _pin(pools), *aux)
                return run

            # named functions, not lambdas: the name is what a device trace
            # calls the program (``jit_paged_decode``) and what the
            # persistent compile cache keys it by
            # ``kept``: what the model keeps a request beside its KV blocks,
            # by its cache spec and in this order, the last operands: the
            # state slot(s) of a model with a recurrent state, the window
            # table(s) from the host of one with window layers
            spec = self._cache_spec()

            def _kept(kept, slot, table=None):
                names = [n for n, on in ((slot, spec.get("state")),
                                         (table, spec.get("window")))
                         if n and on]
                return dict(zip(names, kept, strict=True))

            def paged_prefill(p, t, pools, slots, li, *kept):
                return _pinned(mod.forward_paged_prefill)(
                    p, t, pools, slots, li,
                    **_kept(kept, "state_slot", "window_table"))

            def paged_decode(p, t, pools, bt, pos, *kept):
                # the session hands ``t`` over as the step's token feed
                # ``(prev, idx, toks)``: row i takes the token at
                # ``prev[idx[i]]``, still on the device as the sampler left
                # it, where idx[i] >= 0, and the host's ``toks[i]``
                # otherwise. ONE form whatever step came before (the sampler
                # leaves its tokens at the decode width), so the first
                # prefill -> decode of a warm-up compiles all there is; and
                # no program of its own: the gather is an op of this one
                if isinstance(t, tuple):
                    prev, idx, toks = t
                    t = jnp.where(idx[:, None] >= 0,
                                  prev[jnp.maximum(idx, 0)][:, None]
                                  .astype(toks.dtype), toks)
                return _pinned(mod.forward_paged_decode)(
                    p, t, pools, bt, pos,
                    **_kept(kept, "state_slots", "window_tables"))

            def paged_prefill_chunk(p, t, pools, bt, slots, sp, li, *kept):
                return _pinned(mod.forward_paged_prefill_chunk)(
                    p, t, pools, bt, slots, sp, li,
                    **_kept(kept, "state_slot"))

            def paged_verify(p, t, pools, bt, slots, pos):
                return _pinned(mod.forward_paged_verify)(
                    p, t, pools, bt, slots, pos)

            gen = getattr(mod.config, "generation", None)

            def paged_block(p, t, pools, bt, pos, n_decide, commit,
                            key=None, temperature=None, top_k=0):
                # one pass of generation by blocks over all running rows,
                # the decision included: ``t`` is the block feed ``(prev,
                # idx, host)`` (entry i's block as the pass in flight left
                # it on the device at main entry idx[i], or the host's): W
                # main entries, a row's open block each (``n_decide``,
                # ``commit`` [W]), then the rider entries (``bt``, ``pos``
                # [N]), whole blocks that commit beside their rows' next
                # (``blockgen``). The head and the decision run over the
                # main entries alone; the first output is their blocks as
                # this pass leaves them, which stay on the device for the
                # next pass's feed
                W = n_decide.shape[0]
                state = blockgen.feed(*t)
                logits, pools, *aux = _pinned(mod.forward_paged_block)(
                    p, blockgen.tokens_of(gen, state), pools, bt, pos,
                    n_logits=W)
                draw = None if key is None else (
                    lambda lg: self._draw(lg, temperature, top_k, key))
                return (blockgen.unmask(gen, logits, state[:W], n_decide,
                                        commit, draw), pools, *aux)

            def paged_cow(pools, src, dst):
                return _pin(copy_paged_block(pools, src, dst))

            # tiered KV cache copy programs: the per-block D2H gather
            # (spill) and H2D scatter (fetch). The block index is traced,
            # so each is ONE program regardless of which block moves; the
            # slice is pinned to the pool's head sharding (under tp each
            # shard moves only its local heads). Gather does NOT donate —
            # the pools live on; scatter donates like every fused step.
            slice_sh = self._kv_slice_sharding()
            slice_pin = slice_sh if any(s is not None for s in slice_sh.spec)\
                else None

            def _pin_slice(a):
                if slice_pin is None:
                    return a
                return jax.lax.with_sharding_constraint(a, slice_pin)

            def paged_spill_gather(pools, b):
                return {
                    "k": _pin_slice(jax.lax.dynamic_index_in_dim(
                        pools["k"], b, axis=1, keepdims=False)),
                    "v": _pin_slice(jax.lax.dynamic_index_in_dim(
                        pools["v"], b, axis=1, keepdims=False))}

            def paged_fetch_scatter(pools, b, ks, vs):
                return _pin({
                    "k": jax.lax.dynamic_update_index_in_dim(
                        pools["k"], ks.astype(pools["k"].dtype), b, axis=1),
                    "v": jax.lax.dynamic_update_index_in_dim(
                        pools["v"], vs.astype(pools["v"].dtype), b, axis=1)})

            def paged_sample(logits, key, temperature, top_k, width):
                # the sampler's whole dispatch as ONE program: the cast,
                # the draw (``key`` and ``temperature`` None: greedy) and
                # the widening of a prefill's one token to the decode
                # width, where the next decode step's feed gathers from
                logits = logits.astype(jnp.float32)
                tok = jnp.argmax(logits, axis=-1) if key is None \
                    else self._draw(logits, temperature, top_k, key)
                return tok if tok.shape[0] == width \
                    else jnp.broadcast_to(tok, (width,))

            def watched(fn, donate=(), static=()):
                return self._watched(
                    jax.jit(fn, donate_argnums=donate, static_argnums=static),
                    "inference." + fn.__name__)

            self._paged_jits = (
                watched(paged_prefill, (2,)),
                watched(paged_decode, (2,)),
                watched(paged_prefill_chunk, (2,))
                if hasattr(mod, "forward_paged_prefill_chunk") else None,
                watched(paged_cow, (0,)),
                watched(paged_verify, (2,))
                if hasattr(mod, "forward_paged_verify") else None,
                watched(paged_spill_gather),
                watched(paged_fetch_scatter, (0,)),
                watched(paged_sample, static=(3, 4)),
                # last, and only for a model that generates by blocks
                *([watched(paged_block, (2,), static=(9,))]
                  if gen is not None else []),
            )
        return self._paged_jits

    @staticmethod
    def _flat_slots(table, start, n_valid, width, bs):
        """Flat pool slot per position ``start + t`` for t in [0, width):
        the first ``n_valid`` positions write through the request's block
        table, compile-bucket pads route their junk k/v to the dummy
        block. The ONE place the slot layout lives — whole-prompt prefill
        and chunked prefill must scatter identically."""
        from deepspeed_tpu.inference.block_allocator import DUMMY_BLOCK
        t = np.arange(width)
        p_t = start + t                              # global positions
        slot = table[np.minimum(p_t // bs, table.size - 1)] * bs + p_t % bs
        return np.where(t < n_valid, slot, DUMMY_BLOCK * bs + p_t % bs)

    def generate_batch(self, prompts, max_new_tokens: Optional[int] = None,
                       temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                       eos_token_id: Optional[int] = None):
        """Serve a batch of variable-length prompts with continuous batching
        over the paged KV cache. Returns a list of 1-D int32 arrays
        (prompt + generated tokens, stopping at eos / max_new per request),
        in the order the prompts were given.

        ``config.serving`` governs the path: ``paged="auto"`` (default)
        pages whenever the model supports it, ``"on"`` requires it,
        ``"off"`` — and unsupported models under auto — falls back to the
        static ``generate`` path per request. ``prefix_caching`` (default
        auto = on) shares already-computed KV blocks across requests AND
        across calls (the pool workspace persists); ``prefill_chunk_tokens``
        interleaves prefill chunks with decode steps;
        ``speculative: {mode: "ngram", k}`` turns on draft-free
        self-speculation — verified multi-token decode steps that emit
        (accepted + 1) tokens per fused step on repetitive workloads.
        ``serving.tp`` > 0 serves tensor-parallel over a ``tp`` mesh axis:
        params column/row-sharded, KV pools split on the KV-head dim,
        the fused steps running with exactly one all-reduce per layer and
        the Pallas paged kernel dispatched per-shard via shard_map —
        token-identical to the tp=1 engine (greedy), with decode
        throughput and max model size scaling with the slice.
        """
        with self._mesh_scope():
            return self._generate_batch(prompts, max_new_tokens, temperature,
                                        top_k, seed, eos_token_id)

    def _generate_batch(self, prompts, max_new_tokens, temperature, top_k,
                        seed, eos_token_id):
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        if not prompts:
            return []
        self._reject_encoders("generate_batch()")
        srv = self._config.serving
        mode = str(srv.paged)
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"serving.paged={mode!r} (expected auto|on|off)")
        supported = self._paged_supported()
        if mode == "on" and not supported:
            raise ValueError(
                "serving.paged='on' but this engine cannot page: the model "
                "must be a zoo causal LM (forward_paged_decode) and the "
                "engine must not be weight-streaming or expert-parallel "
                "(moe.ep_size > 1)")
        max_new = (max_new_tokens if max_new_tokens is not None
                   else self._config.max_out_tokens)
        if mode == "off" or not supported:
            if str(srv.speculative.mode) == "ngram":
                # the same courtesy the temperature>0 case gets: the user
                # configured speculation, and silence would read as "on"
                warn_once("serving.speculative is ignored on the static "
                          "(non-paged) serving path — speculation needs "
                          "the paged engine (serving.paged)")
            # static fallback: each request through the (batched-workspace)
            # generate path, one at a time — correct for every engine mode.
            # Per-request seed offset: sampled mode must not hand every
            # request (or duplicate prompts) the same rng stream
            return [self.generate(p[None, :], max_new_tokens=max_new,
                                  temperature=temperature, top_k=top_k,
                                  seed=seed + i, eos_token_id=eos_token_id)[0]
                    for i, p in enumerate(prompts)]
        if max_new <= 0:
            return [jnp.asarray(p) for p in prompts]

        session = self.open_serve_session(
            max_new=max_new, temperature=temperature, top_k=top_k,
            seed=seed, eos_token_id=eos_token_id)
        ev = self._events
        t_serve0 = time.monotonic_ns() if ev is not None else 0
        if ev is not None:
            ev.emit("serve.begin", t_ns=t_serve0, requests=len(prompts))
        # the try/finally guards rid uniqueness: even when a serve aborts
        # (oversized prompt, pool exhaustion) the next serve's rids must
        # not collide with this one's in the shared flight-recorder ring
        try:
            for p in prompts:
                session.add(p)
            while session.step():
                pass
        finally:
            session.close()
        if ev is not None:
            ev.emit("serve.end", t_ns=t_serve0,
                    dur_ns=time.monotonic_ns() - t_serve0,
                    requests=len(prompts))
        session.end()
        sched = session.sched
        failed = [r for r in sched.finished if r.error is not None]
        if failed:
            # a silently truncated generation is worse than a loud failure:
            # this only happens when preemption grew a request's prefix past
            # what the pool can EVER hold — the same misconfiguration
            # add_request rejects up front, arising dynamically
            raise RuntimeError(
                f"{len(failed)} request(s) retired without completing "
                "(KV pool too small for the workload — raise "
                "serving.max_num_blocks): "
                + "; ".join(f"request {r.rid}: {r.error}" for r in failed))
        done = sorted(sched.finished, key=lambda r: r.rid)
        return [jnp.asarray(r.output) for r in done]

    def open_serve_session(self, *, max_new: int, temperature: float = 0.0,
                           top_k: int = 0, seed: int = 0,
                           eos_token_id: Optional[int] = None, policy=None,
                           on_tokens=None, on_finish=None,
                           retain_finished: bool = True):
        """Open one paged serving session: the scheduler, the persistent
        pool workspace, and the fused-step jit context, bundled behind a
        step API (:class:`_ServeSession`). BOTH entry points run through
        it — ``generate_batch`` adds its whole batch and drains, the
        always-on ``AsyncServingEngine`` (``inference/serve.py``) feeds
        arrivals in as they come — so the open-loop path executes exactly
        the closed-loop compiled programs (the ``serving_async_steady``
        compile-budget contract). At most one session may be active per
        engine: the pools are donated through every fused step, so a
        second concurrent user would read deleted buffers.

        ``policy`` plugs a scheduling policy (``inference/policy.py``)
        into the scheduler; ``on_tokens(req, tokens)`` streams each
        emitted burst (speculation emits multi-token bursts) and
        ``on_finish(req)`` fires once per retired request — both host-side
        callbacks on the serving thread."""
        if self._active_session is not None:
            raise RuntimeError(
                "another serving session is active on this engine (an "
                "AsyncServingEngine loop, or a generate_batch in flight); "
                "drain/shutdown it before opening a new one")
        srv = self._config.serving
        if str(srv.paged) == "off" or not self._paged_supported():
            raise ValueError(
                "a serving session needs the paged engine (zoo causal LM, "
                "dense or MoE, not weight-streaming, moe.ep_size == 1, "
                "serving.paged != 'off') — the serving loop has no static "
                "fallback")
        if max_new <= 0:
            raise ValueError("a serving session needs max_new >= 1")

        from deepspeed_tpu.inference.scheduler import \
            ContinuousBatchingScheduler

        cfg = self.module.config
        bs = int(srv.block_size)
        W = int(srv.max_running)
        n_max = -(-cfg.max_seq // bs)          # block-table width
        num_blocks = int(srv.max_num_blocks) or (W * n_max + 1)

        # prefix caching + chunked prefill both ride the chunk forward
        pc_mode = str(srv.prefix_caching)
        if pc_mode not in ("auto", "on", "off"):
            raise ValueError(
                f"serving.prefix_caching={pc_mode!r} (expected auto|on|off)")
        chunk_tokens = int(srv.prefill_chunk_tokens)
        if chunk_tokens < 0:
            raise ValueError("serving.prefill_chunk_tokens must be >= 0")
        chunk_ok = hasattr(self.module, "forward_paged_prefill_chunk")
        # what cannot hold beside a recurrent state or a window layer's
        # ring yet, each refused from the model's cache spec
        stateful = self._keeps_beside_kv()
        if stateful and pc_mode == "on":
            raise ValueError(
                f"serving.prefix_caching='on' but the model keeps "
                f"{self._slot_kept()} beside its KV: a cached block says "
                "nothing of the state at its end, or of the window before "
                "it (snapshots at block boundaries are not built)")
        if stateful and str(srv.speculative.mode) != "off":
            raise ValueError(
                f"serving.speculative.mode={str(srv.speculative.mode)!r} "
                f"but the model keeps {self._slot_kept()}: a verify window "
                "rewinds to the last accepted position, and a state or a "
                "ring that has been written over cannot be rewound (no "
                "snapshot is kept)")
        # how the model generates: its config's record, read as the cache
        # spec is (None: a token a step)
        gen = getattr(cfg, "generation", None)
        if gen is not None and not hasattr(self.module, "forward_paged_block"):
            raise ValueError("the model generates by blocks (config."
                             "generation) but has no forward_paged_block")
        if gen is not None and str(srv.speculative.mode) != "off":
            raise ValueError(
                f"serving.speculative.mode={str(srv.speculative.mode)!r} "
                "but the model generates by diffusion over blocks: a pass "
                "already carries a block of positions a row, and a draft's "
                "next-token window has no meaning under its mask")
        if chunk_tokens and stateful and self.module.config.cache_spec.get("window"):
            raise ValueError(
                "serving.prefill_chunk_tokens set but the model keeps a "
                "window layer's ring of blocks: a chunk longer than a block "
                "writes over ring positions its first queries still read "
                "(no form reads the ring beside the chunk's own keys)")
        if stateful and self.mesh.shape.get("tp", 1) > 1:
            raise ValueError(f"serving.tp > 1 but the model keeps "
                             f"{self._slot_kept()}: the pools that hold it "
                             "are not sharded")
        # what no program reads a latent row for yet (a hit's tail and a
        # chunk ride the chunk forward, speculation the verify window)
        latent = bool(self._latent_rows())
        for on, what in ((pc_mode == "on", "serving.prefix_caching='on'"),
                         (chunk_tokens, "serving.prefill_chunk_tokens"),
                         (str(srv.speculative.mode) != "off",
                          "serving.speculative.mode")):
            if latent and on:
                raise ValueError(
                    f"{what} set but the model keeps latent rows "
                    "(cache_spec['latent']): whole-prompt prefill and "
                    "decode alone read a latent row (no chunk or verify "
                    "form is built)")
        if not chunk_ok:
            if pc_mode == "on":
                raise ValueError(
                    "serving.prefix_caching='on' but the model has no "
                    "forward_paged_prefill_chunk (needed to prefill the "
                    "uncached tail against cached blocks)")
            if chunk_tokens:
                raise ValueError(
                    "serving.prefill_chunk_tokens set but the model has no "
                    "forward_paged_prefill_chunk")
        caching = chunk_ok and pc_mode != "off" and not stateful \
            and not latent

        # ---- speculative decoding (n-gram self-speculation) ----
        spec = srv.speculative
        spec_mode = str(spec.mode)
        if spec_mode not in ("off", "ngram", "auto"):
            raise ValueError(f"serving.speculative.mode={spec_mode!r} "
                             "(expected off|ngram|auto)")
        # "auto" is reserved for a future draft-model speculator: off today
        spec_on = spec_mode == "ngram"
        if spec_on and not hasattr(self.module, "forward_paged_verify"):
            raise ValueError(
                "serving.speculative.mode='ngram' but the model has no "
                "forward_paged_verify (the fused multi-position verify "
                "step); serve a zoo causal LM or set mode='off'")
        if spec_on and temperature > 0.0:
            # acceptance is greedy-argmax-exact; lossless sampled
            # speculation needs rejection sampling over the verify logits
            warn_once("serving.speculative is greedy-only: temperature > 0 "
                      "disables speculation for this call")
            spec_on = False
        spec_k = int(spec.k)
        if spec_on and spec_k < 1:
            raise ValueError("serving.speculative.k must be >= 1")
        proposer = None
        spec_wb = 0
        if spec_on:
            from deepspeed_tpu.inference.spec import NgramProposer
            proposer = NgramProposer(min_match=int(spec.min_match),
                                     max_match=int(spec.max_match))
            # verify window compile bucket: next power of two of k+1, so
            # sweeping k costs <= log2 programs (pinned by the
            # serving_speculative compile-budget contract)
            spec_wb = 1 << int(spec_k).bit_length()

        pools, pools_reused = self._paged_pools(num_blocks, bs)
        alloc = self._paged_allocator(num_blocks, bs, caching, pools_reused)
        # tiered KV cache: attach the persistent host-RAM tier (content-
        # addressed, so it survives pool/allocator rebuilds) and decide
        # whether this session demotes (spill) or only serves host hits
        host_pool = self._kv_host_pool_for(num_blocks, bs, caching)
        alloc.attach_host_pool(host_pool)
        kv_spill = (host_pool is not None
                    and str(srv.kv_host.spill) != "off")
        if self._serving_tel is not None:
            # KV gauges (blocks free/used, fragmentation) are GLOBAL per
            # slice — the allocator is replicated and block ids are shard-
            # invariant; this gauge annotates them so a head-sharded pool
            # is not misread as 1/tp of the memory
            self._serving_tel.tp.set(float(self.mesh.shape.get("tp", 1)))
        ev = self._events
        sched = ContinuousBatchingScheduler(alloc, W, n_max,
                                            telemetry=self._serving_tel,
                                            prefix_caching=caching,
                                            chunk_tokens=chunk_tokens,
                                            events=ev,
                                            rid_base=self._serve_rid_base,
                                            spec_k=spec_k if spec_on else 0,
                                            spec_proposer=proposer,
                                            policy=policy, generation=gen)
        session = _ServeSession(
            self, sched, pools, self._ensure_paged_jits(),
            max_new=max_new, temperature=temperature, top_k=top_k,
            rng=jax.random.key(seed), eos_token_id=eos_token_id,
            spec_wb=spec_wb, W=W, n_max=n_max, bs=bs,
            num_blocks=num_blocks, chunk_tokens=chunk_tokens, ev=ev,
            on_tokens=on_tokens, on_finish=on_finish,
            retain_finished=retain_finished, kv_spill=kv_spill)
        self._active_session = session
        return session

    @staticmethod
    def _sample_jit(logits, temperature, top_k, rng):
        """Sampling with traced temperature/top_k (so the decode step compiles
        once): logits below the top_k-th value are masked when top_k > 0."""
        logits = logits / jnp.maximum(temperature, 1e-6)
        idx = jnp.clip(top_k - 1, 0, logits.shape[-1] - 1)
        thresh = jnp.sort(logits, axis=-1)[..., ::-1][..., idx][..., None]
        logits = jnp.where((top_k > 0) & (logits < thresh), -jnp.inf, logits)
        return jax.random.categorical(rng, logits, axis=-1)

    @property
    def config(self):
        return self._config


#: the dispatch sites of a serving step (the four action kinds' and the
#: sub-dispatches), in the order of ``_ensure_paged_jits``' programs
_DISPATCH_SITES = ("prefill", "decode", "prefill_chunk", "cow", "verify",
                   "spill", "fetch", "sample", "block")


def _on_device(a):
    """A numpy operand as a device array, a tuple of operands (the decode
    step's token feed) one by one; anything else as it is."""
    if isinstance(a, tuple):
        return tuple(map(_on_device, a))
    return jnp.asarray(a) if isinstance(a, (np.ndarray, np.generic)) else a


class _Launched:
    """A step on the device's queue whose tokens the host has not fetched:
    what :meth:`_ServeSession._launch` leaves for :meth:`_ServeSession.land`."""
    __slots__ = ("name", "kind", "reqs", "part", "tok", "aux", "t0",
                 "logits", "spent", "live")

    def __init__(self, name, kind, reqs, part, tok, aux, t0, logits, spent):
        self.name, self.kind, self.reqs, self.part = name, kind, reqs, part
        #: the sampler's output, its copy to the host under way (None: a
        #: prefill chunk that is not the last samples nothing)
        self.tok = tok
        self.aux, self.t0 = aux, t0
        #: dropped under ``serve.release`` when the step lands: its logits
        #: and the handles of the pools it consumed
        self.logits, self.spent = logits, spent
        #: by row, whether its token counts; False where the token of the
        #: step before turned out to be the request's EOS (the overshoot)
        self.live = [True] * len(reqs)


class _ServeSession:
    """One paged serving session: scheduler + pools + jit context behind a
    step API. ``generate_batch`` (closed loop) and ``AsyncServingEngine``
    (open loop) both execute scheduler actions THROUGH this class, so an
    action compiles and dispatches identically no matter which front-end
    drove it — the ``serving_async_steady`` contract's mechanism, not just
    its test. Single-threaded by contract: every method must run on the
    thread that owns the engine's jit dispatch (the caller's thread for
    generate_batch, the serving loop thread for the async engine), under
    the engine's ``_mesh_scope``.

    **One step ahead.** Between two :meth:`step` calls at most ONE launched
    step is in flight (``_flight``): its program and its sampler are on the
    device's queue, its positional half is in the scheduler
    (``advance_*``), its tokens are not on the host. The next call launches
    the following action BEHIND it, from its tokens on the device (the
    decode program's feed operand), and only then fetches, commits and releases it, under
    the new step's device time. The overlap is the device's queue: there is
    no second thread. The queue is one deep, so a decode step launched when
    the step in flight has ALREADY finished found the device dry: the launch
    counts those (``serving/decode_steps_late``, one non-blocking
    ``is_ready()``), the stalls of the host that outlasted a device step,
    and with a telemetry how LONG the device was dry, as a bracket
    (``serving/late_ms``, ``late_slack_ms``: ``self.loop``, which also
    books the loop's phases, :meth:`phase`).
    Nobody but :meth:`step` and :meth:`land` may touch a step in flight, and
    everything that needs its tokens on the host or
    would undo its rows lands it first — :meth:`cancel`,
    :meth:`demote_prompt`, :meth:`contain_fault`, :meth:`restart_engine`,
    :meth:`close`, and inside :meth:`step` the cases ``plans_ahead`` and
    ``_ActionKind.ahead`` name. A front-end that touches ``sched`` itself
    (a knob, load shedding, a look-up by rid) calls :meth:`land` first; a
    ``submit`` needs none. Served tokens are those of the serial order (the
    pipe at depth zero); the one difference is a request whose EOS lands
    while its next step is already queued: that step's token for it is
    dropped (``_Launched.live``), its write went beyond ``pos`` into the
    request's own block, never read and never registered. (Under
    ``temperature > 0`` such a step still takes its ``random.split`` and
    holds the row for one step more, so the OTHER rows' draws after an EOS
    are another sample of the same distribution, not the serial order's.)"""

    #: private: False pins the pipe at depth zero (every step lands in the
    #: call that launched it), the serial order the exactness tests compare
    #: with. Nothing a user can set.
    _run_ahead = True

    def __init__(self, engine, sched, pools, jits, *, max_new, temperature,
                 top_k, rng, eos_token_id, spec_wb, W, n_max, bs, num_blocks,
                 chunk_tokens, ev, on_tokens=None, on_finish=None,
                 retain_finished=True, kv_spill=False):
        self.engine = engine
        self.sched = sched
        self.pools = pools
        # whether the decode program's MoE counts carry the two columns of
        # a model with zero-compute experts (``count_moe``)
        self.zero_experts = bool(getattr(getattr(
            engine.module, "moe", None), "zero_experts", 0))
        # the row tile of the decode program's grouped expert kernel; 0: its
        # call is of one row tile, or takes another form (``count_moe``)
        tile_of = getattr(engine.module, "expert_row_tile", None)
        self.moe_row_tile = tile_of(
            engine.params, (W + sched.ride_slots) * sched.gen.block
            if sched.gen else W) if tile_of else 0
        self._programs = dict(zip(_DISPATCH_SITES, jits))
        # fault containment (serving.fault): the action a fault can be
        # attributed to, the finer-grained dispatch site for the
        # step_faults{kind=} label (an action may run cow/fetch sub-steps
        # before its own dispatch), and the retry/backoff bounds the
        # always-on loop's containment applies (see contain_fault)
        self.last_action = None
        self.fault_site = None
        fault = engine._config.serving.fault
        self.fault_max_retries = int(fault.max_request_retries)
        self.fault_backoff_steps = int(fault.retry_backoff_steps)
        self._kv_spill = kv_spill
        # tiered KV cache: the demotion hook is session-scoped — it reads
        # the LIVE (donated-through) pools, so it must never outlive this
        # session (close() clears it)
        if kv_spill:
            sched.allocator.set_spill(self._spill_block)
        else:
            sched.allocator.set_spill(None)
        self.max_new = int(max_new)
        self.temperature = temperature
        self.top_k = top_k
        self.rng = rng
        self.eos_token_id = eos_token_id
        self.spec_wb = spec_wb
        self.W = W
        self.n_max = n_max
        self.bs = bs
        self.num_blocks = num_blocks
        self.chunk_tokens = chunk_tokens
        self.ev = ev
        self.on_tokens = on_tokens
        self.on_finish = on_finish
        # closed loop reads sched.finished for its outputs; the ALWAYS-ON
        # loop must not retain every Request forever (unbounded growth) —
        # it consumes results through on_finish and sets this False
        self.retain_finished = retain_finished
        self._finished_seen = 0
        self._closed = False
        # a model that keeps a recurrent state takes the requests' slots,
        # one with window layers their window tables from the host, as
        # each program's last operands
        self._stateful = "state" in pools
        # a window layer's reach (positions) and its ring's blocks; 0: none
        self._window = int(engine.module.config.attn_window) \
            if "wk" in pools else 0
        self._ring = engine.module.config.ring_blocks(bs) \
            if self._window else 0
        self._flight: Optional[_Launched] = None
        # the newest sampled tokens at the decode width, on the device:
        # what a decode step's feed gathers from (the step in flight's, if any)
        self._tok_dev = None
        # generation by blocks (the model's record; None: a token a step):
        # the rows' open blocks as the newest pass left them, on the device
        self._gen = sched.gen
        self._blk_dev = None
        #: the loop's own time (``monitor.trace.LoopTime``), where there is
        #: a telemetry to publish it; without one no phase reads a clock
        self.loop = LoopTime() if sched.telemetry is not None else None

    def phase(self, name, **args):
        """``with self.phase("commit"):`` — the loop's phase ``name``:
        ``span("serve.<name>", **args)`` and, with a telemetry, its time in
        ``self.loop``."""
        if self.loop is None:
            return span("serve." + name, **args)
        return self.loop.phase(name, **args)

    def _booking(self):
        """A step's bookkeeping between the spans (the launch's positional
        half, the landing's rows, MoE counts and recorder events): a
        counter of the loop's time with a telemetry, and never a span."""
        return nullcontext() if self.loop is None else self.loop.phase("book")

    # ---- request front-end ---- #

    _UNSET = object()

    def add(self, prompt, max_new=None, eos=_UNSET, priority: int = 0,
            ttft_budget=None, t_submit=None, deadline_ms=None,
            deadline_steps=None, trace=None, parent=None):
        """Enqueue one request (any time — mid-decode arrivals are the
        point). ``max_new``/``eos`` default to the session-wide values."""
        if self._closed:
            raise RuntimeError("serving session is closed")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        mn = self.max_new if max_new is None else int(max_new)
        if mn < 1:
            # the session-level guard only covers the default; a per-
            # request 0 would still emit the prefill-sampled token
            raise ValueError(f"max_new_tokens must be >= 1, got {mn}")
        cfg = self.engine.module.config
        if prompt.size + mn > cfg.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({mn}) exceeds "
                f"model max_seq {cfg.max_seq}")
        return self.sched.add_request(
            prompt, mn, self.eos_token_id if eos is self._UNSET else eos,
            priority=priority, ttft_budget=ttft_budget, t_submit=t_submit,
            deadline_ms=deadline_ms, deadline_steps=deadline_steps,
            trace=trace, parent=parent)

    def cancel(self, req) -> bool:
        """Cancel between engine steps; fires ``on_finish`` for the
        retired request."""
        self.land()
        ok = self.sched.cancel_request(req)
        self._flush_finished()
        return ok

    # ---- stepping ---- #

    def step(self) -> bool:
        """Launch ONE scheduler action (admission prefill, prefill chunk,
        fused decode or fused verify) and land the one launched before it,
        in that order when the new action can be chosen and fed without the
        old one's tokens on the host, else the other way round. Returns
        False when nothing is runnable — queue and running batch both empty
        — and nothing is in flight."""
        if self._closed:
            raise RuntimeError("serving session is closed")
        self.last_action = None      # a fault in next_action itself must
        self.fault_site = None       # not be attributed to the PREVIOUS
        # step's action or dispatch site
        # whatever else it does, a step lands the step it finds in flight
        worked = self._flight is not None
        if worked and not self.sched.plans_ahead(self._flight.reqs):
            self.land()
        try:
            with self.phase("schedule"):
                action = self.sched.next_action()
        except BaseException:
            self.land()              # its tokens are sound
            raise
        if action is None:
            if worked:
                self._count_step()
            if self._flight is not None:
                self.land()          # an EOS may yet free what a queued
                return True          # request waits for: choose again
            self._flush_finished()   # admission-time error retirements
            return False
        self.last_action = action
        name, payload = action
        kind = _ACTION_KINDS.get(name)
        if kind is None:
            self.land()
            raise ValueError(f"scheduler action of unknown kind {name!r}")
        reqs = kind.rows(payload)
        if not kind.ahead or reqs[0].cow_pending is not None \
                or reqs[0].fetch_pending:
            # the action reads tokens on the host (verify), is a tick of
            # the clock alone (wait), or copies blocks a row in flight may
            # share: the serial order
            self.land()
        with span("serve.exec", kind=name, **kind.says(self, reqs)):
            try:
                launched = self._launch(name, kind, reqs)
            except BaseException:
                # a fault at this launch is contained as ever, AFTER the
                # step before it landed: no token lost, none given twice
                self.land()
                raise
            self.land()              # the step before, under this one
            if launched is not None:
                with self._booking():
                    self._advance(launched)
                self._flight = launched
                if (launched.tok is None and self._gen is None) \
                        or not kind.ahead or not self._run_ahead:
                    # nothing to wait for, or depth zero. (A model that
                    # generates by blocks samples nothing at ANY prefill:
                    # its prefill stays in flight as a sampling one does,
                    # the next pass is launched behind it and counts so.)
                    self.land()
            if self._finished_seen < len(self.sched.finished):
                # retirements the launch itself made (an admission's error)
                with self.phase("commit"):
                    self._flush_finished()
        if worked or launched is not None:
            self._count_step()
        return True

    def _count_step(self) -> None:
        if self.loop is not None:
            self.loop.steps += 1

    def _chunk_len(self, req) -> int:
        """Tokens the next prefill chunk of ``req`` computes."""
        remaining = req.prefill_target - req.pos
        chunk = self.chunk_tokens
        if chunk and self._gen is not None:
            # pieces start and end on whole generation blocks
            chunk = max(chunk // self._gen.block, 1) * self._gen.block
        return min(chunk, remaining) if chunk else remaining

    def _flush_finished(self) -> None:
        fin = self.sched.finished
        while self._finished_seen < len(fin):
            r = fin[self._finished_seen]
            self._finished_seen += 1
            if self.on_finish is not None:
                self.on_finish(r)
        if not self.retain_finished and self._finished_seen:
            del fin[:self._finished_seen]
            self._finished_seen = 0

    # ---- serving fault containment (serving.fault) ---- #

    def pools_alive(self) -> bool:
        """Whether the session's pool buffers are still valid. Every
        fused step DONATES the pools, so an exception between the
        dispatch and the adoption of its outputs leaves ``self.pools``
        naming consumed buffers — the definitive engine-fatal signature
        (a pre-dispatch failure leaves them intact: per-request)."""
        return not any(getattr(a, "is_deleted", lambda: False)()
                       for a in jax.tree.leaves(self.pools))

    def contain_fault(self, exc: BaseException) -> str:
        """Classify and (when possible) contain an exception that escaped
        :meth:`step`. Returns ``"request"`` when the fault was contained
        per-request — the faulting action's request(s) re-queued with
        logical-step backoff, or quarantined with ``req.error`` after
        ``serving.fault.max_request_retries`` — ``"fatal"`` when the
        donated pools died mid-step and the caller must run
        :meth:`restart_engine` (or give up), or ``"unattributed"`` when
        nothing could be re-queued (the exception fired before an action
        was chosen, e.g. a broken scheduling policy): per-request retry
        budgets cannot bound that class, so the caller must escalate
        rather than spin on a deterministic recurrence. Either way the
        fault is recorded (``serve.fault`` event,
        ``serving/step_faults{kind=}``). The closed loop never calls
        this: ``generate_batch`` propagates, exactly like its
        :class:`PoolExhausted` contract."""
        self.land()                  # step() has, before it raised
        kind, payload = self.last_action or ("unknown", None)
        # the LABEL is the finer dispatch site (a cow/fetch sub-step of a
        # prefill action attributes to cow/fetch); request attribution
        # below still follows the enclosing action's payload
        site = self.fault_site if self.fault_site is not None else kind
        msg = f"{type(exc).__name__}: {exc}"
        if self.ev is not None:
            # payload key "action", not "kind": the recorder's own kind
            # argument is the event type
            self.ev.emit("serve.fault", action=site, error=msg)
        if self.sched.telemetry is not None:
            self.sched.telemetry.step_faults.labels(kind=site).inc()
        logger.warning(f"serving step fault ({site}): {msg}")
        if not self.pools_alive():
            return "fatal"
        known = _ACTION_KINDS.get(kind)
        # a fused step has no single culprit: every row that still runs
        # re-queues (recompute keeps each greedy-identical), so whichever
        # request is poison accrues retries until quarantine while the
        # innocent ones recompute (their retry counts reset as soon as
        # they emit a token again)
        reqs = [r for r in known.rows(payload) if r.state == "running"] \
            if known is not None else []
        if not reqs:
            return "unattributed"
        # REVERSED: each requeue appendlefts, so walking the batch
        # back-to-front leaves the earliest-admitted request at the queue
        # head — the same fairness preemption and reset_pool preserve
        for r in reversed(reqs):
            self._retry_or_quarantine(r, msg)
        self._flush_finished()
        return "request"

    def _retry_or_quarantine(self, req, msg: str) -> None:
        req.retry_count += 1
        if req.retry_count > self.fault_max_retries:
            self.sched.fail_request(
                req, f"quarantined after {self.fault_max_retries} "
                     f"step-fault retries: {msg}")
            return
        backoff = self.fault_backoff_steps * (1 << (req.retry_count - 1))
        self.sched.requeue_for_retry(req, backoff, error=msg)

    def restart_engine(self) -> None:
        """Crash-safe engine recovery after an engine-fatal step fault:
        rebuild the pool workspace, the block allocator and the fused-step
        jits (each entry recompiles AT MOST once per restart — the
        ``serving_faulted_steady`` contract), then re-admit every
        in-flight request from prompt + generated tokens through
        :meth:`ContinuousBatchingScheduler.reset_pool` — the exact
        recovery recompute-preemption already proves greedy-identical.
        The content-addressed host KV tier survives (its bytes live in
        host RAM); the device prefix cache starts cold."""
        self.land()
        engine, sched = self.engine, self.sched
        sched.allocator.set_spill(None)      # hook captured the dead pools
        host_pool = sched.allocator.host_pool
        engine._paged_workspace = None
        engine._paged_alloc = None
        engine._paged_jits = None
        pools, _ = engine._paged_pools(self.num_blocks, self.bs)
        alloc = engine._paged_allocator(self.num_blocks, self.bs,
                                        sched.prefix_caching, False)
        alloc.attach_host_pool(host_pool)
        sched.reset_pool(alloc)
        self._programs = dict(zip(_DISPATCH_SITES,
                                  engine._ensure_paged_jits()))
        self.pools = pools
        if self._kv_spill:
            alloc.set_spill(self._spill_block)

    # ---- tiered KV cache: demote (D2H) / re-materialize (H2D) ---- #

    def _spill_block(self, block: int, key: bytes) -> bool:
        """Allocator demotion hook: gather ``block``'s per-layer k/v
        slices (one jitted program, block index traced) and hand them to
        the host pool, which starts the async D2H copy — dispatched
        BEFORE the reclaiming owner's writes, so stream order reads the
        pre-overwrite content, and overlapping the running decode loop
        the way weight streaming overlaps layer copies. Never raises:
        any failure degrades to today's destroy-on-reclaim (the host
        pool counts and warns)."""
        hp = self.sched.allocator.host_pool
        if hp is None:
            return False
        prev_site = self.fault_site
        self.fault_site = "spill"    # degraded internally below, but a
        # non-Exception escape (SimulatedCrash) should still read "spill"
        try:
            # the gather does not donate (the pools live on): no "post"
            sl, t0 = self._dispatch("spill", self.pools, np.int32(block),
                                    post=False, spanned=False)
            ok = hp.put(key, sl["k"], sl["v"])
        except Exception as e:          # SimulatedCrash (BaseException)
            # and record_* invariants still propagate; everything else
            # must degrade — a spill is best-effort cache retention
            hp._count_error("spill (gather)", e)
            self.fault_site = prev_site
            return False
        self.fault_site = prev_site
        if ok:
            # no sync: the slice DELIBERATELY brackets only the gather
            # dispatch + async-copy kick-off. The D2H itself overlaps the
            # next fused steps (that overlap is the whole point), so a wait
            # here would serialize what the tier exists to hide
            self._book("spill", t0, [("kv.spill", dict(
                blocks=1, bytes=int(sl["k"].nbytes) + int(sl["v"].nbytes),
                block=block))])
            if self.sched.telemetry is not None:
                self.sched.telemetry.kv_spills.inc()
        return ok

    def demote_prompt(self, tokens) -> int:
        """Force-demote ``tokens``'s committed FULL blocks into the host
        tier (:meth:`BlockAllocator.demote_chain`) — the prefill→decode
        KV handoff: a prefill replica calls this once a warm-up request
        retires, publishing the prompt's KV in the SHARED host pool where
        the decode replica's tiered admission finds it. Single-threaded
        by the session contract (the always-on loop routes it through its
        command intake); returns the number of blocks demoted (0 when
        the session has no spill hook / host tier)."""
        if self._closed:
            raise RuntimeError("serving session is closed")
        if self._stateful or self._window:
            raise NotImplementedError(
                "a prefill->decode handoff moves KV blocks through the host "
                "tier, and this model keeps a recurrent state or a window "
                "layer's ring beside them that no block holds")
        if not self._kv_spill:
            return 0
        self.land()
        return self.sched.allocator.demote_chain(tokens)

    def _run_fetches(self, req, pools):
        """Land the admission's host-tier hits H2D: device_put each
        demoted ``[L, bs, KV*Hd]`` slice (head-sharded under tp, like
        the pools) and scatter it into the request's freshly allocated
        block via the jitted per-block program. Runs BEFORE any of the
        request's prefill compute reads the blocks. Each promoted block
        registers under its chain key only NOW — content actually on
        device — and its host entry is dropped (a key lives in one
        tier); the COW split's private copy (key None) stays
        unregistered and keeps its host entry cached."""
        fetches = req.fetch_pending
        req.fetch_pending = []
        if not fetches:
            return pools
        with self.phase("kv_fetch", blocks=len(fetches)):
            return self._land_fetches(req, pools, fetches)

    def _land_fetches(self, req, pools, fetches):
        """The H2D copies and scatters of :meth:`_run_fetches`."""
        prev_site = self.fault_site
        self.fault_site = "fetch"    # the copies label as "fetch" too;
        # restored only on the success path so containment sees the site
        alloc, tel = self.sched.allocator, self.sched.telemetry
        sh = self.engine._kv_slice_sharding()
        t0 = time.monotonic_ns() if self.ev is not None else 0
        nbytes = 0
        ntokens = 0
        for dst, key, k_np, v_np, tokens in fetches:
            ks = jax.device_put(jnp.asarray(k_np), sh)
            vs = jax.device_put(jnp.asarray(v_np), sh)
            # no serve.dispatch: the landing lies under serve.kv_fetch
            pools, _ = self._dispatch("fetch", pools, np.int32(dst), ks, vs,
                                      spanned=False)
            nbytes += int(k_np.nbytes) + int(v_np.nbytes)
            ntokens += int(tokens)
            if key is not None:
                alloc.register(dst, key)
                if alloc.host_pool is not None:
                    alloc.host_pool.remove(key)
        if tel is not None:
            # observed at LANDING, not admission: a preempt-before-fetch
            # re-admission must not double-count an H2D that never ran
            tel.kv_fetch_hits.inc(len(fetches))
            if ntokens:
                tel.kv_fetch_tokens.inc(ntokens)
        self._book("fetch", t0, [("kv.fetch", dict(
            blocks=len(fetches), bytes=nbytes))], rid=req.rid, sync=pools)
        self.fault_site = prev_site
        return pools

    def _cow_split(self, req, pools):
        """Copy-on-write split ahead of a chunk: the request restarts
        mid-block inside a SHARED cached block — give it a private device
        copy before any of its writes land."""
        if req.cow_pending is None:
            return pools
        src, dst = req.cow_pending
        pools, t0 = self._dispatch("cow", pools, np.int32(src), np.int32(dst))
        self._book("cow", t0, [("req.cow_copy", dict(src=src, dst=dst))],
                   rid=req.rid, sync=pools)
        req.cow_pending = None
        return pools

    # ---- the executor: one pipeline for every kind of action ---- #

    def _dispatch(self, site, *operands, pre=True, post=True, spanned=True):
        """Call ``site``'s program: the ONE place where a fault, the host
        clock and ``serve.dispatch`` attach, for the four action kinds, the
        sub-dispatches ``cow``, ``fetch`` and ``spill`` and the sampler
        (``sample``: under ``serve.sample``, no fault consult of its own).
        Returns ``(outputs, t0)``; numpy operands go to the device here. Fault
        injection (utils/fault_injection.fail_step) costs one None check a
        consult. ``"pre"`` fires before the dispatch: the pools are intact,
        the fault is containable per request. ``"post"`` fires between the
        donating dispatch and the caller's adoption of the outputs:
        ``self.pools`` still names the consumed buffers, which leaves the
        session as a mid-step device death would, engine-fatal. ``fault_site``
        reads the finer site for ``step_faults{kind=}`` meanwhile, and on if
        the dispatch raises."""
        prev_site = self.fault_site
        self.fault_site = site
        if pre:
            _step_fault(site, "pre")
        with self.phase("dispatch") if spanned else nullcontext():
            t0 = time.monotonic_ns() if self.ev is not None else 0
            out = self._programs[site](*map(_on_device, operands))
            if post:
                _step_fault(site, "post")
        self.fault_site = prev_site
        return out, t0

    def _book(self, site, t0, events, rid=None, sync=None) -> None:
        """Book one timed phase when the recorder is on (off: no clock is
        read, nothing waits for the device): a slice from ``t0`` to now for
        each of ``events`` ((kind, fields); ``rid`` unless they name their
        own) and the phase ledger's sample for ``site``. ``sync`` is what to
        wait for where the site fetched nothing of its own: dispatch is
        async, the slice would clock microseconds of it (the DS005 rule)."""
        if self.ev is None:
            return
        if sync is not None:
            jax.block_until_ready(sync)
        dur = time.monotonic_ns() - t0
        for kind, fields in events:
            self.ev.emit(kind, t_ns=t0, dur_ns=dur, **{"rid": rid, **fields})
        if self.sched.telemetry is not None:
            self.sched.telemetry.phase(site, dur / 1e6, rid=rid)

    def _launch(self, name, kind, reqs) -> Optional[_Launched]:
        """Put one action on the device's queue: its sub-dispatches, its
        operands, its program and its sampler, then the tokens' copy to the
        host — and wait for none of it. What differs by kind is in ``kind``
        (:class:`_ActionKind`); the data between the phases is explicit.
        The pools a dispatch hands back are adopted at once, so that a
        fault between a donating dispatch and its adoption (``"post"``)
        leaves ``self.pools`` naming consumed buffers, as a device death
        would. Between this and :meth:`land` the step belongs to the
        session alone (see the class docstring)."""
        tel = self.sched.telemetry
        self.fault_site = name       # the action's own "pre" ticks the
        # injector's step counter, ahead of the sub-dispatches' consults
        _step_fault(name, "pre", tick=True)
        if kind.inputs is None:
            # retry-backoff idle tick: no device work, clock advanced
            return None
        for sub in kind.before:
            # each adopted as it lands: a fault at a later site leaves
            # the pools the earlier ones handed back
            self.pools = sub(self, reqs[0], self.pools)
        loop = self.loop
        with self.phase("inputs"):
            (toks, *rest), part = kind.inputs(self, reqs)
        if kind.fed and self._flight is not None:
            self.sched.stats["decode_steps_ahead"] += 1
            if tel is not None:
                tel.decode_steps_ahead.inc()
            tok = self._flight.tok
            # one query at the launch: with a telemetry, the one the inputs'
            # exit has just made for the late time's bracket
            if tok is not None and (tok.is_ready() if loop is None
                                    else loop.finished):
                # the queue is one deep: the step in flight has finished,
                # so the device has nothing to run until this dispatch
                self.sched.stats["decode_steps_late"] += 1
                if tel is not None:
                    tel.decode_steps_late.inc()
        before_ns = 0 if loop is None else loop.t
        spent = self.pools
        (logits, pools, *aux), t0 = self._dispatch(
            name, self.engine.params, toks, spent, *rest, pre=False)
        self.pools = pools
        if loop is not None:
            # how long the device had been dry when this dispatch returned
            loop.launched(self._flight is not None, before_ns)
        if aux and tel is not None:
            # an MoE model's assignment counts, on their way beside
            # the tokens: no wait of their own
            aux[0].copy_to_host_async()
        tok = kind.sample(self, logits, reqs, part)
        step = _Launched(name, kind, reqs, part, tok, aux, t0, logits, spent)
        if loop is not None:
            loop.watch(step.tok)
        return step

    def _advance(self, step: _Launched) -> None:
        """The positional half of a launched step, once the step before it
        has landed: every token the blocks it fills are keyed by is on the
        host then, and so is an EOS that makes a row of it an overshoot."""
        step.live = [r.state == "running" for r in step.reqs]
        if step.kind.advance is not None:
            for r, live in zip(step.reqs, step.live):
                if live:
                    step.kind.advance(self.sched, r, step.part)

    def land(self) -> None:
        """Fetch, commit and release the step in flight, if there is one:
        the tokens' fetch (the one wait for the device), the MoE counts, the
        booked phase, ``record``/``on_tokens`` row by row, the retirements,
        and the drop of what the step held. A front-end calls this before
        it touches the scheduler's running rows itself."""
        step, self._flight = self._flight, None
        if step is None:
            return
        tel = self.sched.telemetry
        reqs, part = step.reqs, step.part
        try:
            with self.phase("fetch"):
                got = None if step.tok is None else np.asarray(step.tok)
        except BaseException:
            self.sched.abandon(reqs)     # recomputed, like a preemption
            raise
        with self._booking():
            # a row whose EOS landed meanwhile is left out: its token is
            # dropped
            rows = [(r, t) for r, t, live in zip(
                reqs, step.kind.tokens(got, reqs), step.live) if live]
            if step.aux and tel is not None:
                tel.count_moe(np.asarray(step.aux[0]), self.zero_experts,
                              self.moe_row_tile)
            if self.ev is not None:
                # AFTER the tokens' fetch (it is the sync: emitting first
                # would clock async dispatch; a step that fetched none
                # waits in _book) and BEFORE the commit, so a retirement
                # this step triggers lands after its last slice
                rid, events = step.kind.events(
                    [r for r, _ in rows], part, [t for _, t in rows])
                self._book(step.name, step.t0, events, rid=rid,
                           sync=None if any(t for _, t in rows)
                           else step.logits)
        with self.phase("commit"):
            self._commit(step, rows)
            self._flush_finished()
        with self.phase("release"):
            # the span is there because dropping the consumed pool handles
            # costs the host a millisecond (PERF.md §5); the step's logits
            # die under it too, and not at the return
            step.spent = step.logits = step.tok = None

    def _commit(self, step: _Launched, rows) -> None:
        """``record`` and ``on_tokens``, row by row. With a telemetry, the
        newest launched step is asked whether it has finished every
        ``COMMIT_POLL_ROWS`` rows (the late time's bracket stays that
        narrow across the loop's longest phase), and on one landed step in
        ``COMMIT_SAMPLE`` the two halves of each row are timed apart."""
        loop = self.loop
        if loop is None:
            return self._commit_rows(step, rows)
        if not rows:
            return
        timed = loop.landed % loop.COMMIT_SAMPLE == 0
        loop.landed += 1
        commit = self._commit_rows_timed if timed else self._commit_rows
        for lo in range(0, len(rows), loop.COMMIT_POLL_ROWS):
            commit(step, rows[lo:lo + loop.COMMIT_POLL_ROWS])
            loop.poll_now()

    def _commit_rows(self, step, rows) -> None:
        record, sched, part = step.kind.record, self.sched, step.part
        on_tokens = self.on_tokens
        for r, tokens in rows:
            out = record(sched, r, part, tokens)
            if out is not None:
                tokens = out         # what the row streams of what landed
            if tokens and on_tokens is not None:
                on_tokens(r, tokens)

    def _commit_rows_timed(self, step, rows) -> None:
        """:meth:`_commit_rows` with a clock read between a row's halves."""
        record, sched, part = step.kind.record, self.sched, step.part
        on_tokens, loop = self.on_tokens, self.loop
        now_ns = loop.now
        for r, tokens in rows:
            t0 = now_ns()
            out = record(sched, r, part, tokens)
            t1 = now_ns()
            if out is not None:
                tokens = out
            if tokens and on_tokens is not None:
                on_tokens(r, tokens)
            loop.record_ns += t1 - t0
            loop.wake_ns += now_ns() - t1
        loop.sampled_rows += len(rows)

    # ---- what differs by kind: the hooks _ACTION_KINDS names ---- #

    def _piece_inputs(self, req, prefix, start, n, bucket_of=None):
        """Tokens ``start .. start + n`` of ``prefix`` in their compile
        bucket (the bucket of ``bucket_of`` tokens where that is given), the
        request's block table, and the pool slots they write."""
        engine = self.engine
        Tb = engine._bucket(bucket_of or n, engine.module.config.max_seq)
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :n] = prefix[start:start + n]
        table = np.asarray(req.blocks, np.int32)
        slots = engine._flat_slots(table, start, n, Tb, self.bs)
        if self.sched.telemetry is not None:
            self.sched.telemetry.count_prefill(n, Tb, start)
            if self._stateful and start == 0:
                self.sched.telemetry.count_state_reset()
        return toks, table, slots.astype(np.int32), np.int32(n - 1)

    def _kept_of(self, req, window: bool = True):
        """The trailing operands of a prefill program: the request's slot
        (a model with recurrent state), then its window table (one with
        window layers; a chunk program takes none)."""
        kept = (np.int32(req.state_slot),) if self._stateful else ()
        if self._window and window:
            wt = np.zeros((self._ring,), np.int32)          # zeros → dummy
            wt[:len(req.window_blocks)] = req.window_blocks
            kept += (wt,)
        return kept

    def _prefill_inputs(self, reqs):
        # generation by blocks prefills the prefix's whole generation
        # blocks only, in the bucket of the whole prefix: a front-end that
        # warms one prompt a bucket (``_bucket`` of its length) has warmed
        # the program this one takes
        whole = reqs[0].prefix()
        prefix = whole[:reqs[0].prefill_target]
        toks, _, slots, last = self._piece_inputs(
            reqs[0], prefix, 0, prefix.size, bucket_of=whole.size)
        return (toks, slots, last, *self._kept_of(reqs[0])), (0, prefix.size)

    def _chunk_inputs(self, reqs):
        req = reqs[0]
        start, n = req.pos, self._chunk_len(req)
        toks, table, slots, last = self._piece_inputs(req, req.prefix(),
                                                      start, n)
        # the chunk attends over the gathered table, so its cost is
        # O(table width × block_size) per layer — bucket the width to the
        # next power of two of the request's OWN block count (≤ log2(n_max)
        # compiles) instead of paying n_max (= max_seq worth of KV) for
        # every short cache-hit tail
        nb = min(self.n_max, 1 << max(int(table.size) - 1, 0).bit_length())
        bt = np.zeros((1, nb), np.int32)
        bt[0, :table.size] = table
        return (toks, bt, slots, np.int32(start), last,
                *self._kept_of(req, window=False)), (start, n)

    def _decode_inputs(self, reqs):
        tel = self.sched.telemetry
        bt = np.zeros((self.W, self.n_max), np.int32)       # zeros → dummy
        pos = np.zeros((self.W,), np.int32)
        toks = np.zeros((self.W, 1), np.int32)
        # a row of the step in flight takes its token from that step's
        # sampler on the device, at the row it had there (rows move as
        # requests retire and are admitted); the host holds the others'
        idx = np.full((self.W,), -1, np.int32)
        ahead = self._flight
        src = {} if ahead is None else {
            id(r): j for j, r in enumerate(ahead.reqs)}
        # the window layers' tables beside ``bt``, built in the same pass:
        # a row's changes only on the steps in which it takes a block
        # below a whole ring
        wt = np.zeros((self.W, self._ring), np.int32) \
            if self._window else None                       # zeros → dummy
        held = 0
        for i, r in enumerate(reqs):
            bt[i, :len(r.blocks)] = r.blocks
            if wt is not None:
                wt[i, :len(r.window_blocks)] = r.window_blocks
                held += len(r.window_blocks)
            pos[i] = r.pos
            j = src.get(id(r))
            if j is None:
                toks[i, 0] = r.last_token
            else:
                idx[i] = j
        if tel is not None:
            tel.decode_live_kv_tokens.inc(int(pos.sum()))
            # an idle row is copied nothing
            tel.decode_live_kv_blocks.inc(
                int((pos[:len(reqs)] // self.bs + 1).sum()))
            if self._stateful:
                tel.count_state(len(reqs))
        kept = ()
        if self._stateful:
            slots = np.zeros((self.W,), np.int32)           # zeros → dummy
            slots[:len(reqs)] = [r.state_slot for r in reqs]
            kept = (slots,)
        if wt is not None:
            kept += (wt,)
            if tel is not None:
                tel.count_window(pos, len(reqs), self._window, self.bs,
                                 held, self._ring)
        # _tok_dev: a request decodes after its own prefill, so the sampler
        # has left tokens there by the first decode step
        return ((self._tok_dev, idx, toks), bt, pos, *kept), None

    def _block_inputs(self, reqs):
        """A fused pass of generation by blocks: W main entries, each row's
        table, the depth of the block its pass decides in, that block's
        feed (from the pass in flight on the device where the row rode it,
        else the host's state) and the scheduler's plan for it (denoise or
        lone commit, how many to decide), then the rider entries: the whole
        block of each row whose commit rides, under the row's own table at
        its committed depth, while the row's main entry is its NEXT block,
        all undecided. The plan is also the step's ``part``, by rid."""
        tel, sched, Bg = self.sched.telemetry, self.sched, self._gen.block
        W, N = self.W, self.W + self.sched.ride_slots
        bt = np.zeros((N, self.n_max), np.int32)            # zeros → dummy
        pos = np.zeros((N,), np.int32)
        host = np.full((N, Bg), -1, np.int32)
        idx = np.full((N,), -1, np.int32)
        n_decide = np.zeros((W,), np.int32)
        commit = np.ones((W,), bool)     # an idle row's block stays undecided
        ahead = self._flight
        src = {} if ahead is None or ahead.name != "block" else {
            id(r): j for j, r in enumerate(ahead.reqs)}
        plan = {}
        rides = 0
        for i, r in enumerate(reqs):
            step = plan[r.rid] = sched.plan_block(r)
            bt[i, :len(r.blocks)] = r.blocks
            pos[i] = r.pos
            n_decide[i] = step.n
            at = i                       # the entry of the row's block as
            if step.ride:                # it stands: its own, or a rider
                at = W + rides
                rides += 1
                bt[at], pos[at] = bt[i], r.pos
                pos[i] += Bg
            commit[i] = step.alone
            j = src.get(id(r))
            if j is None:
                host[at] = sched.block_state(r)
            else:
                idx[at] = j
        if tel is not None:
            # an entry's pass reads its row's committed tokens and its own
            # block: a row with a rider is read twice
            live = np.concatenate([pos[:len(reqs)], pos[W:W + rides]])
            tel.decode_live_kv_tokens.inc(int(live.sum()) + Bg * live.size)
            tel.decode_live_kv_blocks.inc(
                int(((live + Bg - 1) // self.bs + 1).sum()))
            tel.count_block(len(reqs), int(commit[:len(reqs)].sum()), rides)
        prev = self._blk_dev if self._blk_dev is not None \
            else np.full((W, Bg), -1, np.int32)
        draw = ()
        if self.temperature > 0.0:
            self.rng, key = jax.random.split(self.rng)
            draw = (key, np.float32(self.temperature), self.top_k)
        return ((prev, idx, host), bt, pos, n_decide, commit, *draw), plan

    def _block_kept(self, state, reqs, part):
        """Launch side of a block step: its program decided already (the
        ``unmask`` ops); the rows' blocks stay on the device for the next
        pass's feed while their copy to the host starts."""
        self._blk_dev = state
        state.copy_to_host_async()
        return state

    @staticmethod
    def _block_states(got, reqs):
        """Landing side: each row's block as the pass left it."""
        return got[:len(reqs)].tolist()

    def _verify_inputs(self, reqs):
        # speculative multi-token step: the fused decode math over each
        # request's window (pending token + proposed candidates) at once
        engine, W, spec_wb, bs = self.engine, self.W, self.spec_wb, self.bs
        bt = np.zeros((W, self.n_max), np.int32)       # zeros → dummy
        pos = np.zeros((W,), np.int32)
        toks = np.zeros((W, spec_wb), np.int32)
        slotm = np.zeros((W, spec_wb), np.int32)
        zt = np.zeros((1,), np.int32)
        for i in range(W):
            if i >= len(reqs):
                # inactive rows: junk routed to the dummy block
                slotm[i] = engine._flat_slots(zt, 0, 0, spec_wb, bs)
                continue
            r = reqs[i]
            nv = 1 + len(r.spec_tokens)
            toks[i, 0] = r.last_token
            toks[i, 1:nv] = r.spec_tokens
            table = np.asarray(r.blocks, np.int32)
            bt[i, :table.size] = table
            pos[i] = r.pos
            slotm[i] = engine._flat_slots(table, r.pos, nv, spec_wb, bs)
        return (toks, bt, slotm, pos), None

    def _sample(self, logits, reqs, part):
        """Launch side of a sampled step: one token a request, none from a
        chunk that is not its prefill's last. The sampler is dispatch only
        (argmax/categorical run on the device); the tokens stay there, at
        the decode width for the next step's feed, while their copy to the
        host starts."""
        if self._gen is not None \
                or (part is not None and sum(part) < reqs[0].prefill_target):
            # a model that generates by blocks samples nothing at a prefill
            return None
        with self.phase("sample"):
            key = temperature = None     # greedy draws nothing
            if self.temperature > 0.0:
                self.rng, key = jax.random.split(self.rng)
                temperature = np.float32(self.temperature)
            tok, _ = self._dispatch(
                "sample", logits, key, temperature, self.top_k, self.W,
                pre=False, post=False, spanned=False)
            self._tok_dev = tok
            tok.copy_to_host_async()
        return tok

    @staticmethod
    def _sampled(tok, reqs):
        """Landing side: the fetched tokens, row by row."""
        if tok is None:
            return [[] for _ in reqs]
        return [[int(t)] for t in tok[:len(reqs)]]

    def _greedy(self, logits, reqs, part):
        """Launch side of a verify step: the same argmax the decode path's
        sampler runs, at every window position."""
        with self.phase("sample"):
            return jnp.argmax(logits.astype(jnp.float32), axis=-1)

    @staticmethod
    def _accepted(greedy, reqs):
        """Greedy acceptance over each row's verify window: the accepted
        candidate prefix plus the first-mismatch token is exactly what
        token-by-token decode would emit. The fetch before it is the sync
        point, so the spec_verify slices clock device time."""
        out = []
        for i, r in enumerate(reqs):
            cands = r.spec_tokens
            n_acc = 0
            while n_acc < len(cands) \
                    and int(greedy[i, n_acc]) == cands[n_acc]:
                n_acc += 1
            emitted = list(cands[:n_acc]) + [int(greedy[i, n_acc])]
            # truncate at eos HERE so the event's accepted= matches what
            # record_verify will commit (its own truncation stays as the
            # invariant check)
            if r.eos is not None and int(r.eos) in emitted:
                emitted = emitted[:emitted.index(int(r.eos)) + 1]
            out.append(emitted)
        return out

    # ---- lifecycle ---- #

    def close(self) -> None:
        """Always-run bookkeeping (the closed loop runs this in its
        ``finally``): rid uniqueness across serves — even an aborted serve
        must not let the next one reuse rids in the shared flight-recorder
        ring — the serve-stats stash, and releasing the engine's
        active-session slot. Idempotent."""
        if self._closed:
            return
        try:
            self.land()              # nothing stays in flight
        finally:
            self._close()

    def _close(self) -> None:
        self._closed = True
        engine = self.engine
        # the demotion hook captures THIS session's live pools: a stale
        # hook on the persistent allocator would gather freed buffers
        self.sched.allocator.set_spill(None)
        engine._serve_rid_base = self.sched._next_rid
        # step accounting for the serve that just ran (plain host
        # counters, kept even when the metrics registry is off):
        # accepted_tokens_per_step > 1 is the speculation win
        engine._last_serve_stats = dict(self.sched.stats)
        if engine._active_session is self:
            engine._active_session = None

    def end(self) -> None:
        """Success-path epilogue: serving memory gauges and the hand-back
        of the (donated-through) pools into the engine's persistent
        workspace, so the next session — or ``generate_batch`` call —
        reuses them and, with prefix caching, re-hits this session's
        registered blocks."""
        engine = self.engine
        if engine._telemetry is not None:
            # HBM live/peak + host RSS after the serve (the pools and the
            # decode workspace are the serving memory story)
            from deepspeed_tpu.monitor.health import sample_memory_gauges
            sample_memory_gauges(engine._tel_reg)
        engine._paged_workspace = (
            self.num_blocks, self.bs, self.pools,
            (engine._state_slots(),
             engine._window_blocks(self.num_blocks, self.bs)))


class _ActionKind(NamedTuple):
    """What differs between the kinds of action the scheduler hands over;
    ``_ServeSession._launch`` and ``land`` run every kind through the same
    phases and ``_ACTION_KINDS`` is the one place a kind is looked up.
    ``s``: the session; ``part``: the ``(start, tokens)`` of its prefix a
    prefill step computes (None for a fused step); ``out``: a token list for
    each of ``reqs``."""
    #: ``(payload) -> reqs``: the requests it carries, in row order
    rows: Callable
    #: ``(s, reqs)``: what ``serve.exec`` says of it besides its kind, the
    #: identifiers a kept trace is followed by
    says: Callable
    #: ``(s, req, pools) -> pools`` each: sub-dispatches ahead of its own
    before: Tuple[Callable, ...] = ()
    #: ``(s, reqs) -> (operands, part)``: its program's operands after the
    #: parameters, less the pools (always third), built in numpy under
    #: ``serve.inputs`` with the counters taken there; None runs nothing
    inputs: Optional[Callable] = None
    #: its first operand is the feed ``(prev, idx, toks)``: the newest
    #: sampled tokens on the device, the rows that take theirs from there
    #: (the step in flight's), and the host's tokens for the others
    fed: bool = False
    #: may be launched while the step before it is unfetched, and stay in
    #: flight itself: it reads no token on the host. (A prefill of a request
    #: that is a row of the step in flight cannot come up: the scheduler
    #: has those rows past their prefill.)
    ahead: bool = False
    #: ``(s, logits, reqs, part) -> array or None``: the sampler's dispatch,
    #: at the launch
    sample: Optional[Callable] = None
    #: ``(fetched, reqs) -> out``: how tokens come out of what it left, at
    #: the landing
    tokens: Optional[Callable] = None
    #: ``(reqs, part, out) -> (rid, [(event kind, fields), ...])``: its
    #: flight-recorder events, and whose exemplar its ledger sample is
    events: Optional[Callable] = None
    #: ``(sched, req, part)``: the positional half of the scheduler's
    #: ``record_*``, known at the launch (None: ``record`` does it all)
    advance: Optional[Callable] = None
    #: ``(sched, req, part, tokens)``: the rest of it, at the landing. A
    #: kind whose landed output is not yet tokens (a block step lands the
    #: rows' blocks) returns the tokens the row streams of it
    record: Optional[Callable] = None


def _commits_of(steps, **says):
    """``says`` and how a block step's rows commit: alone, or as riders."""
    steps = list(steps)
    return dict(says, commits=sum(s.alone for s in steps),
                rides=sum(s.ride for s in steps))


def _commit_one(sched, r, part, t):
    """A prefill's token, if this piece of it sampled one."""
    if t:
        sched.commit_token(r, t[0])


# an action carries one request (an admission's prefill, whole or a chunk
# at a time) or the running rows of a fused step
_ONE = dict(rows=lambda req: [req], says=lambda s, reqs: {
    "rid": reqs[0].rid, "tokens": s._chunk_len(reqs[0])}, ahead=True,
    sample=_ServeSession._sample, tokens=_ServeSession._sampled,
    record=_commit_one)
_FUSED = dict(rows=lambda reqs: reqs,
              says=lambda s, reqs: {"rows": len(reqs)})
_ACTION_KINDS: Dict[str, _ActionKind] = {
    "wait": _ActionKind(rows=lambda _: [], says=lambda s, reqs: {}),
    "prefill": _ActionKind(
        **_ONE, before=(_ServeSession._run_fetches,),
        inputs=_ServeSession._prefill_inputs,
        events=lambda reqs, part, out: (
            reqs[0].rid, [("req.prefill", dict(tokens=part[1]))]),
        advance=lambda sched, r, part: sched.advance_prefill(r)),
    "prefill_chunk": _ActionKind(
        **_ONE, before=(_ServeSession._run_fetches, _ServeSession._cow_split),
        inputs=_ServeSession._chunk_inputs,
        events=lambda reqs, part, out: (reqs[0].rid, [
            ("req.prefill_chunk", dict(start=part[0], tokens=part[1]))]),
        # the sampled token rides the last chunk alone
        advance=lambda sched, r, part: sched.advance_prefill_chunk(
            r, part[1], last=sum(part) >= r.prefill_target)),
    "verify": _ActionKind(
        **_FUSED, inputs=_ServeSession._verify_inputs,
        sample=_ServeSession._greedy, tokens=_ServeSession._accepted,
        # an event a row (they carry identity), one ledger sample a step
        events=lambda reqs, part, out: (None, [
            ("req.spec_verify", dict(rid=r.rid, window=1 + len(r.spec_tokens),
                                     accepted=len(t) - 1))
            for r, t in zip(reqs, out)]),
        record=lambda sched, r, part, t: sched.record_verify(r, t)),
    "decode": _ActionKind(
        **_FUSED, inputs=_ServeSession._decode_inputs, fed=True, ahead=True,
        sample=_ServeSession._sample, tokens=_ServeSession._sampled,
        events=lambda reqs, part, out: (None, [
            ("decode.tick", dict(rids=[r.rid for r in reqs], n=len(reqs)))]),
        advance=lambda sched, r, part: sched.advance_decode(r),
        record=lambda sched, r, part, t:
            sched.commit_token(r, t[0], fused=True)),
    # generation by diffusion over blocks: a fused pass over every running
    # row's open block, denoise rows, lone commits and riders together
    # (``part``: the scheduler's plan by rid). Ahead like decode: under the
    # static rules the plan reads no token (``plans_ahead`` lands the
    # data-dependent one)
    "block": _ActionKind(
        rows=lambda reqs: reqs,
        says=lambda s, reqs: _commits_of(map(s.sched.plan_block, reqs),
                                         rows=len(reqs)),
        inputs=_ServeSession._block_inputs, fed=True, ahead=True,
        sample=_ServeSession._block_kept, tokens=_ServeSession._block_states,
        # the fused step's event, as a decode step's: what the recorder's
        # request tracks and the latency anatomy read a row's step from
        events=lambda reqs, part, out: (None, [
            ("decode.tick", _commits_of(
                (part[r.rid] for r in reqs),
                rids=[r.rid for r in reqs], n=len(reqs)))]),
        advance=lambda sched, r, part: sched.advance_block(r, *part[r.rid]),
        record=lambda sched, r, part, t:
            sched.record_block(r, *part[r.rid], t)),
}
