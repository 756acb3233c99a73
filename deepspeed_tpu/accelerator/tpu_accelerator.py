"""TPU (and CPU-mesh) accelerator implementation over JAX.

Reference parity: ``accelerator/cuda_accelerator.py`` reimagined for XLA:
- streams/events: XLA dispatch is already async; ``synchronize`` drains it.
- RNG: functional ``jax.random`` keys instead of stateful generators; a
  per-device stateful tracker lives in ``runtime/activation_checkpointing``.
- memory stats come from ``device.memory_stats()``.
- op builders resolve against the Pallas/C++ kernel registry.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, List, Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self, platform: Optional[str] = None):
        super().__init__()
        self._name = platform or "tpu"
        self._communication_backend_name = "xla"
        self._current_device = 0

    # --------------------------------------------------------------- #
    @property
    def _jax(self):
        import jax
        return jax

    def _devices(self):
        return self._jax.devices()

    def _local_devices(self):
        return self._jax.local_devices()

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def device(self, device_index: Optional[int] = None):
        devs = self._local_devices()
        return devs[device_index if device_index is not None else self._current_device]

    @contextlib.contextmanager
    def device_ctx(self, device_index: Optional[int] = None):
        with self._jax.default_device(self.device(device_index)):
            yield

    def set_device(self, device_index: int) -> None:
        self._current_device = device_index

    def current_device(self) -> int:
        return self._current_device

    def current_device_name(self) -> str:
        return f"{self._name}:{self._current_device}"

    def device_count(self) -> int:
        return len(self._devices())

    def local_device_count(self) -> int:
        return len(self._local_devices())

    def synchronize(self, device_index: Optional[int] = None) -> None:
        self._jax.effects_barrier()

    # ------------------------- RNG --------------------------------- #
    # Functional RNG: there is no mutable global generator — seeding
    # returns a fresh key the caller threads explicitly (reference
    # abstract_accelerator.py:44-67 surface, functional semantics).
    def random_seed(self, seed: int):
        self._seed = int(seed)
        return self._jax.random.key(seed)

    manual_seed = random_seed
    manual_seed_all = random_seed

    def initial_seed(self) -> int:
        """The last seed passed to manual_seed/random_seed (reference
        ``initial_seed()``: no arguments, returns the current seed)."""
        return getattr(self, "_seed", 0)

    def random(self):
        """The RNG namespace (reference ``accelerator.random`` returns
        ``torch.random``); here it is ``jax.random``."""
        return self._jax.random

    def is_available(self) -> bool:
        """True when the REQUESTED platform has devices (the generic
        device fallback would otherwise make this unconditionally true)."""
        try:
            return len(self._jax.devices(self._name)) > 0
        except RuntimeError:
            return False

    def default_generator(self, device_index: int):
        # Functional RNG: the "generator" is just a key derived per device.
        return self._jax.random.key(device_index)

    # ------------------------- memory ------------------------------ #
    def _stats(self, device_index: Optional[int] = None) -> dict:
        try:
            return self.device(device_index).memory_stats() or {}
        except Exception:
            return {}

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return self._stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return self._stats(device_index).get("peak_bytes_in_use", 0)

    def memory_cached(self, device_index: Optional[int] = None) -> int:
        return self._stats(device_index).get("pool_bytes", 0)

    def max_memory_cached(self, device_index: Optional[int] = None) -> int:
        return self._stats(device_index).get("largest_alloc_size", 0)

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return self._stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index: Optional[int] = None) -> int:
        stats = self._stats(device_index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def empty_cache(self) -> None:
        # XLA owns the allocator; nothing to flush.
        pass

    # Per-chip dense bf16 peak, TFLOP/s, keyed by jax's ``device_kind`` as
    # libtpu 0.0.34 spells it (one jax device per chip on these kinds).
    # Source: the Google Cloud TPU documentation page of each generation
    # ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e": peak compute per chip).
    PEAK_BF16_TFLOPS = {
        "TPU v4": 275.0,
        "TPU v5 lite": 197.0,   # v5e
        "TPU v5": 459.0,        # v5p
        "TPU v6 lite": 918.0,   # v6e
    }

    def peak_tflops(self) -> float:
        """Per-chip bf16 peak TFLOP/s of device 0's kind. A kind without a
        published entry raises ``LookupError``: a peak is a measurement's
        denominator, so there is no default."""
        kind = self._devices()[0].device_kind
        try:
            return self.PEAK_BF16_TFLOPS[kind]
        except KeyError:
            raise LookupError(
                f"no published bf16 peak for device_kind {kind!r} (known: "
                f"{sorted(self.PEAK_BF16_TFLOPS)})") from None

    def memory_stats(self, device_index: Optional[int] = None) -> dict:
        return self._stats(device_index)

    def memory_report(self) -> dict:
        """Per-local-device memory summary for the health/env surfaces:
        ``{device_name: {bytes_in_use, peak_bytes_in_use, bytes_limit,
        headroom_bytes}}``. Devices whose backend exposes no memory stats
        (e.g. the CPU test mesh) map to an empty dict — callers render
        "no stats" rather than fabricated zeros."""
        out = {}
        for i in range(self.local_device_count()):
            stats = self._stats(i)
            if stats:
                used = stats.get("bytes_in_use", 0)
                limit = stats.get("bytes_limit", 0)
                out[self.device_name(i)] = {
                    "bytes_in_use": used,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
                    "bytes_limit": limit,
                    "headroom_bytes": max(limit - used, 0),
                }
            else:
                out[self.device_name(i)] = {}
        return out

    def reset_peak_memory_stats(self, device_index: Optional[int] = None) -> None:
        # Not exposed by PJRT; peak stats are monotone per process.
        pass

    # ------------------------- dtype ------------------------------- #
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def is_triton_supported(self) -> bool:
        return False

    def preferred_dtype(self):
        import jax.numpy as jnp
        return jnp.bfloat16

    def supported_dtypes(self) -> List[Any]:
        import jax.numpy as jnp
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    # ------------------------- comm / misc ------------------------- #
    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    def on_accelerator(self, array) -> bool:
        try:
            platform = getattr(array, "platform", None)
            if callable(platform):
                return array.platform() != "cpu"
            shards = array.addressable_shards
            return shards[0].device.platform != "cpu"
        except Exception:
            return False

    def pin_memory(self, array):
        try:
            jax = self._jax
            dev = self.device()
            host_sharding = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
            return jax.device_put(array, host_sharding)
        except Exception:
            return array

    def range_push(self, msg: str) -> None:
        try:
            self._trace_stack.append(self._jax.profiler.TraceAnnotation(msg))
            self._trace_stack[-1].__enter__()
        except Exception:
            pass

    def range_pop(self) -> None:
        try:
            ann = self._trace_stack.pop()
            ann.__exit__(None, None, None)
        except Exception:
            pass

    @property
    def _trace_stack(self):
        if not hasattr(self, "_trace_stack_"):
            self._trace_stack_ = []
        return self._trace_stack_

    # ------------------------- op builders ------------------------- #
    def create_op_builder(self, class_name: str):
        builder = self.get_op_builder(class_name)
        return builder() if builder is not None else None

    def get_op_builder(self, class_name: str):
        from deepspeed_tpu.ops.registry import get_builder_class
        return get_builder_class(class_name)


class CPU_Accelerator(TPU_Accelerator):
    """CPU accelerator used by the unit tests (virtual 8-device mesh)."""

    def __init__(self):
        super().__init__(platform="cpu")
        self._communication_backend_name = "gloo"

    def _devices(self):
        return self._jax.devices("cpu")

    def is_bf16_supported(self) -> bool:
        return True

    def preferred_dtype(self):
        import jax.numpy as jnp
        return jnp.float32
